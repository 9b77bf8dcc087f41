"""The numbers that decide ``correct``, and how each is compared.

Each cell's configuration file states the limit of each number it compares
(``limits``); ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

# A leaf whose first reference gradient is under this share of the median
# leaf's moves by round-off alone; it is left out of the change comparison.
STILL_LEAF = 1e-3


def loss_gaps(prog: List[float], ref: List[float]) -> List[float]:
    """Relative gap of each step's loss."""
    assert len(prog) == len(ref), (prog, ref)
    return [abs(p - r) / abs(r) for p, r in zip(prog, ref)]


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             skip=()) -> Tuple[float, str]:
    """Worst gap between the program's and the reference's norm of a leaf,
    over the larger of that leaf's reference norm and the median leaf's.
    Returns (gap, leaf)."""
    assert set(prog) == set(ref), (sorted(prog), sorted(ref))
    median = statistics.median(ref.values())
    worst = (0.0, "")
    for k, r in ref.items():
        if k in skip:
            continue
        g = abs(prog[k] - r) / max(abs(r), median)
        if g >= worst[0]:
            worst = (g, k)
    return worst


def still_leaves(first_grad: Dict[str, float]) -> List[str]:
    median = statistics.median(first_grad.values())
    return sorted(k for k, v in first_grad.items() if v < STILL_LEAF * median)


def train_readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The two numbers a training cell compares. The losses are not
    compared: neither the float8 control nor a planted fault moves them by
    three times what sound runs read (PERF.md), so a limit on them could
    only fail sound runs; ``train_notes`` reports them."""
    skip = still_leaves(ref["grad_norms"])
    return {
        "grad_norm_gap": leaf_gap(prog["grad_norms"], ref["grad_norms"])[0],
        "change_gap": leaf_gap(prog["changes"], ref["changes"], skip)[0],
    }


def train_notes(prog: Dict, ref: Dict) -> Dict[str, object]:
    """What a training run prints beside its checks: each step's loss gap
    and the leaves that set the compared numbers."""
    skip = still_leaves(ref["grad_norms"])
    return {
        "loss gaps per step": loss_gaps(prog["losses"], ref["losses"]),
        "worst gradient leaf": leaf_gap(prog["grad_norms"],
                                        ref["grad_norms"])[1],
        "worst change leaf": leaf_gap(prog["changes"], ref["changes"],
                                      skip)[1],
        "leaves left out of the change": skip,
    }


def judge(readings: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(all within their limits, {name: {value, limit}})."""
    out = {name: {"value": readings[name], "limit": float(limits[name])}
           for name in limits}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out
