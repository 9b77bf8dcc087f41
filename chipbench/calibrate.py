"""Readings that set a cell's limits: its control and its planted faults.

    python3 chipbench/calibrate.py --workload <name> --seeds 11,12,13

For a training cell, on each seed, the plain reference is run as the cell's
run runs it, then put in the program's place three times and compared with
itself as a run compares the program:

- ``control``: the reference with every trunk matmul operand rounded to
  float8 e4m3, the precision below the configuration's bfloat16;
- ``half_batch``: the loss and its gradient over the first half of each
  batch's rows only, the mean taken over those;
- ``unchanged``: a step that returns its state unchanged (no run needed:
  every change norm is 0, so ``change_gap`` reads 1).

One JSON line per seed and reading. The benchmark's own runs never run
this; ``PERF.md`` records its output beside the limits set from it.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]

import argparse  # noqa: E402
import json  # noqa: E402

ROOT = HERE.parent


def train_readings(cell, seed: int):
    from chipbench import checks, device, traffic
    from chipbench.lm_ref import fp8
    from chipbench.train_cell import build
    spec, mix = cell.config, cell.mix
    tcfg, model = spec["train"], spec["model"]
    _, _, _, _, weights = build(cell, seed)
    n_check = int(mix["checked_steps"])
    batches = [traffic.train_batch(mix, model["vocab_size"], seed, i)
               for i in range(n_check)]
    key = device.seed_key(seed, "loop")
    ref = cell.reference
    exact = ref.train(model, tcfg, weights, batches, key)
    yield "reference", exact
    yield "still_leaves", checks.still_leaves(exact["grad_norms"])
    ctrl = ref.train(model, tcfg, weights, batches, key, cast=fp8)
    yield "control", checks.train_readings(ctrl, exact)
    yield "control_notes", checks.train_notes(ctrl, exact)
    half = ref.train(model, tcfg, weights, batches, key,
                     rows=range(int(mix["batch"]) // 2))
    yield "half_batch", checks.train_readings(half, exact)
    still = dict(exact, changes={k: 0.0 for k in exact["changes"]})
    yield "unchanged", checks.train_readings(still, exact)


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import device, spec
    cell = spec.Cell(root, spec.load_benchmark(root), args.workload)
    device.enable_compile_cache(root)
    if cell.mix["kind"] != "train":
        raise SystemExit(f"no calibration for {cell.mix['kind']} cells")
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name, value in train_readings(cell, seed):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": name, "value": value}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
