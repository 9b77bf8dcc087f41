"""Device trace: capture a window with the JAX profiler and reduce it.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else. On a TPU each chip is a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per operation
(a ``while`` op spans the ops of its body) and whose line ``XLA Modules``
holds one event per program run. Host spans opened with
``jax.profiler.TraceAnnotation`` (the program's spans, when its registry
annotates) sit on the host plane's threads on the same clock.

- busy: union of the op intervals of a chip; ``busy_s`` is its mean over
  the chips, ``window_s`` the traced window's length on the host clock;
- per program: summed device time of each module name;
- per op: time of each op less the time of the ops nested in it (the body
  of a ``while``), by short HLO name;
- idle gaps: holes in the busy union of the first chip; the longest
  ``NAMED_GAPS`` are each named by the innermost host span open at its
  midpoint, the rest summed under one name.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
NAMED_GAPS = 200          # idle gaps named by host span, longest first
_HASH = re.compile(r"\(\d+\)$")


class Capture:
    """Context manager: profile the enclosed window, or its part up to
    ``stop()``, into a temporary directory (removed by ``cleanup``); inert
    when ``enabled`` is false."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.window_s: Optional[float] = None

    def __enter__(self) -> "Capture":
        if self.enabled:
            import jax.profiler
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self.dir)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        """End the traced window (once; later calls do nothing)."""
        if self.enabled and self.window_s is None:
            import jax.profiler
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()

    def file(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace in {self.dir}")
        return files[0]

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


def short_op(name: str) -> str:
    """'%fusion.634 = (...) fusion(...)' -> 'fusion.634'."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def module_name(name: str) -> str:
    """'jit_train_step(2280246702507474696)' -> 'jit_train_step'."""
    return _HASH.sub("", name)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def exclusive_times(events: List[Tuple[float, float, str]]
                    ) -> Dict[str, float]:
    """Per-name time of nested intervals less that of their children."""
    total: Dict[str, float] = defaultdict(float)
    stack: List[List[Any]] = []          # [end, name, child_time]

    def close(item):
        end, name, start, child = item
        total[name] += (end - start) - child
        if stack:
            stack[-1][3] += end - start

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return dict(total)


class Reduced:
    """What one traced window says, in seconds."""

    def __init__(self, busy_s: float, window_s: float,
                 programs: Dict[str, float], ops: Dict[str, float],
                 program_runs: Dict[str, int],
                 gaps: List[Tuple[str, float]]):
        self.busy_s = busy_s
        self.window_s = window_s
        self.programs = programs          # module name -> device s
        self.program_runs = program_runs  # module name -> runs
        self.ops = ops                    # op name -> exclusive device s
        self.gaps = gaps                  # (host span, s), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and runs of the programs whose name matches."""
        rx = re.compile(pattern)
        secs = sum(v for k, v in self.programs.items() if rx.search(k))
        runs = sum(v for k, v in self.program_runs.items() if rx.search(k))
        return secs, runs

    def breakdown(self, n: int = 10) -> Dict[str, List[List[Any]]]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            gaps[name] += s
        top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in top_gaps]}


def _host_spans(planes) -> List[List[Tuple[float, float, str]]]:
    """Host intervals in order of preference: annotations (spans), python
    frames ('$file:line name'), then the runtime's own host events."""
    tiers: List[List[Tuple[float, float, str]]] = [[], [], []]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in line.events]
            # The thread that runs Python carries its frames ('$...') and,
            # beside them, the TraceAnnotations opened on it.
            python = any(name.startswith("$") for _, _, name in events)
            for ev in events:
                tiers[2 if not python else 1 if ev[2].startswith("$")
                      else 0].append(ev)
    return tiers


def _span_at(tiers, t: float) -> str:
    for spans in tiers:
        best = None
        for s, e, name in spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        if best:
            return best[2]
    return "no host span"


def reduce(path: str, window_s: float, min_gap_s: float = 1e-5) -> Reduced:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    planes = list(data.planes)
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    if not devices:
        raise RuntimeError("the trace holds no TPU plane")
    busy_total = 0.0
    programs: Dict[str, float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    first_busy: List[Tuple[float, float]] = []
    for i, plane in enumerate(devices):
        lines = {line.name: list(line.events) for line in plane.lines}
        op_events = [(e.start_ns, e.start_ns + e.duration_ns,
                      short_op(e.name)) for e in lines.get("XLA Ops", [])]
        busy = union([(s, e) for s, e, _ in op_events])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for name, t in exclusive_times(op_events).items():
            ops[name] += t * 1e-9 / len(devices)
        for e in lines.get("XLA Modules", []):
            programs[module_name(e.name)] += e.duration_ns * 1e-9 / len(
                devices)
            if i == 0:
                runs[module_name(e.name)] += 1
        if i == 0:
            first_busy = busy
    holes = sorted(((s1 - e0) * 1e-9, 0.5 * (s1 + e0))
                   for (_, e0), (s1, _) in zip(first_busy, first_busy[1:])
                   if (s1 - e0) * 1e-9 >= min_gap_s)[::-1]
    spans = _host_spans(planes)
    gaps = [(_span_at(spans, mid), g) for g, mid in holes[:NAMED_GAPS]]
    if holes[NAMED_GAPS:]:
        gaps.append((f"gaps after the {NAMED_GAPS} longest",
                     sum(g for g, _ in holes[NAMED_GAPS:])))
    return Reduced(busy_total / len(devices), window_s, dict(programs),
                   dict(ops), dict(runs), gaps)
