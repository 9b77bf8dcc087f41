"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window. The
last line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with a trace ``breakdown``, and last
``checks``: each compared number with its limit); the last lines of
standard error repeat the checks. A run on a machine without a TPU, or with
fewer chips than the cell asks for, exits non-zero and prints no result.
"""
from __future__ import annotations

import sys
from pathlib import Path

# Run as a script, this file's directory heads sys.path; the package is
# imported from the checkout's root instead.
HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402

ROOT = HERE.parent

EXIT_SPEC, EXIT_NO_CHIP, EXIT_COMPILED = 2, 3, 4


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell, out, trace: bool, peaks) -> dict:
    if trace:
        ctx = dict(out["layer_inputs"], peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if trace and "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None, root: Path = ROOT, require_chip: bool = True,
         peaks=None) -> int:
    args = parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import device, spec
    try:
        cell = spec.Cell(root, spec.load_benchmark(root), args.workload)
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_SPEC
    device.enable_compile_cache(root)
    try:
        import jax
        devices = (device.check_chip(cell.chips) if require_chip
                   else jax.devices()[:cell.chips])
        peaks = peaks or device.peaks(devices[0].device_kind)
    except device.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(f"chipbench: {cell.name} seed {args.seed} on "
          f"{devices[0].platform} {devices[0].device_kind!r} x"
          f"{len(devices)}", file=sys.stderr, flush=True)
    clock = device.CompileClock()
    runner = importlib.import_module(f"chipbench.{cell.mix['kind']}_cell")
    t0 = time.perf_counter()
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     clock, devices)
    print(f"chipbench: compiles in the window: "
          f"{out['compiles_in_window']}", file=sys.stderr, flush=True)
    if out["compiles_in_window"]:
        print("chipbench: set-up left a program to compile inside the "
              "window", file=sys.stderr)
        return EXIT_COMPILED
    line = result_line(cell, out, bool(args.trace), peaks)
    print(f"chipbench: run took {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)
    for name, v in out.get("notes", {}).items():
        print(f"chipbench: {name}: {v}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
