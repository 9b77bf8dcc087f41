"""Split a training cell's step by layer on the chip, and time what the
program's spans cost.

    python3 chipbench/split_step.py --workload <name> --seed <n> \
        [--seconds 20] [--pairs 2] [--keep <dir>]

Builds the cell's train step as ``train_cell`` builds it and warms it with
one step, then:

- runs ``--pairs`` pairs of untraced windows of ``--seconds`` each, in the
  order off, on, on, off, ...: ``off`` with no registry, as an untraced
  run of the cell runs; ``on`` with an enabled registry that annotates its
  spans, as a traced run does, but with no profiler; each prints its
  tokens per second;
- profiles a window of ``--seconds`` whose first ``traced_steps`` steps
  (the mix's) are traced, with an annotating registry, as a traced run of
  the cell does; and splits ``jit_train_step``'s device time by the
  step's named scopes against the compiled module's text
  (``chipbench/scopes.py``).

Notes go to standard error: each window's tokens/s, the unscoped share of
the step's device time, the share in fusions that span scopes (with the
largest), and the per-scope table. The last line of standard output is one
JSON object: ``metrics`` (the cell's per-layer metrics and the scope
metrics, each read by its reader in ``chipbench/metrics/``), ``scopes``
(seconds per run), ``windows`` (mode, steps, tokens/s) and the trace's
``breakdown``. ``--keep`` copies the trace and the module's text into a
directory. A machine without a TPU exits 3 and prints no result.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import time  # noqa: E402

ROOT = HERE.parent

# Per-layer metrics that read the step's scopes and the loop's host span.
SCOPE_METRICS = ("trunk_ms.train", "head_ms.train", "sampler_ms.train",
                 "optimizer_ms.train", "host_ms.train")


def build_step(cell, seed: int):
    """The cell's jitted train step, its state and its pool of batches on
    the device, and its loop settings, as ``train_cell.run`` makes them."""
    import jax
    import jax.numpy as jnp
    from chipbench import device, traffic, train_cell
    from repro.parallel import batch_shardings
    from repro.train import LoopConfig
    mix, tcfg = cell.mix, cell.config["train"]
    cfg, hcfg, opt, mesh, weights = train_cell.build(cell, seed)
    state, state_sh = train_cell.train_state(cfg, opt, weights, mesh)
    host = [traffic.train_batch(mix, cfg.vocab_size, seed, i)
            for i in range(int(mix["pool"]))]
    batch_sh = batch_shardings(cfg, mesh, jax.eval_shape(
        lambda: {k: jnp.asarray(v) for k, v in host[0].items()}))
    pool = [jax.device_put({k: jnp.asarray(v) for k, v in b.items()},
                           batch_sh) for b in host]
    step_fn = jax.jit(
        train_cell.STEP_BUILDER(
            cfg, hcfg, opt, head_update=tcfg["head_update"],
            head_kernel=tcfg["head_kernel"], mesh=mesh,
            skip_nonfinite=tcfg["nonfinite_policy"] == "skip"),
        in_shardings=(state_sh, batch_sh, None),
        out_shardings=(state_sh, None), donate_argnums=(0,))

    def loop_cfg(total):
        return LoopConfig(total_steps=total, checkpoint_every=1 << 30,
                          nonfinite_policy=tcfg["nonfinite_policy"])

    return step_fn, state, pool, loop_cfg, device.seed_key(seed, "loop")


def compiled_text(step_fn, state, batch, key) -> str:
    """The optimized module the step runs (a compile-cache load once the
    step has run)."""
    return step_fn.lower(state, batch, key).compile().as_text()


def window(state, step_fn, pool, loop_cfg, key, seconds, registry=None,
           capture=None, traced=0):
    """Run the loop for ``seconds``; stop ``capture`` after ``traced``
    steps. Returns the state, the steps run and the window's seconds."""
    from repro.train import Preemption, run_loop
    stop, done_at = Preemption(), []

    def on_step(step, m):
        done_at.append(time.perf_counter())
        if capture is not None and len(done_at) == traced:
            capture.stop()
        if done_at[-1] - t0 >= seconds:
            stop.trigger()

    t0 = time.perf_counter()
    state, _ = run_loop(state, step_fn, lambda s: pool[s % len(pool)],
                        loop_cfg(1 << 40), key, preemption=stop,
                        on_step=on_step, registry=registry)
    return state, len(done_at), done_at[-1] - t0


def notes(split, mixed_top):
    from chipbench.scopes import TOP
    total = split["total"] or 1.0
    scoped = sum(split[k] for k in TOP)
    return {"unscoped share of the step's device time":
            f"{100 * split['unscoped'] / total:.3f}%",
            "share in fusions spanning scopes":
            f"{100 * split['mixed'] / total:.3f}% (largest, ms per run: "
            f"{mixed_top})",
            "scoped share of the step's device time per run":
            f"{100 * scoped / split['module_s']:.3f}% of "
            f"{1e3 * split['module_s']:.3f} ms",
            "ms per step run by scope":
            {k: round(1e3 * v, 3) for k, v in split.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--keep", default="",
                    help="directory to keep the trace and the compiled "
                         "module's text in")
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import device, scopes, spec
    from chipbench.devtrace import Capture, exclusive_times, reduce
    from repro.obs import Registry
    cell = spec.Cell(ROOT, spec.load_benchmark(ROOT), args.workload)
    device.enable_compile_cache(ROOT)
    try:
        devices = device.check_chip(cell.chips)
    except device.NoChip as e:
        print(f"split_step: {e}", file=sys.stderr)
        return 3
    mix = cell.mix
    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
    traced = int(mix["traced_steps"])
    step_fn, state, pool, loop_cfg, key = build_step(cell, args.seed)
    state, _, _ = window(state, step_fn, pool, loop_cfg, key, 0.0)

    runs = []
    for i in range(2 * args.pairs):
        on = i % 4 in (1, 2)
        state, steps, secs = window(
            state, step_fn, pool, loop_cfg, key, args.seconds,
            registry=Registry(annotate=True) if on else None)
        runs.append({"mode": "on" if on else "off", "steps": steps,
                     "tokens_per_s": steps * tokens_per_step / secs})
        print(f"split_step: window {runs[-1]}", file=sys.stderr, flush=True)

    registry = Registry(annotate=True)
    with Capture(True) as cap:
        state, steps, _ = window(state, step_fn, pool, loop_cfg, key,
                                 args.seconds, registry=registry,
                                 capture=cap, traced=traced)
    text = compiled_text(step_fn, state, pool[0], key)
    module = scopes.module_name_of(text)
    names, mixed = scopes.op_scopes(text), scopes.mixed_fusions(text)
    red = reduce(cap.file(), cap.window_s)
    split = scopes.read(cap.file(), module, names, mixed)
    host = scopes.host_spans(cap.file(), "train/phase/host")
    chip = scopes.module_ops(cap.file(), module)[0]
    excl = exclusive_times(chip[0])
    mixed_top = sorted(((round(1e3 * excl[o] / chip[1], 3), o)
                        for o in excl if o in mixed), reverse=True)[:5]
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        shutil.copy(cap.file(), os.path.join(args.keep, "step.xplane.pb"))
        with open(os.path.join(args.keep, "step.hlo.txt"), "w") as f:
            f.write(text)
    cap.cleanup()

    snap = registry.snapshot()
    ctx = {"trace": red, "window_s": red.window_s,
           "tokens": min(steps, traced) * tokens_per_step,
           "flops_per_token": cell.reference.train_flops_per_token(
               cell.config["model"], int(cell.config["train"]["n_neg"])),
           "data_span_mean_s": (snap.get("train/phase/data") or {}).get(
               "mean"),
           "chips": len(devices),
           "peaks": device.peaks(devices[0].device_kind),
           "scopes": split,
           "host_span_mean_s": sum(host) / len(host) if host else None}
    metrics = {}
    for name in [m["name"] for m in cell.per_layer] + list(SCOPE_METRICS):
        v = cell.reader(name).read(ctx)
        if v is not None:
            metrics[name] = float(v)
    idle_ms = 1e3 * (red.window_s - red.busy_s) / min(steps, traced)
    for k, v in notes(split, mixed_top).items():
        print(f"split_step: {k}: {v}", file=sys.stderr)
    print(f"split_step: idle ms per traced step: {idle_ms:.3f}",
          file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({"metrics": metrics, "scopes": split,
                      "idle_ms_per_step": idle_ms, "windows": runs,
                      "device": device.device_info(devices),
                      "breakdown": red.breakdown()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
