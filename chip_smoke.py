"""Bring-up smoke test: the system's main path on a TPU, at full width.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: the sharded head only

Everything runs in this one process, which holds the chip(s); it starts no
other process. Phases (one chip):

  device   stop unless JAX's first device is a TPU;
  kernels  each Pallas kernel, compiled by Mosaic at the shapes the
           smoke's models give it (``repro.launch.smoke_shapes``), against
           its jnp reference (``repro.kernels.ref``); the sampled loss
           with accidental hits (a token's positive drawn as negative);
  train    ``launch/train`` on mamba2-370m, adversarial_ns head, batch 8 x
           seq 1024: two steps on the initial tree, the generator fit at
           step 2, three steps on the fitted tree (the XLA head). From
           the state before each of those steps (and after the last),
           one step of the XLA head and one of the fused Pallas head
           (``--head-kernel``) on the loop's batch and rng: loss,
           gradient norm and mean scores within ONE_STATE_RTOL. Then the
           same five steps with ``--head-kernel``, installing the first
           run's fitted generator at step 2: step 0 within ONE_STATE_RTOL,
           step 1's loss within RUN_RTOL, later steps reported (two
           correct runs drift apart there; see RUN_RTOL). Every loss
           finite, no failed refresh, no skipped step;
  serve    ``launch/serve`` on hymba-1.5b: 8 requests over 4 slots,
           prompt 256, 32 new tokens, page 128, once dense and once on the
           tree beam (16). Every request ends "ok" with exactly 32 tokens
           in [0, vocab).

With ``--chips 4`` it trains mamba2-370m at the same sizes on all four
chips, once with the head's vocab sharded over them (``--model-axis 4``)
and once data-parallel with a replicated head (``--model-axis 1``), for
three steps (losses gated at each, the gradient norm at step 0), and
for one step with uniform negatives (no head row may end apart by
HEAD_ROW_LR_SHARE of the learning rate). No generator fit there, so the
adversarial runs keep the initial tree.

Each phase prints one JSON line (compile seconds from JAX's compile
events, wall seconds, the device's peak bytes, its readings), also when
a check fails. Any failed
check exits non-zero before the last line, which is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Agreement of two readings, as |a - b| / max(1, |b|).
#
# ONE_STATE_RTOL: the XLA and the kernel head path evaluated on one state,
# batch and rng (rounding only: 9e-6 in loss and 1.5e-4 in gradient norm
# at step 0 on the chip). Gates every step of the training run, the
# fitted-tree steps included, and step 0 of the kernel run.
ONE_STATE_RTOL = 1e-3
# Two whole runs drift apart once their first updates differ: Adagrad's
# first steps move a weight by about the learning rate whatever its
# gradient's size, so rounding-level gradient differences become
# lr-sized weight differences (after two steps 23% of mamba2-370m's
# weights differ by more than lr/2 between the XLA and the kernel head,
# whose losses agree to 1e-3; PERF.md). Run against run, losses are gated
# at step 1 (and through step 2 on four chips, which never fit a
# generator) within RUN_RTOL; later steps are reported, and the
# one-state check gates them.
RUN_RTOL = 5e-2
# Four chips, step 0 (one state, sharded against replicated head).
MESH_STEP0_RTOL = 5e-3
# Four chips: the share of the learning rate by which a head row's
# weights may differ between --model-axis 4 and 1 after one step,
# averaged over the row (see compare_head_rows).
HEAD_ROW_LR_SHARE = 0.5
# The head of the one-step runs behind that check. The tree's negatives
# depend on the trunk's output, which the two shardings round
# differently, so under adversarial_ns they draw different negatives for
# some tokens (2360 rows apart on the chip; at full width cut to 2
# layers on fake CPU devices, every row apart was one only one run
# touched). Uniform negatives depend on the rng alone.
ROW_CHECK_HEAD = "uniform_ns"
# Kernel against reference: max|kernel - ref| <= tol * max(1, max|ref|).
KERNEL_TOL = {"sampled_loss": 1e-3, "gather_scores": 1e-3,
              "tree_logprob": 1e-3, "segment_stats": 1e-2,
              "flash_attention": 2e-2}
ONE_STATE_READINGS = ("loss", "grad_norm", "pos_score", "neg_score")
GEN_TOKENS = 32
# Training steps; the generator fit runs before step FIT_STEP.
STEPS, FIT_STEP = 5, 2
MESH_STEPS = 3


def train_args():
    from repro.launch import smoke_shapes as shapes
    return ["--arch", shapes.TRAIN_ARCH, "--full", "--head",
            "adversarial_ns", "--batch", str(shapes.BATCH), "--seq",
            str(shapes.SEQ), "--n-neg", str(shapes.N_NEG),
            "--nonfinite-policy", "raise"]


def fit_args(steps=STEPS):
    return ["--steps", str(steps), "--gen-warmup", str(FIT_STEP)]


def serve_args():
    from repro.launch import smoke_shapes as shapes
    return ["--arch", shapes.SERVE_ARCH, "--full", "--batch", "8",
            "--slots", "4", "--prompt-len", "256", "--gen", str(GEN_TOKENS),
            "--page-len", "128"]


class SmokeFailure(Exception):
    pass


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit is
    timed there too, as its retrieval) and counts cache hits."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def run_phase(name, body, clock, device):
    """Run ``body(info)``, which records its readings in ``info`` as it
    goes, and print the phase's line, also when a check fails."""
    gc.collect()                   # drop the last phase's device buffers
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    info = {}
    try:
        body(info)
    except SmokeFailure as e:
        info["failed"] = str(e)
        raise
    finally:
        stats = device.memory_stats() or {}
        print(json.dumps({"phase": name,
                          "compile_s": clock.seconds - c0,
                          "cache_hits": clock.cache_hits - h0,
                          "wall_s": time.perf_counter() - t0,
                          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                          **info}), flush=True)


def rel_diff(a, b):
    return abs(a - b) / max(1.0, abs(b))


def compare_runs(info, key, a, b, what, bounds):
    """Record in ``info[key]`` the relative differences of two runs'
    per-step losses and gradient norms; ``bounds`` maps (step, reading)
    to the largest allowed, and the first pair beyond its bound fails."""
    check(len(a["step_metrics"]) == len(b["step_metrics"]),
          f"{what}: step counts differ")
    rel = [{k: rel_diff(x[k], y[k]) for k in ("loss", "grad_norm")}
           for x, y in zip(a["step_metrics"], b["step_metrics"])]
    info[key] = {"per_step": rel, "bounds": {
        f"step {s} {k}": v for (s, k), v in bounds.items()}}
    for (step, key), bound in bounds.items():
        check(rel[step][key] <= bound,
              f"{what}: step {step} {key} differs by {rel[step][key]} "
              f"> {bound}")


def train_checks(result, args):
    steps = int(args[args.index("--steps") + 1])
    losses = result["losses"]
    counters = result["counters"]
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(counters["genfit/refresh_failed"] == 0, "generator refresh failed")
    check(counters["train/nonfinite_skipped"] == 0, "a step was skipped")
    if "--gen-warmup" in args:
        check(0 <= result["gen_fit_step"] <= steps - 3,
              f"generator installed at step {result['gen_fit_step']}: "
              f"fewer than 3 of {steps} steps trained on the fitted tree")


def phase_kernels(info):
    """Each kernel at the shapes the smoke's models give it, against its
    jnp reference evaluated at full fp32 precision."""
    import jax
    import jax.numpy as jnp

    from repro.core import tree as tree_lib
    from repro.kernels import ops, ref
    from repro.launch import smoke_shapes as shapes

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, dtype)

    def err(name, out, want):
        out = jax.tree.leaves(out)
        want = jax.tree.leaves(want)
        worst = 0.0
        for o, w in zip(out, want):
            o, w = jnp.asarray(o, jnp.float32), jnp.asarray(w, jnp.float32)
            check(bool(jnp.all(jnp.isfinite(o))), f"{name}: non-finite")
            scale = max(1.0, float(jnp.max(jnp.abs(w))))
            worst = max(worst, float(jnp.max(jnp.abs(o - w))) / scale)
        check(worst <= KERNEL_TOL[name],
              f"{name}: error {worst} > {KERNEL_TOL[name]}")
        return worst

    def exact():
        return jax.default_matmul_precision("highest")

    errors = info["max_rel_error"] = {}
    info["tolerances"] = KERNEL_TOL
    c, k, t, m = shapes.sampled_loss_shape()
    w, b, h = normal((c, k)), normal((c,)), normal((t, k))
    ids = jax.random.randint(next(keys), (t, m), 0, c)
    # Every other token draws its positive as a negative (an accidental
    # hit), as a fitted tree often does; sampled softmax masks those.
    ids = ids.at[::2, 1:].set(ids[::2, :1])
    lp = -jnp.abs(normal((t, m)))
    for kind in ("adversarial_ns", "sampled_softmax"):
        kw = dict(kind=kind, num_labels=c, reg=1e-4)
        with exact():
            want = ref.sampled_head_loss_ref(w, b, h, ids, lp, **kw)
        errors[f"sampled_loss_{kind}"] = err(
            "sampled_loss", ops.sampled_head_loss(w, b, h, ids, lp, **kw),
            want)
    for c, k in shapes.gather_scores_shapes():
        w, b, h = normal((c, k)), normal((c,)), normal((8, k))
        ids = jax.random.randint(next(keys), (8, shapes.BEAM), 0, c)
        with exact():
            want = ref.gather_scores_ref(w, b, h, ids)
        errors[f"gather_scores_k{k}"] = err(
            "gather_scores", ops.gather_scores(w, b, h, ids), want)
    for arch in (shapes.TRAIN_ARCH, shapes.SERVE_ARCH):
        nodes, kt = shapes.tree_shape(arch)
        tree = tree_lib.init_tree(next(keys), nodes + 1, kt, scale=0.5)
        x = normal((8, kt))
        with exact():
            want = ref.tree_logprob_all_ref(tree.w, tree.b, x)
        errors[f"tree_logprob_{nodes + 1}_leaves"] = err(
            "tree_logprob", ops.tree_logprob_all(tree.w, tree.b, x), want)
    for hd, window in shapes.flash_attention_shapes():
        q, kk, v = (normal((1, 4, 1024, hd), jnp.bfloat16) for _ in range(3))
        with exact():
            want = ref.flash_attention_ref(q, kk, v, window=window)
        errors[f"flash_attention_hd{hd}"] = err(
            "flash_attention", ops.flash_attention(q, kk, v, window=window),
            want)
    for n, d, segs in shapes.segment_stats_shapes():
        vals = normal((n, d))
        seg = jax.random.randint(next(keys), (n,), 0, segs)
        with exact():
            want = ref.segment_stats_ref(vals, seg, segs)
        errors[f"segment_stats_d{d}"] = err(
            "segment_stats", ops.segment_stats(vals, seg, segs), want)


class OneStateCheck:
    """One train step of the XLA head and one of the kernel head from the
    same state, on the batch and rng the training loop uses at that step.
    Their loss, gradient norm and mean scores must agree to rounding."""

    def __init__(self, args):
        import jax

        from repro.data import lm_batch_fn
        from repro.launch import train
        from repro.launch.mesh import make_host_mesh
        from repro.train import make_train_step

        ns = train.parse_args(args)
        self.cfg, self.hcfg, opt = train.build(ns)
        self.batches = lm_batch_fn(self.cfg.vocab_size, ns.batch, ns.seq,
                                   seed=train.DATA_SEED)
        mesh = make_host_mesh(model_axis=1)
        self.steps = {}
        for name, kernel in (("xla", False), ("kernel", True)):
            step = make_train_step(self.cfg, self.hcfg, opt,
                                   head_kernel=kernel, mesh=mesh)
            self.steps[name] = jax.jit(
                lambda s, b, r, step=step: step(s, b, r)[1])

    def batch(self, step):
        """The batch and rng run_loop gives ``step``."""
        import jax
        import jax.numpy as jnp

        from repro.launch import train
        return ({k: jnp.asarray(v) for k, v in self.batches(step).items()},
                jax.random.fold_in(jax.random.PRNGKey(train.LOOP_SEED),
                                   step))

    def __call__(self, state, step):
        batch, rng = self.batch(step)
        readings = {}
        for name, fn in self.steps.items():
            metrics = fn(state, batch, rng)
            readings[name] = {k: float(metrics[k])
                              for k in ONE_STATE_READINGS}
        rel = {k: rel_diff(readings["kernel"][k], readings["xla"][k])
               for k in ONE_STATE_READINGS}
        return {"step": step, "readings": readings, "rel_diff": rel}

    def hit_share(self, state, step):
        """Share of tokens for which the state's tree draws the token's
        own label as its negative (an accidental hit)."""
        import jax
        import jax.numpy as jnp

        from repro.core import heads
        from repro.models import lm_head, transformer
        batch, rng = self.batch(step)

        @jax.jit
        def share(params, head_state, tokens, labels, rng):
            h, _, _ = transformer.forward(params, self.cfg, tokens)
            x_gen = lm_head.gen_features(head_state, h)
            neg, _ = heads.sample_negatives(self.hcfg, head_state.gen, x_gen,
                                            rng, labels.shape)
            return jnp.mean(jnp.any(neg == labels[..., None], axis=-1))

        return float(share(state.params, state.head_state, batch["tokens"],
                           batch["labels"].astype(jnp.int32), rng))


def phase_train(info):
    """The XLA run with its generator fit; the two head paths on the state
    before each of its steps; the kernel run on the same generator."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train
    args = train_args()
    xla = train.main(args + fit_args())
    info["step_metrics_xla"] = xla["step_metrics"]
    train_checks(xla, args + fit_args())
    fitted = jax.device_get(xla["state"].head_state)

    def install_fitted(state):
        # A fresh device copy each time: a run donates what it installs.
        return jax.tree.map(jnp.asarray, fitted)

    one_state = OneStateCheck(args)
    info["accidental_hit_share"] = one_state.hit_share(xla["state"], STEPS)
    per_step = []
    info["one_state"] = {"bound": ONE_STATE_RTOL, "per_step": per_step}
    # The state before step s is where an s-step run ends (runs are
    # deterministic); the XLA run's own end adds a step on batch STEPS.
    per_step.append(one_state(xla.pop("state"), STEPS))
    for s in range(1, STEPS):
        gc.collect()
        state = train.main(args + fit_args(s),
                           gen_fit_fn=install_fitted).pop("state")
        if s == FIT_STEP:      # the run stopped just before the install
            state = state._replace(head_state=install_fitted(state))
        reading = one_state(state, s)
        reading["xla_run_loss"] = xla["losses"][s]
        per_step.append(reading)
        del state
    per_step.sort(key=lambda r: r["step"])
    gc.collect()

    kernel = train.main(args + fit_args() + ["--head-kernel"],
                        gen_fit_fn=install_fitted)
    kernel.pop("state")
    info["step_metrics_kernel"] = kernel["step_metrics"]
    info.update(first_step_s=[xla["first_step_s"], kernel["first_step_s"]],
                wall_s_per_run=[xla["wall_s"], kernel["wall_s"]],
                gen_fit_step=[xla["gen_fit_step"], kernel["gen_fit_step"]],
                counters=[xla["counters"], kernel["counters"]])
    train_checks(kernel, args + fit_args())
    for r in per_step:
        check(max(r["rel_diff"].values()) <= ONE_STATE_RTOL,
              f"step {r['step']}: the kernel and the XLA head on one state "
              f"differ by {r['rel_diff']} (bound {ONE_STATE_RTOL})")
    compare_runs(info, "kernel_run_against_xla_run", kernel, xla,
                 "--head-kernel run against the XLA run",
                 {(0, "loss"): ONE_STATE_RTOL,
                  (0, "grad_norm"): ONE_STATE_RTOL,
                  (1, "loss"): RUN_RTOL})


def phase_serve(info):
    from repro import configs as cfg_lib
    from repro.launch import serve
    from repro.launch import smoke_shapes as shapes
    vocab = cfg_lib.get_config(shapes.SERVE_ARCH).vocab_size
    runs = {}
    for name, beam in (("dense", 0), ("beam", shapes.BEAM)):
        runs[name] = serve.main(serve_args() + ["--topk-beam", str(beam)])
        gc.collect()
    dense, beam = runs["dense"]["requests"], runs["beam"]["requests"]
    match = sum(d["tokens"] == b["tokens"] for d, b in zip(dense, beam))
    info.update(requests=len(dense),
                statuses=sorted({r["status"] for r in dense + beam}),
                beam_matches_dense_share=match / len(dense),
                serve_wall_s={k: v["wall_s"] for k, v in runs.items()})
    for name, result in runs.items():
        for r in result["requests"]:
            check(r["status"] == "ok",
                  f"{name} request {r['id']}: {r['status']} {r['error']}")
            check(len(r["tokens"]) == GEN_TOKENS,
                  f"{name} request {r['id']}: {len(r['tokens'])} tokens")
            check(all(0 <= tok < vocab for tok in r["tokens"]),
                  f"{name} request {r['id']}: token outside [0, {vocab})")


def head_rows(result):
    import jax
    import numpy as np
    head = result.pop("state").params["head"]
    return np.asarray(jax.device_get(head["w"]), np.float32)


def compare_head_rows(info, a, b, lr):
    """Rows of the output embedding that two runs moved apart by more
    than HEAD_ROW_LR_SHARE of the learning rate, averaged over the row.
    Adagrad's first step moves a touched weight by about the learning
    rate whatever its gradient's size, so a row one run updates and the
    other does not (or updates with another row's gradient) stands out
    by about 1; rounding flips the sign of the odd near-zero gradient.
    Both runs must touch the same rows: see ROW_CHECK_HEAD."""
    import numpy as np
    per_row = np.mean(np.abs(a - b), axis=1) / lr
    apart = np.flatnonzero(per_row > HEAD_ROW_LR_SHARE)
    info["head_rows_after_step_0"] = {
        "rows_apart": int(apart.size),
        "worst_row_mean_diff_in_lr": float(per_row.max()),
        "bound_in_lr": HEAD_ROW_LR_SHARE}
    check(apart.size == 0,
          f"{apart.size} head rows differ by more than {HEAD_ROW_LR_SHARE}"
          f" x lr on average (rows {apart[:8].tolist()})")


def phase_train_4chips(info):
    """mamba2-370m on four chips, the head vocab-sharded (--model-axis 4)
    against replicated (--model-axis 1): per-step losses, the step-0
    gradient norm (a reduction that sums where it should average changes
    no Adagrad step, but changes that), and the head rows after one
    uniform-negative step (a sparse update scattered to rows a shard does
    not own)."""
    from repro.launch import train
    args = train_args()
    lr = train.build(train.parse_args(args))[2].learning_rate
    row_args = args[:]
    row_args[row_args.index("--head") + 1] = ROW_CHECK_HEAD
    runs, rows = {}, {}
    for axis in ("4", "1"):
        one = train.main(row_args + ["--steps", "1", "--model-axis", axis])
        rows[axis] = head_rows(one)
        gc.collect()
        runs[axis] = train.main(args + ["--steps", str(MESH_STEPS),
                                        "--model-axis", axis])
        runs[axis].pop("state")
        info[f"step_metrics_model_axis_{axis}"] = runs[axis]["step_metrics"]
        train_checks(runs[axis], args + ["--steps", str(MESH_STEPS)])
        gc.collect()
    info["first_step_s"] = [runs[a]["first_step_s"] for a in ("4", "1")]
    bounds = {(0, "loss"): MESH_STEP0_RTOL, (0, "grad_norm"): MESH_STEP0_RTOL}
    bounds.update({(s, "loss"): RUN_RTOL for s in range(1, MESH_STEPS)})
    compare_runs(info, "model_axis_4_against_1", runs["4"], runs["1"],
                 "--model-axis 4 against 1", bounds)
    compare_head_rows(info, rows["4"], rows["1"], lr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, train and serve on one chip; 4: the "
                         "sharded-head training comparison on four")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT / 'src' / 'repro'} not found; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from jax import monitoring

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is "
              f"{device.platform!r})", file=sys.stderr)
        return 1
    print(json.dumps({"phase": "device", "platform": device.platform,
                      "kind": device.device_kind, "count": len(devices)}),
          flush=True)
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(json.dumps({"phase": "compile_cache",
                      "dir": enable_compile_cache()}), flush=True)
    clock = CompileClock(monitoring)
    phases = ([("train_4chips", phase_train_4chips)] if args.chips == 4
              else [("kernels", phase_kernels), ("train", phase_train),
                    ("serve", phase_serve)])
    try:
        for name, body in phases:
            run_phase(name, body, clock, device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
