"""Training launcher: pjit the train step onto a mesh and run the
fault-tolerant loop.

On a TPU host it trains on every local chip (``--model-axis`` of them per
model replica); on the CPU backend it runs reduced configs on a host mesh
(set XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise real
multi-device sharding). Run as a script, it keeps JAX's persistent
compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` or else
``<checkout>/.jax_cache``.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --reduced \
      --steps 20 --head adversarial_ns --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs as cfg_lib
from repro.data import lm_batch_fn
from repro.models import lm_head
from repro.optim import OptimizerConfig
from repro.parallel import batch_shardings, train_state_shardings
from repro.train import (LoopConfig, init_train_state, make_train_step,
                         run_loop)
from repro.train.generator_fit import make_gen_fit_fn


# Default learning rates. Adagrad's first step moves every weight by the
# full rate; at the published widths 0.05 drives mamba2-370m's loss from
# 1.64 to 12.96 within four steps on a TPU v5e, where 0.005 keeps it
# between 1.1 and 1.7 (PERF.md).
REDUCED_LR = 0.05
FULL_LR = 0.005
# Seeds of the synthetic data stream and of run_loop's step-indexed rng.
DATA_SEED, LOOP_SEED = 0, 1


def build(args):
    cfg = (cfg_lib.reduced_config(args.arch) if args.reduced
           else cfg_lib.get_config(args.arch))
    hcfg = lm_head.head_config(cfg, args.head, n_neg=args.n_neg,
                               reg=args.reg)
    lr = args.lr if args.lr is not None else (
        REDUCED_LR if args.reduced else FULL_LR)
    opt = OptimizerConfig(name=args.optimizer, learning_rate=lr,
                          clip_norm=1.0,
                          head_name=args.head_optimizer,
                          state_dtype=args.state_dtype)
    return cfg, hcfg, opt


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b",
                    choices=list(cfg_lib.ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--head", default="adversarial_ns")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None,
                    help=f"learning rate (default {REDUCED_LR} on the "
                         f"reduced configs, {FULL_LR} with --full)")
    ap.add_argument("--reg", type=float, default=1e-4)
    ap.add_argument("--n-neg", type=int, default=1)
    ap.add_argument("--head-update", default="auto",
                    choices=("auto", "dense", "sparse"),
                    help="head-gradient path (DESIGN.md §8): sparse = "
                         "O(B·K·n_neg) touched-row updates (default for "
                         "sampled heads), dense = O(C·K) autodiff "
                         "(default/required for softmax)")
    ap.add_argument("--head-kernel", action="store_true",
                    help="route the sparse head loss through the fused "
                         "Pallas sampled_head_loss kernel")
    ap.add_argument("--optimizer", default="adagrad")
    ap.add_argument("--head-optimizer", default=None,
                    choices=(None, "sgd", "adagrad", "adamw", "sm3"),
                    help="override the optimizer for head params only "
                         "(DESIGN.md §11): 'sm3' keeps one row + one col "
                         "second-moment cover instead of the full (C, K) "
                         "slab — the 100M-label memory play")
    ap.add_argument("--state-dtype", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="storage dtype for the head optimizer "
                         "accumulators (compute stays fp32; int8 adds a "
                         "per-row scale)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--gen-warmup", type=int, default=0)
    ap.add_argument("--gen-refresh", type=int, default=0,
                    help="refit the generator every N steps (0 = once)")
    ap.add_argument("--gen-async", action="store_true",
                    help="fit in a background thread; swap at the "
                         "recorded step (submit + --gen-swap-delay)")
    ap.add_argument("--gen-swap-delay", type=int, default=4)
    ap.add_argument("--gen-method", default="levelwise",
                    choices=("levelwise", "sequential", "sharded"))
    ap.add_argument("--sampler", default="config",
                    choices=("config", "uniform", "unigram", "lsh", "rff"),
                    help="negative-sampling proposal (core.samplers): "
                         "'config' derives it from --head + the generator "
                         "state (the tree for adversarial_ns); the others "
                         "are fitted once from a model snapshot at startup "
                         "and override the head's default proposal")
    ap.add_argument("--gen-refresh-mode", default="period",
                    choices=("period", "snr"),
                    help="'period' refits every --gen-refresh steps; "
                         "'snr' refits when the online gradient-SNR proxy "
                         "(DESIGN.md §9) degrades past --snr-threshold x "
                         "its post-install reference")
    ap.add_argument("--snr-threshold", type=float, default=0.85)
    ap.add_argument("--snr-patience", type=int, default=8)
    ap.add_argument("--metrics-jsonl", default=None,
                    help="write the repro.obs JSONL event log (per-step "
                         "samples + genfit lifecycle, DESIGN.md §10) to "
                         "this path")
    ap.add_argument("--metrics-interval", type=int, default=1,
                    help="emit a 'step' JSONL event every N steps")
    ap.add_argument("--nonfinite-policy", default="skip",
                    choices=("skip", "raise"),
                    help="on a non-finite loss: 'skip' drops the update "
                         "in-graph and counts it (train/nonfinite_skipped"
                         "), escalating to rollback-restore after "
                         "--max-consecutive-nonfinite skips; 'raise' "
                         "fails fast (DESIGN.md §13)")
    ap.add_argument("--max-consecutive-nonfinite", type=int, default=3)
    ap.add_argument("--max-rollbacks", type=int, default=2,
                    help="rollback-restores allowed per run before the "
                         "loop gives up with FloatingPointError")
    ap.add_argument("--gen-fit-retries", type=int, default=2,
                    help="transient generator-fit failures absorbed by "
                         "retry (exponential backoff) before the loop "
                         "keeps the stale generator")
    ap.add_argument("--gen-fit-timeout", type=float, default=None,
                    help="watchdog seconds for a background fit; a hung "
                         "fit is abandoned and the stale generator kept "
                         "(default: wait forever)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of a few "
                         "steady-state steps into this directory (host "
                         "spans annotate the timeline)")
    return ap.parse_args(argv)


def main(argv=None, gen_fit_fn=None) -> dict:
    """Parse ``argv`` (default: ``sys.argv``), train, and return the
    run's result: per-step losses and step metrics, the first step's
    seconds (which hold the compile), the run's wall seconds, the
    installed generator's fit step, the resilience counters, and the
    final ``TrainState``.

    ``gen_fit_fn(state) -> LMHeadState`` replaces the launcher's
    generator fit (``--gen-warmup`` still sets when it runs): a caller
    that compares two runs installs one fitted generator in both.
    """
    args = parse_args(argv)
    t_start = time.perf_counter()

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(model_axis=args.model_axis)
    print(f"mesh: {dict(mesh.shape)} on {jax.device_count()} devices")

    cfg, hcfg, opt = build(args)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, args.head)
    state_sh = train_state_shardings(cfg, mesh, jax.eval_shape(lambda:
                                                               state))
    state = jax.device_put(state, state_sh)

    make = lm_batch_fn(cfg.vocab_size, args.batch, args.seq,
                       seed=DATA_SEED)
    batch_abs = jax.eval_shape(lambda: {k: jnp.asarray(v)
                                        for k, v in make(0).items()})
    batch_sh = batch_shardings(cfg, mesh, batch_abs)
    # Sparse head updates run shard-local against the vocab-sharded head
    # (each model shard applies only the rows it owns — no all-gather).
    # Donating the TrainState lets XLA scatter the touched rows in place
    # instead of copying the (C, K) param/accumulator buffers to build the
    # functional update — without it the O(U·K) sparse step pays an
    # O(C·K) memcpy. Safe even with --gen-async: run_loop snapshots the
    # leaves the background fit reads (_fit_snapshot, snapshot-then-
    # donate) before submitting, so training can keep invalidating its
    # own buffers mid-fit.
    sampler = None
    if args.sampler != "config":
        # Fit the override proposal once from a startup snapshot, in the
        # head state's own feature space (x_gen = h @ proj) so sampling
        # and the Eq. 5 debias see identical features.
        from repro.train.generator_fit import fit_lm_sampler
        sampler, _ = fit_lm_sampler(
            args.sampler, state.params, cfg,
            ({k: jnp.asarray(v) for k, v in make(10_000 + i).items()}
             for i in range(4)),
            proj=state.head_state.proj)
        print(f"sampler: {type(sampler).__name__} (--sampler "
              f"{args.sampler})")

    donate = (0,)
    # skip_nonfinite puts the accept/reject select inside the jitted step
    # (donation invalidates the old buffers, so the guard cannot live in
    # Python) — the loop's degradation ladder builds on it.
    train_step = jax.jit(make_train_step(
        cfg, hcfg, opt, head_update=args.head_update,
        head_kernel=args.head_kernel, mesh=mesh, sampler=sampler,
        skip_nonfinite=(args.nonfinite_policy == "skip")),
                         in_shardings=(state_sh, batch_sh, None),
                         out_shardings=(state_sh, None),
                         donate_argnums=donate)

    def batch_fn(step):
        return jax.device_put({k: jnp.asarray(v)
                               for k, v in make(step).items()}, batch_sh)

    gen_cb = None
    if args.gen_warmup and args.head in ("adversarial_ns", "nce",
                                         "sampled_softmax", "freq_ns"):
        gen_cb = gen_fit_fn or make_gen_fit_fn(
            cfg, lambda s: {k: jnp.asarray(v) for k, v in make(s).items()},
            kind=args.head, max_tokens=8192, method=args.gen_method)

    loop = LoopConfig(total_steps=args.steps,
                      checkpoint_every=max(args.steps // 2, 1),
                      checkpoint_dir=args.ckpt,
                      gen_warmup_steps=args.gen_warmup,
                      gen_refresh_steps=args.gen_refresh,
                      gen_async=args.gen_async,
                      gen_swap_delay=args.gen_swap_delay,
                      gen_refresh_mode=args.gen_refresh_mode,
                      snr_threshold=args.snr_threshold,
                      snr_patience=args.snr_patience,
                      metrics_jsonl=args.metrics_jsonl,
                      metrics_interval=args.metrics_interval,
                      profile_dir=args.profile_dir,
                      nonfinite_policy=args.nonfinite_policy,
                      max_consecutive_nonfinite=(
                          args.max_consecutive_nonfinite),
                      max_rollbacks=args.max_rollbacks,
                      gen_fit_retries=args.gen_fit_retries,
                      gen_fit_timeout_s=args.gen_fit_timeout)
    from repro.obs import Registry, console_summary
    registry = Registry()
    step_metrics = []

    def on_step(step, m):
        step_metrics.append({k: m[k] for k in (
            "loss", "grad_norm", "pos_score", "neg_score") if k in m})
        print(f"step {step:4d} loss={m['loss']:.4f} "
              f"{m['step_time']*1e3:.0f}ms", flush=True)

    state, hist = run_loop(
        state, train_step, batch_fn, loop, jax.random.PRNGKey(LOOP_SEED),
        gen_fit_fn=gen_cb, registry=registry, on_step=on_step)
    print(f"final loss {hist['loss'][-1]:.4f}; "
          f"stragglers={hist['stragglers']}")
    result = {
        "losses": hist["loss"],
        "step_metrics": step_metrics,
        "first_step_s": hist.get("compile_time_s"),
        "wall_s": time.perf_counter() - t_start,
        "gen_fit_step": int(jax.device_get(state.gen_fit_step)),
        "counters": {name: registry.counter(name).value for name in (
            "genfit/submits", "genfit/refresh_failed",
            "train/nonfinite_skipped", "train/rollbacks")},
        "state": state,
    }
    if args.metrics_jsonl or args.profile_dir:
        print(console_summary(registry, title="train metrics"))
        if args.metrics_jsonl:
            print(f"metrics JSONL: {args.metrics_jsonl}")
        if args.profile_dir:
            print(f"profile: {args.profile_dir} (load in TensorBoard / "
                  f"xprof; host spans annotate the trace)")
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
