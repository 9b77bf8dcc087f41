"""A training cell: the program's train step, built as ``launch/train.py``
builds it, driven by ``train.loop.run_loop`` for a fixed number of seconds.

Set-up builds one object, the compiled step with its state, and drives it
through the mix's first ``checked_steps`` steps with the window's own call
and feed (this also warms every program the window runs). Their losses, the
first gradient as the optimizer holds it (Adagrad's accumulator after one
step is its square) and the parameters' change over those steps are kept.
The same object then runs the window. After the window the program's state
is freed and the configuration's plain reference replays those steps from
the same seed for the comparison. A traced run profiles the window's first
``traced_steps`` steps only: a trace is some 2 MB a step.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks, device, traffic
from chipbench.devtrace import Capture, reduce
from repro.core.heads import Generator
from repro.core.tree import Tree
from repro.launch.mesh import make_host_mesh
from repro.models import lm_head, transformer
from repro.models.config import ModelConfig
from repro.obs import Registry
from repro.optim import OptimizerConfig, init_opt_state
from repro.parallel import batch_shardings, train_state_shardings
from repro.train import LoopConfig, Preemption, TrainState, run_loop
from repro.train.state import snr_reset_pair
from repro.train.step import make_train_step

# The step builder; a planted-fault test swaps it for a broken one.
STEP_BUILDER: Callable = make_train_step


def _leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    vals = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(v.astype(
        jnp.float32)))) for _, v in flat])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, vals)}


def _change_norms(params, start) -> Dict[str, float]:
    return _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b, params, start))


def build(cell, seed: int):
    """Configs, mesh, shardings, the jitted step and a weights function."""
    spec, tcfg = cell.config, cell.config["train"]
    cfg = ModelConfig(**spec["model"])
    hcfg = lm_head.head_config(cfg, tcfg["head"], n_neg=tcfg["n_neg"],
                               reg=tcfg["reg"])
    opt = OptimizerConfig(name=tcfg["optimizer"],
                          learning_rate=tcfg["lr"],
                          clip_norm=tcfg["clip_norm"])
    mesh = make_host_mesh(model_axis=1)
    ref = cell.reference
    wkey = device.seed_key(seed, "weights")
    make_w = jax.jit(lambda k: ref.make_weights(k, spec["model"],
                                                spec["weights"]))

    def weights():
        return make_w(wkey)

    return cfg, hcfg, opt, mesh, weights


def train_state(cfg, opt, weights, mesh):
    params, tree, proj = weights()
    want = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter layout")
    ewma, ref0 = snr_reset_pair()
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=init_opt_state(opt, params),
        head_state=lm_head.LMHeadState(gen=Generator(tree=Tree(**tree)),
                                       proj=proj),
        gen_fit_step=jnp.full((), -1, jnp.int32),
        snr_ewma=ewma, snr_ref=ref0)
    sh = train_state_shardings(cfg, mesh, jax.eval_shape(lambda: state))
    return jax.device_put(state, sh), sh


def run(cell, seed: int, seconds: float, trace: bool, clock,
        devices) -> Dict[str, Any]:
    t_setup = time.perf_counter()
    spec, mix, tcfg = cell.config, cell.mix, cell.config["train"]
    cfg, hcfg, opt, mesh, weights = build(cell, seed)
    state, state_sh = train_state(cfg, opt, weights, mesh)

    n_check = int(mix["checked_steps"])
    host_batches = [traffic.train_batch(mix, cfg.vocab_size, seed, i)
                    for i in range(max(int(mix["pool"]), n_check))]
    batch_sh = batch_shardings(cfg, mesh, jax.eval_shape(
        lambda: {k: jnp.asarray(v) for k, v in host_batches[0].items()}))
    pool = [jax.device_put({k: jnp.asarray(v) for k, v in b.items()},
                           batch_sh) for b in host_batches]
    step_fn = jax.jit(
        STEP_BUILDER(cfg, hcfg, opt, head_update=tcfg["head_update"],
                     head_kernel=tcfg["head_kernel"], mesh=mesh,
                     skip_nonfinite=tcfg["nonfinite_policy"] == "skip"),
        in_shardings=(state_sh, batch_sh, None),
        out_shardings=(state_sh, None), donate_argnums=(0,))
    loop_key = device.seed_key(seed, "loop")
    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])

    def batch_fn(step):
        return pool[step % len(pool)]

    def loop_cfg(total):
        return LoopConfig(total_steps=total, checkpoint_every=1 << 30,
                          nonfinite_policy=tcfg["nonfinite_policy"])

    # -- set-up: the checked steps, through the window's own call and feed
    start = jax.tree.map(jnp.copy, state.params)
    losses = []

    def record(step, m):
        losses.append(float(m["loss"]))

    state, _ = run_loop(state, step_fn, batch_fn, loop_cfg(1),
                        loop_key, on_step=record)
    grad_norms = _leaf_norms(jax.tree.map(jnp.sqrt, state.opt_state.nu))
    state, _ = run_loop(state, step_fn, batch_fn, loop_cfg(n_check),
                        loop_key, on_step=record)
    changes = _change_norms(state.params, start)
    del start
    program = {"losses": losses, "grad_norms": grad_norms,
               "changes": changes}
    setup_s = time.perf_counter() - t_setup

    # -- the window
    registry = Registry(annotate=True) if trace else None
    stop = Preemption()
    done_at, failed = [], [0]

    traced = int(mix["traced_steps"])

    def on_step(step, m):
        done_at.append(time.perf_counter())
        if len(done_at) == traced:
            cap.stop()
        if not np.isfinite(m["loss"]) or m.get("nonfinite", 0.0) > 0:
            failed[0] += 1
        if done_at[-1] - t0 >= seconds:
            stop.trigger()

    clock.mark()
    with Capture(trace) as cap:
        t0 = time.perf_counter()
        state, _ = run_loop(state, step_fn, batch_fn,
                            loop_cfg(1 << 40), loop_key, preemption=stop,
                            on_step=on_step, registry=registry)
    compiles = clock.since()
    window_s = done_at[-1] - t0
    steps = len(done_at)
    info = device.device_info(devices)
    del state, pool
    gc.collect()

    # -- the reference, after the window and with the program's state freed
    ref = cell.reference.train(spec["model"], tcfg, weights,
                               host_batches[:n_check], loop_key)
    readings = checks.train_readings(program, ref)
    ok, compared = checks.judge(readings, spec["limits"])

    out = {"correct": ok, "attempted": steps, "failed": failed[0],
           "device": info, "compiles_in_window": compiles,
           "checks": compared,
           "notes": checks.train_notes(program, ref),
           "e2e": {"train_tokens_per_s": steps * tokens_per_step / window_s,
                   "setup_s": setup_s}}
    if trace:
        red = reduce(cap.file(), cap.window_s)
        cap.cleanup()
        out["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = red.breakdown()
        hist = (registry.snapshot().get("train/phase/data") or {})
        out["layer_inputs"] = {
            "trace": red, "window_s": red.window_s,
            "tokens": min(steps, traced) * tokens_per_step,
            "flops_per_token": cell.reference.train_flops_per_token(
                spec["model"], int(tcfg["n_neg"])),
            "data_span_mean_s": hist.get("mean"),
            "chips": len(devices)}
    return out
