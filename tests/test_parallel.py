"""Multi-device correctness (8 fake CPU devices via subprocess, since the
device count is locked at first jax init in the main test process).

Checks: sharded train step == single-device train step; sharded candidate
scores == gather; compressed psum ~= fp32 psum; launcher entry points run.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\n" \
                                 f"STDERR:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    run_py("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro import configs as cfg_lib
    from repro.data import lm_batch_fn
    from repro.models import lm_head
    from repro.optim import OptimizerConfig
    from repro.parallel import batch_shardings, train_state_shardings
    from repro.train import init_train_state, make_train_step

    cfg = dataclasses.replace(cfg_lib.reduced_config("stablelm-3b"),
                              num_layers=2, dtype="float32")
    hcfg = lm_head.head_config(cfg, "adversarial_ns", reg=1e-4)
    opt = OptimizerConfig(name="adagrad", learning_rate=0.05)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                             "adversarial_ns")
    make = lm_batch_fn(cfg.vocab_size, 8, 16, seed=1)
    batch = {k: jnp.asarray(v) for k, v in make(0).items()}
    rng = jax.random.PRNGKey(7)
    step = make_train_step(cfg, hcfg, opt)

    # single device
    s1, m1 = jax.jit(step)(state, batch, rng)

    # 4x2 mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    st_sh = train_state_shardings(cfg, mesh, jax.eval_shape(lambda: state))
    b_sh = batch_shardings(cfg, mesh, jax.eval_shape(lambda: batch))
    state_d = jax.device_put(state, st_sh)
    batch_d = jax.device_put(batch, b_sh)
    s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh, None),
                     out_shardings=(st_sh, None))(state_d, batch_d, rng)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    # fp32 cross-device reduction order shifts grads at ~1e-7; Adagrad's
    # rsqrt amplifies that to ~1e-4 relative on the params after one step.
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    print("sharded == single OK")
    """)


def test_sharded_candidate_scores():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.parallel.collectives import sharded_candidate_scores
    from repro.core.heads import candidate_scores, HeadParams

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    c, k, t, n = 64, 16, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    w = jax.random.normal(ks[0], (c, k))
    b = jax.random.normal(ks[1], (c,))
    h = jax.random.normal(ks[2], (t, k))
    ids = jax.random.randint(ks[3], (t, n), 0, c)
    out = sharded_candidate_scores(mesh, w, b, h, ids)
    ref = candidate_scores(HeadParams(w=w, b=b), h, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("sharded scores OK")
    """)


def test_sharded_rows_update():
    """Sparse optimizer row updates against a vocab-sharded table: each
    model shard applies only the ids it owns; sentinel and non-owned ids
    drop; result equals the unsharded gather-update-scatter."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.parallel.collectives import sharded_rows_update

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    v, k, u = 64, 8, 7
    w = jax.random.normal(jax.random.PRNGKey(0), (v, k))
    nu = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (v, k)))
    ids = jnp.array([3, 17, 63, 0, 40, 25, v], jnp.int32)  # v = sentinel
    vals = jax.random.normal(jax.random.PRNGKey(2), (u, k)).at[-1].set(0.)

    def fn(rows, vals_t):
        p, n = rows
        (g,) = vals_t
        n2 = n + g * g
        return (p - 0.1 * g / (jnp.sqrt(n2) + 1e-8), n2)

    w2, nu2 = sharded_rows_update(mesh, fn, ids, (vals,), [w, nu])
    exp_nu = nu.at[ids].add(vals ** 2, mode="drop")
    rows_p, rows_n = fn((w[jnp.clip(ids, 0, v - 1)],
                         nu[jnp.clip(ids, 0, v - 1)]), (vals,))
    exp_w = w.at[ids].set(rows_p, mode="drop")
    np.testing.assert_allclose(np.asarray(nu2), np.asarray(exp_nu),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(exp_w),
                               rtol=1e-6)
    print("sharded rows update OK")
    """)


def test_sparse_train_step_on_mesh():
    """make_train_step(head_update='sparse', mesh=...) under pjit on a
    sharded TrainState: runs, loss finite, head rows move."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro import configs as cfg_lib
    from repro.data import lm_batch_fn
    from repro.models import lm_head
    from repro.optim import OptimizerConfig
    from repro.parallel import batch_shardings, train_state_shardings
    from repro.train import init_train_state, make_train_step

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = cfg_lib.reduced_config("stablelm-3b")
    hcfg = lm_head.head_config(cfg, "adversarial_ns", n_neg=2)
    opt = OptimizerConfig(name="adagrad", learning_rate=0.05,
                          clip_norm=1.0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                             "adversarial_ns")
    state_sh = train_state_shardings(cfg, mesh,
                                     jax.eval_shape(lambda: state))
    state = jax.device_put(state, state_sh)
    make = lm_batch_fn(cfg.vocab_size, 8, 16, seed=0)
    batch = {k: jnp.asarray(v) for k, v in make(0).items()}
    batch_sh = batch_shardings(cfg, mesh, jax.eval_shape(lambda: batch))
    step = jax.jit(make_train_step(cfg, hcfg, opt, head_update="sparse",
                                   mesh=mesh),
                   in_shardings=(state_sh, batch_sh, None),
                   out_shardings=(state_sh, None))
    w0 = np.asarray(jax.device_get(state.params["head"]["w"]))
    for s in range(2):
        state, metrics = step(state, jax.device_put(batch, batch_sh),
                              jax.random.PRNGKey(s))
        assert np.isfinite(float(metrics["loss"]))
    w1 = np.asarray(jax.device_get(state.params["head"]["w"]))
    assert np.abs(w1 - w0).max() > 0
    print("sparse step on mesh OK")
    """)


def test_compressed_grad_allreduce():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.parallel.collectives import compressed_grad_allreduce

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    n_dp = 4
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (n_dp, 32, 8)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (n_dp, 16))}
    ef = jax.tree.map(jnp.zeros_like, g)
    mean, new_ef = compressed_grad_allreduce(mesh, g, ef)
    ref = jax.tree.map(lambda x: jnp.mean(x, 0), g)
    for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(ref)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=0.02 * scale + 1e-6)
    # error feedback: residual equals what quantization dropped
    for gl, el, ml in zip(jax.tree.leaves(g), jax.tree.leaves(new_ef),
                          jax.tree.leaves(mean)):
        assert el.shape == gl.shape
    print("compressed allreduce OK")
    """)


@pytest.mark.slow
def test_launcher_entry_points():
    out = run_py("""
    import sys
    sys.argv = ["train", "--arch", "stablelm-3b", "--steps", "3",
                "--batch", "8", "--seq", "16", "--model-axis", "2"]
    from repro.launch.train import main
    main()
    """)
    assert "final loss" in out
    out = run_py("""
    import sys
    sys.argv = ["serve", "--arch", "stablelm-3b", "--batch", "4",
                "--prompt-len", "8", "--gen", "4", "--model-axis", "2",
                "--lockstep"]
    from repro.launch.serve import main
    main()
    """)
    assert "decode 4 steps" in out
    # Engine path on a mesh, beam candidates scored via the vocab-sharded
    # sharded_candidate_scores collective (model axis = 2).
    out = run_py("""
    import sys
    sys.argv = ["serve", "--arch", "stablelm-3b", "--batch", "3",
                "--prompt-len", "8", "--gen", "4", "--model-axis", "2",
                "--topk-beam", "8", "--shard-scores"]
    from repro.launch.serve import main
    main()
    """)
    assert "engine: 3 requests" in out
    assert "beam=8" in out


def test_ssd_intra_kernel_on_mesh():
    """On a mesh the SSD intra-chunk kernel runs on each device's rows of
    the batch: forward and gradients match the einsums, with no
    all-gather of the activations."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.kernels import ref as ref_lib
    from repro.kernels.ssd_intra import ssd_intra

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    b, nc, q, n, h, p = 4, 2, 128, 16, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    seg = jnp.cumsum(-jax.nn.softplus(
        jax.random.normal(ks[0], (b, nc, q, h))), axis=2)
    cm = jax.random.normal(ks[1], (b, nc, q, n)) / 4
    bm = jax.random.normal(ks[2], (b, nc, q, n)) / 4
    x = jax.random.normal(ks[3], (b, nc, q, h, p))
    dy = jax.random.normal(ks[4], (b, nc, q, h, p))
    args = (cm, bm, seg, x, dy)

    def run(fn, *a):
        y, vjp = jax.vjp(fn, *a[:4])
        return (y,) + vjp(a[4])

    rows = NamedSharding(mesh, P("data"))
    step = jax.jit(lambda *a: run(
        lambda *u: ssd_intra(*u, True, jnp.float32), *a))
    sharded = [jax.device_put(a, rows) for a in args]
    got = step(*sharded)
    text = step.lower(*sharded).compile().as_text()
    assert "all-gather" not in text and "all-reduce" not in text
    want = run(ref_lib.ssd_intra_ref, *args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    print("ssd_intra on mesh OK")
    """, devices=4)
