"""Memory-cheap head state (DESIGN.md §11): SM3 factored covers, bf16 /
int8 accumulator storage, exact lazy AdamW, and the sparse embedding
gather.

Pins the PR's guarantees:
  * sm3 sparse touched-rows == sm3 dense, everywhere (monotone-max covers
    make the factored update exactly sparse-safe),
  * bf16-stored accumulators track the fp32 trajectory within tolerance;
    int8 + per-row scale stays finite and converges,
  * lazy AdamW with per-row catch-up == dense AdamW under *random* touch
    patterns (hypothesis property), not just the all-touched case,
  * the input-embedding SparseRows gather == dense embedding grads,
  * global_norm is fp32-correct over Sm3Cover / QuantizedRows leaves,
  * checkpoints round-trip the new state bit-stably (bf16 view save) and
    a mid-run resume replays bit-exactly,
  * the sharded (mesh) sm3/adamw row update == the unsharded one,
  * _fit_snapshot decouples the background generator fit from donation,
  * head_state_bytes shows the >= 4x adamw/fp32 -> sm3/bf16 reduction.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import heads as heads_lib
from repro.core import tree as tree_lib
from repro.core.heads import Generator, HeadConfig, HeadParams
from repro.optim import (OptimizerConfig, QuantizedRows, Sm3Cover,
                         apply_updates, dequantize_rows, global_norm,
                         head_state_bytes, init_opt_state, load_rows,
                         quantize_rows, store_rows)
from repro.optim import sparse as sparse_lib
from repro.optim.sparse import SparseRows, accumulate_embed_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C, K, KG = 16, 12, 4


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


def _gen(c=C, seed=0):
    return Generator(tree=tree_lib.init_tree(jax.random.PRNGKey(seed), c,
                                             KG, scale=0.5))


def _problem(batch=48, seed=0, c=C):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(ks[0], (batch, K))
    xg = jax.random.normal(ks[1], (batch, KG))
    y = jax.random.randint(ks[2], (batch,), 0, c)
    params = heads_lib.init_head_params(ks[3], c, K, scale=0.3)
    return params, h, xg, y


def _dense_grads(cfg, params, gen, h, xg, y, rng):
    return jax.grad(lambda pp: heads_lib.head_loss(
        cfg, pp, gen, h, xg, y, rng)[0])(params)


def _random_sparse(rng, c, k, touch_all=False, sentinel=True):
    """A SparseRows grad over a random unique subset of rows (optionally
    all rows), with a zero-valued sentinel slot (id == c, the dedupe
    fill) riding along as the head path always produces one."""
    if touch_all:
        ids = np.arange(c)
    else:
        ids = rng.choice(c, size=int(rng.integers(1, c)), replace=False)
    u = len(ids)
    dw = rng.standard_normal((u, k)).astype(np.float32)
    db = rng.standard_normal((u,)).astype(np.float32)
    if sentinel:
        ids = np.append(ids, c)
        dw = np.concatenate([dw, np.zeros((1, k), np.float32)])
        db = np.append(db, np.float32(0.0))
    sp = SparseRows(ids=jnp.asarray(ids, jnp.int32), dw=jnp.asarray(dw),
                    db=jnp.asarray(db))
    dwd = np.zeros((c, k), np.float32)
    dbd = np.zeros((c,), np.float32)
    dwd[ids[:u]] = dw[:u]
    dbd[ids[:u]] = db[:u]
    gd = HeadParams(w=jnp.asarray(dwd), b=jnp.asarray(dbd))
    return sp, gd


class TestSm3Parity:
    """SM3's monotone-max covers make the sparse path exact: a zero-grad
    row has nu' = min(row, col) <= row everywhere, so neither its param
    nor either cover can move — dense == sparse on ALL rows."""

    @pytest.mark.parametrize("state_dtype", ["fp32", "bf16"])
    def test_sparse_equals_dense_n_steps(self, state_dtype):
        cfg = HeadConfig(num_labels=C, kind="adversarial_ns", n_neg=2,
                         reg=1e-3)
        gen = _gen()
        params, h, xg, y = _problem()
        ocfg = OptimizerConfig(name="sm3", learning_rate=0.1,
                               clip_norm=1.0, state_dtype=state_dtype)
        pd = ps = params
        sd = ss = init_opt_state(ocfg, params)
        for s in range(5):
            r = jax.random.fold_in(jax.random.PRNGKey(11), s)
            gd = _dense_grads(cfg, pd, gen, h, xg, y, r)
            pd, sd, _ = apply_updates(ocfg, pd, gd, sd)
            _, _, srows, _ = heads_lib.sparse_head_loss(cfg, ps, gen, h,
                                                        xg, y, r)
            ps, ss, _ = apply_updates(ocfg, ps, srows, ss)
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ps.b), np.asarray(pd.b),
                                   rtol=1e-5, atol=1e-6)
        # the factored state matches too (row cover in storage dtype)
        assert isinstance(ss.nu.w, Sm3Cover) and isinstance(sd.nu.w,
                                                            Sm3Cover)
        np.testing.assert_allclose(
            np.asarray(load_rows(ss.nu.w.row)),
            np.asarray(load_rows(sd.nu.w.row)), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ss.nu.w.col),
                                   np.asarray(sd.nu.w.col),
                                   rtol=1e-5, atol=1e-6)

    def test_untouched_rows_are_bitwise_frozen(self):
        rng = np.random.default_rng(3)
        ocfg = OptimizerConfig(name="sm3", learning_rate=0.1)
        params, _, _, _ = _problem(c=64)
        sp, _ = _random_sparse(rng, 64, K)
        p2, _, _ = apply_updates(ocfg, params, sp,
                                 init_opt_state(ocfg, params))
        touched = np.asarray(sp.ids)
        untouched = np.setdiff1d(np.arange(64), touched[touched < 64])
        np.testing.assert_array_equal(np.asarray(p2.w)[untouched],
                                      np.asarray(params.w)[untouched])


class TestStateDtype:
    def _trajectory(self, state_dtype, steps=12):
        cfg = HeadConfig(num_labels=C, kind="adversarial_ns", n_neg=2,
                         reg=1e-3)
        gen = _gen()
        params, h, xg, y = _problem()
        ocfg = OptimizerConfig(name="sm3", learning_rate=0.1,
                               state_dtype=state_dtype)
        opt = init_opt_state(ocfg, params)
        losses = []
        for s in range(steps):
            r = jax.random.fold_in(jax.random.PRNGKey(21), s)
            loss, _, srows, _ = heads_lib.sparse_head_loss(cfg, params,
                                                           gen, h, xg, y,
                                                           r)
            losses.append(float(loss))
            params, opt, _ = apply_updates(ocfg, params, srows, opt)
        return params, losses

    def test_bf16_storage_tracks_fp32(self):
        p32, l32 = self._trajectory("fp32")
        p16, l16 = self._trajectory("bf16")
        assert l32[-1] < l32[0] and l16[-1] < l16[0]
        assert abs(l16[-1] - l32[-1]) < 0.05, (l16[-1], l32[-1])
        np.testing.assert_allclose(np.asarray(p16.w), np.asarray(p32.w),
                                   rtol=5e-2, atol=5e-3)

    def test_int8_storage_runs_and_converges(self):
        # adamw exercises QuantizedRows mu (+ the 1-D bf16 fallback for
        # b and the int32 last rows) through the sparse gather. nu is
        # NEVER int8: linear per-row int8 zeroes entries below
        # rowmax/127 and 1/(sqrt(nu)+eps) then diverges (_nu_sd).
        cfg = HeadConfig(num_labels=C, kind="adversarial_ns", n_neg=2,
                         reg=1e-3)
        gen = _gen()
        params, h, xg, y = _problem()
        ocfg = OptimizerConfig(name="adamw", learning_rate=0.05,
                               state_dtype="int8")
        opt = init_opt_state(ocfg, params)
        assert isinstance(opt.mu.w, QuantizedRows)
        assert opt.mu.b.dtype == jnp.bfloat16        # 1-D int8 fallback
        assert opt.nu.w.dtype == jnp.bfloat16        # int8 degrades (nu)
        assert opt.nu.b.dtype == jnp.bfloat16
        losses = []
        for s in range(15):
            r = jax.random.fold_in(jax.random.PRNGKey(31), s)
            loss, _, srows, _ = heads_lib.sparse_head_loss(cfg, params,
                                                           gen, h, xg, y,
                                                           r)
            losses.append(float(loss))
            params, opt, _ = apply_updates(ocfg, params, srows, opt)
        assert np.isfinite(np.asarray(params.w)).all()
        assert np.mean(losses[-3:]) < np.mean(losses[:3])

    def test_store_load_rows_round_trip(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
        assert store_rows(x, "fp32") is x
        b16 = store_rows(x, "bf16")
        assert b16.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(load_rows(b16)),
                                      np.asarray(x.astype(jnp.bfloat16)
                                                 .astype(jnp.float32)))
        qr = store_rows(x, "int8")
        assert isinstance(qr, QuantizedRows)
        assert qr.q.dtype == jnp.int8 and qr.scale.shape == (6,)
        # per-row scale: worst-case error is amax/254 per row
        err = np.abs(np.asarray(dequantize_rows(qr)) - np.asarray(x))
        bound = np.abs(np.asarray(x)).max(axis=1, keepdims=True) / 127
        assert (err <= bound + 1e-7).all()
        # 1-D int8 falls back to bf16; zero rows dequantize to zero
        v = store_rows(jnp.ones((5,), jnp.float32), "int8")
        assert v.dtype == jnp.bfloat16
        z = quantize_rows(jnp.zeros((3, 2)))
        np.testing.assert_array_equal(np.asarray(dequantize_rows(z)), 0.0)
        np.testing.assert_array_equal(np.asarray(z.scale), 1.0)


class TestLazyAdamW:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_random_touch_patterns_match_dense(self, seed):
        """The exact-lazy catch-up (ROADMAP item (d)): rows idle for a
        random number of steps replay their missed momentum decay, bias
        correction, and weight decay on next touch. A final all-rows
        touch forces every row through catch-up; params must then equal
        dense AdamW's."""
        c, k, steps = 24, 6, 9
        rng = np.random.default_rng(seed)
        ocfg = OptimizerConfig(name="adamw", learning_rate=0.03,
                               weight_decay=0.2)
        params = HeadParams(
            w=jnp.asarray(rng.standard_normal((c, k)), jnp.float32),
            b=jnp.asarray(rng.standard_normal((c,)), jnp.float32))
        pd = ps = params
        sd = ss = init_opt_state(ocfg, params)
        for s in range(steps):
            sp, gd = _random_sparse(rng, c, k, touch_all=(s == steps - 1))
            pd, sd, _ = apply_updates(ocfg, pd, gd, sd)
            ps, ss, _ = apply_updates(ocfg, ps, sp, ss)
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(ps.b), np.asarray(pd.b),
                                   rtol=1e-5, atol=1e-6)

    def test_mixed_sparse_dense_steps_stay_exact(self):
        """Alternating sparse and dense grads on the SAME state: the
        dense branch must also run the catch-up (and stamp ``last``) or
        the alternation diverges."""
        c, k = 12, 5
        rng = np.random.default_rng(7)
        ocfg = OptimizerConfig(name="adamw", learning_rate=0.05,
                               weight_decay=0.1, warmup_steps=3)
        params = HeadParams(
            w=jnp.asarray(rng.standard_normal((c, k)), jnp.float32),
            b=jnp.zeros((c,), jnp.float32))
        pd = ps = params
        sd = ss = init_opt_state(ocfg, params)
        for s in range(6):
            sp, gd = _random_sparse(rng, c, k, touch_all=(s == 5))
            pd, sd, _ = apply_updates(ocfg, pd, gd, sd)
            g = gd if s % 2 else sp           # alternate carriers
            ps, ss, _ = apply_updates(ocfg, ps, g, ss)
        np.testing.assert_allclose(np.asarray(ps.w), np.asarray(pd.w),
                                   rtol=1e-5, atol=1e-6)

    def test_long_gap_uses_closed_form_tail(self):
        """A 399-step gap exceeds the auto horizon (197 at beta1=0.9, the
        depth at which the momentum term is < 1e-9 of its start): the
        replay covers the first 197 missed steps and the closed-form
        pure-decay tail the remaining 202. Params must match the fully
        replayed reference to ~1e-6."""
        c, k = 4, 3
        rng = np.random.default_rng(1)
        mk = lambda horizon: OptimizerConfig(          # noqa: E731
            name="adamw", learning_rate=0.01, weight_decay=0.3,
            lazy_horizon=horizon)
        params = HeadParams(
            w=jnp.asarray(rng.standard_normal((c, k)), jnp.float32),
            b=jnp.asarray(rng.standard_normal((c,)), jnp.float32))
        sp0, _ = _random_sparse(rng, c, k, touch_all=True)
        sp1 = SparseRows(ids=jnp.asarray([0, c], jnp.int32),
                         dw=jnp.asarray(rng.standard_normal((2, k)),
                                        jnp.float32).at[-1].set(0.0),
                         db=jnp.zeros((2,), jnp.float32))
        outs = []
        for horizon in (1024, 0):   # full replay vs auto horizon + tail
            ocfg = mk(horizon)
            p, o, _ = apply_updates(ocfg, params, sp0,
                                    init_opt_state(ocfg, params))
            o = o._replace(step=jnp.asarray(400, jnp.int32))  # 399 idle
            p, o, _ = apply_updates(ocfg, p, sp1, o)
            outs.append(p)
        np.testing.assert_allclose(np.asarray(outs[1].w),
                                   np.asarray(outs[0].w),
                                   rtol=1e-5, atol=1e-6)


class TestEmbedSparse:
    def test_accumulate_embed_rows_matches_dense_scatter(self):
        rng = np.random.default_rng(0)
        v, d, t = 32, 8, 50
        ids = jnp.asarray(rng.integers(0, v, t), jnp.int32)  # duplicates
        dh = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
        sp = accumulate_embed_rows(ids, dh, v)
        assert sp.db is None and sp.ids.shape == (t,)
        live = np.asarray(sp.ids)
        live = live[live < v]
        assert len(np.unique(live)) == len(live)
        dw, db = sparse_lib.to_dense(sp, (v, d))
        assert db is None
        want = jnp.zeros((v, d)).at[ids].add(dh)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_train_step_sparse_embed_matches_dense_embed(self):
        from repro.data import lm_batch_fn
        from repro.models import lm_head
        from repro.models.config import ModelConfig
        from repro.train.step import init_train_state, make_train_step

        cfg = ModelConfig(name="t", num_layers=2, d_model=32, d_ff=64,
                          vocab_size=128, num_heads=2, num_kv_heads=2,
                          vocab_pad_multiple=64, gen_feature_dim=8,
                          dtype="float32", remat=False)
        hcfg = lm_head.head_config(cfg, "adversarial_ns", n_neg=2,
                                   reg=1e-4)
        opt = OptimizerConfig(name="adagrad", learning_rate=0.05,
                              clip_norm=1.0)
        make = lm_batch_fn(cfg.vocab_size, 4, 16, seed=0)
        st_d = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                "adversarial_ns")
        st_s = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                "adversarial_ns")
        step_d = jax.jit(make_train_step(cfg, hcfg, opt,
                                         head_update="sparse",
                                         embed_update="dense"))
        step_s = jax.jit(make_train_step(cfg, hcfg, opt,
                                         head_update="sparse",
                                         embed_update="sparse"))
        for s in range(3):
            r = jax.random.fold_in(jax.random.PRNGKey(1), s)
            b = {k: jnp.asarray(v) for k, v in make(s).items()}
            st_d, md = step_d(st_d, b, r)
            st_s, ms = step_s(st_s, b, r)
            np.testing.assert_allclose(float(ms["loss"]),
                                       float(md["loss"]), rtol=1e-5)
        for (pa, da), (pb, db_) in zip(
                jax.tree_util.tree_flatten_with_path(st_d.params)[0],
                jax.tree_util.tree_flatten_with_path(st_s.params)[0]):
            assert pa == pb
            np.testing.assert_allclose(np.asarray(db_), np.asarray(da),
                                       rtol=5e-3, atol=5e-5,
                                       err_msg=str(pa))


class TestGlobalNormStateLeaves:
    def test_fp32_norm_over_boxes(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
        qr = quantize_rows(x)
        cov = Sm3Cover(row=jnp.asarray([1.0, 2.0], jnp.bfloat16),
                       col=jnp.asarray([3.0], jnp.float32))
        dense = jnp.full((2, 2), 0.5, jnp.bfloat16)
        tree = {"a": qr, "b": cov, "c": dense}
        want = np.sqrt(
            float(jnp.sum(jnp.square(dequantize_rows(qr))))
            + (1.0 + 4.0 + 9.0) + 4 * 0.25)
        got = global_norm(tree)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(float(got), want, rtol=1e-6)


class TestCheckpoint:
    def _fitted(self, ocfg, steps=3):
        cfg = HeadConfig(num_labels=C, kind="adversarial_ns", n_neg=2,
                         reg=1e-3)
        gen = _gen()
        params, h, xg, y = _problem()
        if ocfg.state_dtype != "fp32":
            params = HeadParams(w=params.w.astype(jnp.bfloat16),
                                b=params.b.astype(jnp.bfloat16))
        opt = init_opt_state(ocfg, params)

        def more(params, opt, n, base):
            for s in range(n):
                r = jax.random.fold_in(jax.random.PRNGKey(41), base + s)
                _, _, srows, _ = heads_lib.sparse_head_loss(
                    cfg, params, gen, h, xg, y, r)
                params, opt, _ = apply_updates(ocfg, params, srows, opt)
            return params, opt

        params, opt = more(params, opt, steps, 0)
        return params, opt, more

    @pytest.mark.parametrize("name,sd", [("sm3", "bf16"),
                                         ("adamw", "int8")])
    def test_round_trip_bit_stable(self, tmp_path, name, sd):
        from repro.checkpoint import restore_checkpoint, save_checkpoint
        ocfg = OptimizerConfig(name=name, learning_rate=0.05,
                               state_dtype=sd)
        params, opt, _ = self._fitted(ocfg)
        tree = {"params": params, "opt": opt}
        save_checkpoint(str(tmp_path), 3, tree)
        got, step = restore_checkpoint(str(tmp_path), tree)
        assert step == 3
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(tree)[0],
                jax.tree_util.tree_flatten_with_path(got)[0]):
            assert pa == pb
            assert np.asarray(a).dtype == np.asarray(b).dtype, pa
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(pa))

    def test_resume_mid_run_replays_exactly(self, tmp_path):
        from repro.checkpoint import restore_checkpoint, save_checkpoint
        ocfg = OptimizerConfig(name="sm3", learning_rate=0.05,
                               state_dtype="bf16")
        params, opt, more = self._fitted(ocfg, steps=3)
        save_checkpoint(str(tmp_path), 3, {"params": params, "opt": opt})
        pa, oa = more(params, opt, 2, 3)          # straight through
        got, _ = restore_checkpoint(str(tmp_path),
                                    {"params": params, "opt": opt})
        rp = jax.tree.map(jnp.asarray, got["params"])
        ro = jax.tree.map(jnp.asarray, got["opt"])
        pb, ob = more(rp, ro, 2, 3)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(oa), jax.tree.leaves(ob)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestStateBytes:
    def test_sm3_bf16_is_4x_smaller_than_adamw_fp32(self):
        c, k = 4096, 64
        key = jax.random.PRNGKey(0)
        ref_p = heads_lib.init_head_params(key, c, k)
        ref = init_opt_state(OptimizerConfig(name="adamw"), ref_p)
        sm_p = heads_lib.init_head_params(key, c, k, dtype=jnp.bfloat16)
        sm = init_opt_state(OptimizerConfig(name="sm3",
                                            state_dtype="bf16"), sm_p)
        big = head_state_bytes(ref_p, ref)
        small = head_state_bytes(sm_p, sm)
        # adamw/fp32: 12K+20 B/label; sm3/bf16: ~2K+6 B/label -> ~5.8x
        assert big / small >= 4.0, (big, small)
        # abstract (eval_shape) and concrete trees agree
        ap, ao = jax.eval_shape(lambda: (sm_p, sm))
        assert head_state_bytes(ap, ao) == small

    def test_head_leaves_only_in_full_param_tree(self):
        tree = {"trunk": jnp.zeros((8, 8)),
                "head": {"w": jnp.zeros((4, 2)), "b": jnp.zeros((4,))}}
        assert head_state_bytes(tree, None) == (4 * 2 + 4) * 4


class TestShardedState:
    @pytest.mark.slow
    def test_sharded_sm3_and_adamw_match_unsharded(self):
        run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.core.heads import HeadParams
        from repro.optim import OptimizerConfig, apply_updates, \\
            init_opt_state
        from repro.optim.sparse import SparseRows

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        c, k = 64, 8
        rng = np.random.default_rng(0)
        params = HeadParams(
            w=jnp.asarray(rng.standard_normal((c, k)), jnp.float32),
            b=jnp.asarray(rng.standard_normal((c,)), jnp.float32))

        def sweep(ocfg, steps=4):
            p1 = p2 = params
            s1 = s2 = init_opt_state(ocfg, params)
            for t in range(steps):
                n = 7 if t < steps - 1 else c
                ids = (rng.choice(c, n, replace=False) if n < c
                       else np.arange(c))
                ids = jnp.asarray(np.append(ids, c), jnp.int32)
                dw = jnp.asarray(rng.standard_normal((n + 1, k)),
                                 jnp.float32).at[-1].set(0.0)
                db = jnp.asarray(rng.standard_normal((n + 1,)),
                                 jnp.float32).at[-1].set(0.0)
                g = SparseRows(ids=ids, dw=dw, db=db)
                p1, s1, _ = apply_updates(ocfg, p1, g, s1)
                p2, s2, _ = apply_updates(ocfg, p2, g, s2, mesh=mesh)
            np.testing.assert_allclose(np.asarray(p2.w),
                                       np.asarray(p1.w),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(p2.b),
                                       np.asarray(p1.b),
                                       rtol=1e-5, atol=1e-6)

        # sm3/bf16: the col cover is recombined by pmax across shards;
        # adamw/fp32: the per-row last + catch-up must mask non-owned
        # (clamped-garbage) gathered rows.
        sweep(OptimizerConfig(name="sm3", learning_rate=0.1,
                              state_dtype="bf16"))
        sweep(OptimizerConfig(name="adamw", learning_rate=0.05,
                              weight_decay=0.2))
        print("sharded state OK")
        """)


class TestSnapshotThenDonate:
    def test_fit_snapshot_survives_donated_step(self):
        from repro.models.config import ModelConfig
        from repro.train.loop import _fit_snapshot
        from repro.train.step import init_train_state

        cfg = ModelConfig(name="t", num_layers=1, d_model=16, d_ff=32,
                          vocab_size=64, num_heads=2, num_kv_heads=2,
                          vocab_pad_multiple=64, gen_feature_dim=4,
                          dtype="float32", remat=False)
        opt = OptimizerConfig(name="adagrad", learning_rate=0.05)
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt,
                                 "adversarial_ns")
        want = np.asarray(state.params["embed"]).copy()
        snap = _fit_snapshot(state)
        # distinct buffers: donation of `state` cannot alias the snapshot
        assert (snap.params["embed"].unsafe_buffer_pointer()
                != state.params["embed"].unsafe_buffer_pointer())

        @functools.partial(jax.jit, donate_argnums=(0,))
        def bump(s):
            return s._replace(
                step=s.step + 1,
                params=jax.tree.map(lambda x: x * 2.0, s.params))

        bump(state)
        # the snapshot still reads the pre-step values even though the
        # submitted state's buffers were donated away
        np.testing.assert_array_equal(np.asarray(snap.params["embed"]),
                                      want)
        np.testing.assert_array_equal(
            np.asarray(snap.gen_fit_step),
            np.asarray(-1, snap.gen_fit_step.dtype))
