"""Explicit shard_map collectives for the hot communication paths.

GSPMD's automatic partitioning is the baseline; these are the hand-rolled
versions used by the perf iterations and by the gradient-compression path:

- ``sharded_candidate_scores``: score sampled labels against a vocab-sharded
  output embedding — each model shard serves only the rows it owns, one psum
  of the (tiny) score tensor. Matches the masked-gather+allreduce GSPMD
  lowering but guarantees it (no all-gather fallback) and fuses the dot.
- ``compressed_grad_allreduce``: int8 error-feedback gradient all-reduce over
  the data axes (distributed-optimization trick for the pod-level DP
  collective; see repro.optim.compression).
- ``sharded_rows_update``: apply a per-row transform (the sparse-head
  optimizer update, DESIGN.md §8) at sampled ids of vocab-sharded arrays —
  each model shard gathers/updates only the rows it owns; replicated ids,
  no all-gather, no cross-shard traffic at all (the row ownership logic of
  ``sharded_candidate_scores``, reused for the write path).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import mesh_axes


def sharded_candidate_scores(mesh: Mesh, w, b, h, ids):
    """xi_{ids} = w[ids] . h + b[ids] with w (V,K) sharded over 'model'.

    h: (..., K) replicated over 'model'; ids: (..., n). Output replicated
    over 'model' (one psum of the score tensor, O(batch·n) bytes).
    """
    dp_axes, model = mesh_axes(mesh)
    n_shards = mesh.shape[model]
    v = w.shape[0]
    shard_rows = v // n_shards

    def local(w_l, b_l, h_l, ids_l):
        me = jax.lax.axis_index(model)
        lo = me * shard_rows
        local_ids = ids_l - lo
        mine = (local_ids >= 0) & (local_ids < shard_rows)
        safe = jnp.clip(local_ids, 0, shard_rows - 1)
        rows = jnp.take(w_l, safe, axis=0)            # (..., n, K)
        scores = (jnp.einsum("...nk,...k->...n", rows.astype(jnp.float32),
                             h_l.astype(jnp.float32))
                  + jnp.take(b_l, safe).astype(jnp.float32))
        scores = jnp.where(mine, scores, 0.0)
        return jax.lax.psum(scores, model)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(model, None), P(model), P(*([None] * h.ndim)),
                  P(*([None] * ids.ndim))),
        out_specs=P(*([None] * ids.ndim)))(w, b, h, ids)


def sharded_rows_update(mesh: Mesh, fn, ids, vals, dense_arrays,
                        rep_arrays=(), with_mask: bool = False):
    """Row-local transform of vocab-sharded arrays at sampled ``ids``.

    dense_arrays: sequence of (V, ...) arrays sharded over 'model' on dim 0
    (params + optimizer accumulators); ids: (U,) int32, replicated, deduped
    (sentinel ids >= V are dropped); vals: sequence of (U, ...) replicated
    per-row gradient coefficients. ``fn(rows_tuple, vals_tuple) ->
    new_rows_tuple`` is the per-row optimizer math — ONE call covers every
    array touched by the update (w + b + their accumulators), so the whole
    sparse optimizer step is a single shard_map. Each shard resolves
    ``ids`` against the row range it owns (same ownership arithmetic as
    :func:`sharded_candidate_scores`), gathers only its rows, applies
    ``fn``, and scatters back — O(U·K) work per shard and zero collective
    traffic: non-owned and sentinel ids clamp on the gather and drop on
    the scatter.

    rep_arrays / with_mask extend the contract for factored state (the
    SM3 column cover, DESIGN.md §11): ``rep_arrays`` are small replicated
    arrays passed whole to ``fn``, whose updated values are recombined
    across shards with a pmax — exact because the cover update is a
    monotone max. When either is used, ``fn`` is called as
    ``fn(rows, vals, reps, mine) -> (new_rows, new_reps)`` where ``mine``
    is the (U,) ownership mask: non-owned ids gather clamped *garbage*
    rows carrying real gradient values, and fn must exclude them from any
    cross-row reduction (their row scatters are dropped regardless).
    """
    dp_axes, model = mesh_axes(mesh)
    n_shards = mesh.shape[model]
    n_vals = len(vals)
    n_dense = len(dense_arrays)
    extended = with_mask or rep_arrays
    for d in dense_arrays:
        assert d.shape[0] % n_shards == 0, (d.shape, n_shards)

    def local(ids_l, *rest):
        vals_l = rest[:n_vals]
        dense_l = rest[n_vals:n_vals + n_dense]
        reps_l = rest[n_vals + n_dense:]
        me = jax.lax.axis_index(model)
        rows, shard_rows = [], []
        mine_any = None
        for d in dense_l:
            n_rows = d.shape[0]
            loc = ids_l - me * n_rows
            safe = jnp.clip(loc, 0, n_rows - 1)
            rows.append(d[safe])
            shard_rows.append((loc, n_rows))
            if mine_any is None:
                mine_any = (loc >= 0) & (loc < n_rows)
        if extended:
            new_rows, new_reps = fn(tuple(rows), tuple(vals_l),
                                    tuple(reps_l), mine_any)
            new_reps = tuple(jax.lax.pmax(r, model) for r in new_reps)
        else:
            new_rows = fn(tuple(rows), tuple(vals_l))
            new_reps = ()
        out = []
        for d, r, (loc, n_rows) in zip(dense_l, new_rows, shard_rows):
            mine = (loc >= 0) & (loc < n_rows)
            tgt = jnp.where(mine, jnp.clip(loc, 0, n_rows - 1),
                            n_rows)                  # non-mine -> dropped
            out.append(d.at[tgt].set(r.astype(d.dtype), mode="drop"))
        return tuple(out) + new_reps

    rep = lambda a: P(*([None] * a.ndim))            # noqa: E731
    dense_spec = tuple(P(model, *([None] * (d.ndim - 1)))
                       for d in dense_arrays)
    rep_spec = tuple(rep(a) for a in rep_arrays)
    # check_vma off: the lazy-AdamW catch-up replay (DESIGN.md §11) is a
    # fori_loop, and `while` has no shard_map replication rule. Replication
    # still holds by construction: rows carry the model axis, reps are
    # pmax'd.
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep(ids),) + tuple(rep(v) for v in vals) + dense_spec
        + rep_spec,
        out_specs=dense_spec + rep_spec, check_vma=False)(
        ids, *vals, *dense_arrays, *rep_arrays)
    if extended:
        return out[:n_dense], out[n_dense:]
    return out


def compressed_grad_allreduce(mesh: Mesh, grads_stacked: Any, ef_stacked):
    """int8 error-feedback all-reduce over the data axes.

    Per-replica gradients arrive stacked on a leading replica axis of size
    n_dp, sharded over the data axes (shard_map gives each replica its own
    slice). Each replica quantizes (grad + residual) to int8; the int8
    payload is psum'd (4x fewer wire bytes than fp32); the shared max-scale
    dequantizes the sum; the quantization mismatch lands in the residual and
    is re-injected next step (EF-SGD).

    Returns (mean_grads replicated, new_ef stacked like the input).
    """
    from repro.optim.compression import _dequantize_leaf

    dp_axes, model = mesh_axes(mesh)
    n_rep = 1
    for a in dp_axes:
        n_rep *= mesh.shape[a]

    def leaf_fn(g, e):
        g = g[0]                      # local replica slice (1, ...) -> (...)
        e = e[0]
        corrected = g.astype(jnp.float32) + e
        # Shared scale across replicas so the int8 sum is exact.
        amax = jax.lax.pmax(jnp.max(jnp.abs(corrected)), dp_axes)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(
            jnp.int8)
        q_sum = jax.lax.psum(q.astype(jnp.int32), dp_axes)
        mean = q_sum.astype(jnp.float32) * scale / n_rep
        new_e = corrected - _dequantize_leaf(q, scale)
        return mean, new_e[None]

    def body(grads_l, err_l):
        out = jax.tree.map(leaf_fn, grads_l, err_l)
        means = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        errs = jax.tree.map(lambda t: t[1], out,
                            is_leaf=lambda t: isinstance(t, tuple))
        return means, errs

    stack_spec = jax.tree.map(
        lambda g: P(dp_axes, *([None] * (g.ndim - 1))), grads_stacked)
    mean_spec = jax.tree.map(
        lambda g: P(*([None] * (g.ndim - 1))), grads_stacked)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(stack_spec, stack_spec),
        out_specs=(mean_spec, stack_spec))(grads_stacked, ef_stacked)
