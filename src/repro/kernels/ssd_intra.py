"""The SSD's intra-chunk block as one Pallas TPU kernel, forward and backward.

Within a chunk of Q steps the Mamba-2 recurrence is a masked,
decay-weighted matmul (``repro.models.ssm.ssd_chunked``): per head h,

    L_h = exp(where(i >= j, seg_i,h - seg_j,h, -inf))      (Q, Q)
    y_h = (cb ∘ L_h) @ xbar_h,   cb = cm @ bmᵀ               (Q, P)

XLA builds the (Q, Q, H) decay tensor and, in the backward pass, its full
f32 cotangent in HBM. Here every (Q, Q) tile lives in VMEM: the grid walks
(batch, chunk, head block) with the head blocks innermost, ``cb`` is
computed once per chunk into scratch and reused by every head, and L is
rebuilt per head from ``seg``. The mask is applied before the ``exp``: above
the diagonal ``seg_i - seg_j`` is a growing positive sum that overflows.

The backward pass recomputes ``cb`` and L from the inputs, so the residuals
are the inputs alone and only O(Q·(N + H·P)) values per chunk leave the
kernel: with dW_h = dy_h @ xbar_hᵀ,

    dxbar_h = (cb ∘ L_h)ᵀ @ dy_h
    dS_h    = dW_h ∘ cb ∘ L_h,   dseg_h = rowsum(dS_h) - colsum(dS_h)
    dcb     = Σ_h dW_h ∘ L_h,    dcm = dcb @ bm,  dbm = dcbᵀ @ cm

The kernel works on the transposes, (H·P, S) activations with the sequence
on the lanes, which is the layout XLA gives the SSM's activations: its
causal conv and its ``cumsum`` run along the sequence. Every (Q, Q) tile is
indexed [j, i] (source, target), a head's P rows are a sublane slice, and
no operand needs a relayout copy around the call.

Precision: the matmuls take ``mxu_dtype`` operands with f32 accumulation;
the mask, the ``exp``, ``cb ∘ L``, every reduction and every output are
f32. ``kernels.ops`` passes the dtype XLA's default precision feeds the
matrix unit for f32 on the platform (one bf16 pass on a TPU, f32 on the
CPU), so the kernel does the same arithmetic as the einsums it replaces.
Q must be a multiple of 128, the lane width (``kernels.ops`` keeps shorter
chunks on the einsums).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.custom_partitioning import custom_partitioning
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

LANES = 128
# Most heads per grid step: fewer steps against larger blocks in VMEM.
_HEADS_PER_STEP_MAX = 8


def heads_per_block(h: int, p: int) -> int:
    """The most heads, at most _HEADS_PER_STEP_MAX, that divide H and whose
    rows tile to 8 sublanes; all H if none does."""
    fits = [k for k in range(1, min(h, _HEADS_PER_STEP_MAX) + 1)
            if h % k == 0 and (k * p) % 8 == 0]
    return max(fits) if fits else h


def _dot(a, b, mxu_dtype, contract=((1,), (0,))):
    return jax.lax.dot_general(a.astype(mxu_dtype), b.astype(mxu_dtype),
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _decay_t(seg, seg_t, h):
    """L_hᵀ (Q, Q) f32, indexed [j, i], from seg (Q, H) and seg_t (H, Q)."""
    q, nh = seg.shape
    seg_j = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (q, nh), 1) == h, seg, 0.0),
        axis=1, keepdims=True)                         # (Q, 1)
    seg_i = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (nh, q), 0) == h, seg_t, 0.0),
        axis=0, keepdims=True)                         # (1, Q)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return jnp.exp(jnp.where(causal, seg_i - seg_j, -jnp.inf))


def _rows(k, p: int):
    """Head k's P rows of a head block."""
    return pl.ds(pl.multiple_of(k * p, 8) if p % 8 == 0 else k * p, p)


def _fwd_kernel(cm_ref, bm_ref, seg_ref, segt_ref, x_ref, y_ref, cbt_scr, *,
                hpb: int, p: int, mxu_dtype):
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _cb():
        cbt_scr[...] = _dot(bm_ref[0], cm_ref[0], mxu_dtype, ((1,), (1,)))

    seg, seg_t = seg_ref[0], segt_ref[0]

    def head(k, carry):
        rows = _rows(k, p)
        m_t = cbt_scr[...] * _decay_t(seg, seg_t, blk * hpb + k)
        y_ref[0, rows, :] = _dot(x_ref[0, rows, :], m_t, mxu_dtype)
        return carry

    jax.lax.fori_loop(0, hpb, head, 0)


def _bwd_kernel(cm_ref, bm_ref, seg_ref, segt_ref, x_ref, dy_ref,
                dx_ref, dcm_ref, dbm_ref, dseg_j_ref, dseg_i_ref,
                cbt_scr, dcbt_scr, *, hpb: int, p: int, mxu_dtype):
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _init():
        cbt_scr[...] = _dot(bm_ref[0], cm_ref[0], mxu_dtype, ((1,), (1,)))
        dcbt_scr[...] = jnp.zeros_like(dcbt_scr)
        dseg_j_ref[...] = jnp.zeros_like(dseg_j_ref)
        dseg_i_ref[...] = jnp.zeros_like(dseg_i_ref)

    seg, seg_t = seg_ref[0], segt_ref[0]
    q, nh = seg.shape

    def head(k, carry):
        h = blk * hpb + k
        rows = _rows(k, p)
        l_t = _decay_t(seg, seg_t, h)
        m_t = cbt_scr[...] * l_t
        x, dy = x_ref[0, rows, :], dy_ref[0, rows, :]
        dw_t = _dot(x, dy, mxu_dtype, ((0,), (0,)))    # dW_h ᵀ (Q, Q)
        ds_t = dw_t * m_t
        dcbt_scr[...] += dw_t * l_t
        dseg_j_ref[0] += jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (q, nh), 1) == h,
            jnp.sum(ds_t, axis=1, keepdims=True), 0.0)
        dseg_i_ref[0] += jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (nh, q), 0) == h,
            jnp.sum(ds_t, axis=0, keepdims=True), 0.0)
        dx_ref[0, rows, :] = _dot(dy, m_t, mxu_dtype, ((1,), (1,)))
        return carry

    jax.lax.fori_loop(0, hpb, head, 0)

    @pl.when(blk == pl.num_programs(2) - 1)
    def _finish():
        dcbt = dcbt_scr[...]
        dcm_ref[0] = _dot(dcbt, bm_ref[0], mxu_dtype, ((0,), (0,)))
        dbm_ref[0] = _dot(dcbt, cm_ref[0], mxu_dtype)


def _layout(cm, bm, seg, xbar):
    """The kernel's operands: cm, bm (B, S, N), seg (B, S, H), seg_t
    (B, H, S), x_t (B, H·P, S)."""
    bsz, nc, q, nh, p = xbar.shape
    s = nc * q
    seg = seg.reshape(bsz, s, nh)
    return (cm.reshape(bsz, s, -1), bm.reshape(bsz, s, -1), seg,
            jnp.swapaxes(seg, 1, 2),
            jnp.swapaxes(xbar.reshape(bsz, s, nh * p), 1, 2))


def _from_t(a_t, shape):
    """(B, H·P, S) -> (B, nc, Q, H, P)."""
    return jnp.swapaxes(a_t, 1, 2).reshape(shape)


def _run(backward: bool, q: int, hpb: int, p: int, interpret: bool,
         mxu_dtype, *args):
    """One pallas_call over (B, nc, head blocks): the forward kernel on
    _layout's operands, or the backward kernel on those and dy_t."""
    bsz, s, n = args[0].shape
    nh = args[2].shape[2]
    specs = [pl.BlockSpec((1, q, n), lambda b, c, j: (b, c, 0)),
             pl.BlockSpec((1, q, n), lambda b, c, j: (b, c, 0)),
             pl.BlockSpec((1, q, nh), lambda b, c, j: (b, c, 0)),
             pl.BlockSpec((1, nh, q), lambda b, c, j: (b, 0, c)),
             pl.BlockSpec((1, hpb * p, q), lambda b, c, j: (b, j, c))]
    # Each output takes the blocks of the operand it has the shape of.
    outs = [4, 0, 1, 2, 3] if backward else [4]
    kernel = functools.partial(_bwd_kernel if backward else _fwd_kernel,
                               hpb=hpb, p=p, mxu_dtype=mxu_dtype)
    return pl.pallas_call(
        kernel,
        grid=(bsz, s // q, nh // hpb),
        in_specs=specs + specs[4:] * (len(args) - 5),
        out_specs=[specs[like] for like in outs],
        out_shape=[jax.ShapeDtypeStruct(args[like].shape, jnp.float32)
                   for like in outs],
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32)] * (1 + backward),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_intra",
    )(*args)


# On a mesh, each device runs the kernel on its rows of the batch: a Mosaic
# call cannot be split by the SPMD partitioner itself.
_run_sharded = custom_partitioning(_run, static_argnums=tuple(range(6)))


def _batch_sharding(mesh, arg_shapes):
    spec = arg_shapes[4].sharding.spec
    return NamedSharding(mesh, P(spec[0] if len(spec) else None))


def _partition(*static_and_shapes):
    *static, mesh, arg_shapes, result_shape = static_and_shapes
    rows = _batch_sharding(mesh, arg_shapes)
    return (mesh, functools.partial(_run, *static),
            (rows,) * len(result_shape), (rows,) * len(arg_shapes))


def _infer(*static_and_shapes):
    *static, mesh, arg_shapes, result_shape = static_and_shapes
    return (_batch_sharding(mesh, arg_shapes),) * len(result_shape)


def _rule(backward, *static_and_types):
    operands = "b s n, b s n, b s h, b h s, b f s"
    if backward:
        return (operands + ", b f s -> b f s, b s n, b s n, b s h, b h s",
                {"need_replication_factors": ("s", "n", "h", "f")})
    return (operands + " -> b f s",
            {"need_replication_factors": ("s", "n", "h", "f")})


_run_sharded.def_partition(partition=_partition,
                           infer_sharding_from_operands=_infer,
                           sharding_rule=_rule)


def _forward(cm, bm, seg, xbar, interpret, mxu_dtype):
    q, nh, p = xbar.shape[2:]
    (y_t,) = _run_sharded(False, q, heads_per_block(nh, p), p, interpret,
                          mxu_dtype, *_layout(cm, bm, seg, xbar))
    return _from_t(y_t, xbar.shape)


def _backward(cm, bm, seg, xbar, dy, interpret, mxu_dtype):
    q, nh, p = xbar.shape[2:]
    dx_t, dcm, dbm, dseg_j, dseg_i = _run_sharded(
        True, q, heads_per_block(nh, p), p, interpret, mxu_dtype,
        *_layout(cm, bm, seg, xbar), _layout(cm, bm, seg, dy)[4])
    dseg = jnp.swapaxes(dseg_i, 1, 2) - dseg_j
    return (dcm.reshape(cm.shape), dbm.reshape(bm.shape),
            dseg.reshape(seg.shape), _from_t(dx_t, xbar.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_intra(cm, bm, seg, xbar, interpret=False, mxu_dtype=jnp.bfloat16):
    """cm, bm: (B, nc, Q, N); seg: (B, nc, Q, H) the within-chunk cumulative
    dt·A; xbar: (B, nc, Q, H, P). Returns y_intra (B, nc, Q, H, P) f32."""
    return _forward(cm, bm, seg, xbar, interpret, mxu_dtype)


def _ssd_intra_fwd(cm, bm, seg, xbar, interpret, mxu_dtype):
    return (_forward(cm, bm, seg, xbar, interpret, mxu_dtype),
            (cm, bm, seg, xbar))


def _ssd_intra_bwd(interpret, mxu_dtype, res, dy):
    return _backward(*res, dy, interpret, mxu_dtype)


ssd_intra.defvjp(_ssd_intra_fwd, _ssd_intra_bwd)
