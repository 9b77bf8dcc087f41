"""Sampled-head score Pallas TPU kernel: xi = w[ids]·h + b[ids].

This is the paper's O(K) replacement for the O(K·C) logits matmul: per token
only 1 + n_neg rows of the (C, K) output embedding are touched. The XLA
lowering of the same computation materializes the gathered (T, n, K) rows in
HBM before the dot; this kernel brings each row into VMEM once and reduces
it there, using scalar prefetch for the data-dependent row indices — the
TPU-native analogue of the paper's sparse gradient update.

Row gather (:func:`row_tile_specs`, :func:`stash_rows`, shared with the
fused sampled-loss kernel): a TPU copy moves whole (sublane × lane) tiles,
and Mosaic refuses a one-row slice of an HBM table or any slice of a table
whose width is not a multiple of 128 lanes (K=1600, k=32). So the table is
read in (s, K) row tiles (s = 8 rows for fp32, 16 for bf16): each grid
step fetches ``GROUP`` tiles through BlockSpecs whose index maps read the
prefetched ids. Pallas
double-buffers those fetches; the kernel keeps the one wanted row of each
tile. The bias is one scalar per row, so its gather (O(T·n) values) runs in
XLA and arrives as an ordinary VMEM block.

Grid: (T / blk_t, blk_t·n / GROUP). The inner axis stashes rows into a
slot-major fp32 VMEM scratch (n, blk_t, K); its last step scores the block,
one slot at a time on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows gathered per grid step (one BlockSpec each).
GROUP = 8
# Gathered rows held in VMEM per token block (n · blk_t ≤ this): 512 fp32
# rows of K=1600 take 3.4 MB, well inside the default scoped VMEM limit.
_MAX_ROWS = 512


def token_block(t: int, n: int, blk_t: int) -> int:
    """Tokens per block: a multiple of 8 (the sublane tile), at most
    ``blk_t`` and ``_MAX_ROWS // n``, and no more than ``t`` needs."""
    cap = min(blk_t, _MAX_ROWS // max(n, 1), -(-t // 8) * 8)
    return max(8, cap // 8 * 8)


def row_tile_specs(w, rows_per_block: int):
    """``GROUP`` BlockSpecs of (s, K) row tiles over the (C, K) table
    (s = 32 bytes' worth of rows: 8 fp32, 16 bf16; s must divide C, as
    the vocab padding guarantees): at grid step (it, j), spec q fetches
    the tile holding row ``ids[it·rows_per_block + j·GROUP + q]``."""
    sub = 32 // w.dtype.itemsize
    assert w.shape[0] % sub == 0, (w.shape, sub)

    def index(it, j, ids, *, q):
        return ids[it * rows_per_block + j * GROUP + q] // sub, 0

    return [pl.BlockSpec((sub, w.shape[1]), functools.partial(index, q=q))
            for q in range(GROUP)]


def stash_rows(ids_ref, tile_refs, rows_ref, first):
    """Copy the wanted row of each fetched tile into the slot-major
    scratch: block row jj = j·GROUP + q (token jj // n, slot jj % n)
    lands at ``rows_ref[jj % n, jj // n]``; ``first`` is the block's
    offset into the flat ids."""
    n = rows_ref.shape[0]
    j = pl.program_id(1)
    sub = tile_refs[0].shape[0]
    sub_row = jax.lax.broadcasted_iota(jnp.int32, tile_refs[0].shape, 0)
    for q, tile_ref in enumerate(tile_refs):
        jj = j * GROUP + q
        r = ids_ref[first + jj] % sub
        # Select by mask: a dynamic one-row load is refused on packed
        # (bf16) tiles, and the mask also keeps a non-finite neighbour row
        # out of the sum.
        row = jnp.sum(jnp.where(sub_row == r,
                                tile_ref[...].astype(jnp.float32), 0.0),
                      axis=0, keepdims=True)
        rows_ref[jj % n, pl.ds(jj // n, 1), :] = row


def pad_tokens(blk_t: int, *arrays):
    """Zero-pad each (T, …) array up to a multiple of ``blk_t`` rows.
    Padding tokens gather row 0 and score against h = 0; callers slice
    their outputs back to T."""
    t = arrays[0].shape[0]
    pad = (-t) % blk_t
    if not pad:
        return arrays
    return tuple(jnp.concatenate(
        [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)
        for a in arrays)


def _kernel(ids_ref, *refs, blk_t: int, n: int):
    tile_refs = refs[:GROUP]
    bias_ref, h_ref, o_ref, rows_ref = refs[GROUP:]
    it, j = pl.program_id(0), pl.program_id(1)
    stash_rows(ids_ref, tile_refs, rows_ref, it * blk_t * n)

    @pl.when(j == pl.num_programs(1) - 1)
    def _score():
        h = h_ref[...].astype(jnp.float32)             # (blk_t, K)
        bias = bias_ref[...]                           # (blk_t, n) fp32
        for c in range(n):
            score = jnp.sum(rows_ref[c] * h, axis=-1, keepdims=True)
            o_ref[:, c:c + 1] = score + bias[:, c:c + 1]


def gather_scores(w, b, h, ids, *, blk_t: int = 256,
                  interpret: bool = False):
    """w: (C,K), b: (C,), h: (T,K), ids: (T,n) int32 -> (T,n) fp32."""
    t, k = h.shape
    n = ids.shape[-1]
    blk_t = token_block(t, n, blk_t)
    ids = ids.astype(jnp.int32)
    h, ids = pad_tokens(blk_t, h, ids)
    t_pad = h.shape[0]

    kernel = functools.partial(_kernel, blk_t=blk_t, n=n)
    tok = pl.BlockSpec((blk_t, n), lambda it, j, ids: (it, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // blk_t, blk_t * n // GROUP),
        in_specs=row_tile_specs(w, blk_t * n) + [
            tok,                                       # b[ids], fp32
            pl.BlockSpec((blk_t, k), lambda it, j, ids: (it, 0)),
        ],
        out_specs=tok,
        scratch_shapes=[pltpu.VMEM((n, blk_t, k), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids.reshape(-1), *([w] * GROUP), b[ids].astype(jnp.float32), h)
    return out[:t]
