"""Dense tree log-prob Pallas TPU kernel (the Eq. 5 bias-removal hot spot).

Computes log p_n(leaf | x) for ALL leaves: serving adds this (B, C) tensor to
the logits, so at gemma2 scale it is a (B, 256k) production every decode
step. The XLA path materializes every intermediate level in HBM
(sum_l 2^l = C extra floats per example); this kernel keeps the whole
recursion for a C_blk-leaf subtree in VMEM.

Key structural insight (TPU adaptation of the pointer-walking CPU code):
for an ALIGNED contiguous leaf block [c0, c0 + C_blk), the ancestry factors
as   logp(leaf) = prefix(x, c0)  +  subtree-recursion(x, nodes of block).
The prefixes of all blocks are the leaves of the tree's top
depth - log2(C_blk) levels: a small XLA sum over those levels, each
block's branch log-sigmoid gathered from one matmul of the top nodes,
done before the kernel. The subtree nodes occupy one contiguous range
per level. With the node table shifted down one row (row i + 1 holds
node i), full-tree level L starts at row 2^L, so block ic's nodes at
subtree level l sit in an aligned run of 2^l rows: one BlockSpec per level fetches them (or the whole
sublane tile around them while 2^l is below a tile). Each level is scored
with one matmul and spread over the leaves below its nodes with a 0/1
matmul, so no step needs a lane interleave.

Grid: (B/blk_b, C/blk_c); VMEM per step ~ blk_b·blk_c + blk_c·k floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _block_prefixes(w, b, x, pre_depth: int):
    """(B, 2^pre_depth) log-probs of reaching each aligned leaf block:
    over the top ``pre_depth`` levels, the sum of the log-sigmoid of the
    branch the block's path takes at its node on that level."""
    n_top = (1 << pre_depth) - 1
    z = jax.lax.dot_general(                           # (B, n_top)
        x.astype(jnp.float32), w[:n_top].astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) + b[:n_top].astype(jnp.float32)
    blk = jnp.arange(1 << pre_depth)
    prefix = jnp.zeros((x.shape[0], 1 << pre_depth), jnp.float32)
    for level in range(pre_depth):
        node = (1 << level) - 1 + (blk >> (pre_depth - level))
        right = ((blk >> (pre_depth - level - 1)) & 1) == 1
        z_blk = z[:, node]
        prefix = prefix + jax.nn.log_sigmoid(jnp.where(right, z_blk, -z_blk))
    return prefix


def _kernel(x_ref, prefix_ref, *refs, pre_depth: int, sub_depth: int):
    *level_refs, o_ref = refs
    w_refs, b_refs = level_refs[:sub_depth], level_refs[sub_depth:]
    ic = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                 # (blk_b, k)
    blk_c = 1 << sub_depth
    leaf = jax.lax.broadcasted_iota(jnp.int32, (1, blk_c), 1)
    logp = jnp.broadcast_to(prefix_ref[0], (x.shape[0], blk_c))
    for level, (w_ref, b_ref) in enumerate(zip(w_refs, b_refs)):
        span = w_ref.shape[0]
        shift = sub_depth - level
        # Row of the block's first level node within the fetched span.
        first = ((1 << (pre_depth + level)) + ic * (1 << level)) % span
        z = jax.lax.dot_general(                       # (blk_b, span)
            x, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32
        ) + b_ref[...].astype(jnp.float32)
        node = jax.lax.broadcasted_iota(jnp.int32, (span, blk_c), 0)
        below = jax.lax.broadcasted_iota(jnp.int32, (span, blk_c), 1)
        spread = (node == first + (below >> shift)).astype(jnp.float32)
        z_leaf = jax.lax.dot_general(                  # (blk_b, blk_c)
            z, spread, (((1,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
        right = ((leaf >> (shift - 1)) & 1) == 1
        logp = logp + jax.nn.log_sigmoid(jnp.where(right, z_leaf, -z_leaf))
    o_ref[...] = logp


def _level_specs(pre_depth: int, level: int, span: int, k: int):
    """BlockSpecs of the ``span`` shifted-table rows (and their biases)
    that hold leaf block ic's nodes at subtree level ``level``."""
    def at(ic):
        return ((1 << (pre_depth + level)) + ic * (1 << level)) // span

    return (pl.BlockSpec((span, k), lambda ib, ic: (at(ic), 0)),
            pl.BlockSpec((None, 1, span), lambda ib, ic: (at(ic), 0, 0)))


def tree_logprob_all(w, b, x, *, blk_b: int = 128, blk_c: int = 256,
                     interpret: bool = False):
    """w: (n_nodes, k), b: (n_nodes,), x: (B, k) -> (B, C_pad) fp32."""
    n_nodes = b.shape[0]
    depth = (n_nodes + 1).bit_length() - 1
    assert (1 << depth) == n_nodes + 1
    c_pad = 1 << depth
    bsz, k = x.shape
    blk_c = min(blk_c, c_pad)
    blk_b = min(blk_b, bsz)
    assert c_pad % blk_c == 0 and bsz % blk_b == 0
    assert (blk_c & (blk_c - 1)) == 0 and blk_c >= 2, \
        "blk_c must be a power of two >= 2"
    sub_depth = blk_c.bit_length() - 1
    pre_depth = depth - sub_depth

    # (n_blk, B, 1): one prefix per leaf block, broadcast over its leaves.
    prefix = _block_prefixes(w, b, x, pre_depth).T[:, :, None]

    # Node table shifted one row down (and padded to whole sublane tiles):
    # copies w, which is C·k floats against the kernel's B·C output.
    tile = 32 // w.dtype.itemsize
    rows = max(c_pad, tile)
    w_rows = jnp.pad(w, ((1, rows - c_pad), (0, 0)))
    b_rows = jnp.pad(b, (1, rows - c_pad))
    w_specs, b_specs, b_views = [], [], []
    for level in range(sub_depth):
        span = max(1 << level, tile)
        w_spec, b_spec = _level_specs(pre_depth, level, span, k)
        w_specs.append(w_spec)
        b_specs.append(b_spec)
        b_views.append(b_rows.reshape(rows // span, 1, span))

    kernel = functools.partial(_kernel, pre_depth=pre_depth,
                               sub_depth=sub_depth)
    return pl.pallas_call(
        kernel,
        grid=(bsz // blk_b, c_pad // blk_c),
        in_specs=[
            pl.BlockSpec((blk_b, k), lambda ib, ic: (ib, 0)),
            pl.BlockSpec((1, blk_b, 1), lambda ib, ic: (ic, ib, 0)),
        ] + w_specs + b_specs,
        out_specs=pl.BlockSpec((blk_b, blk_c), lambda ib, ic: (ib, ic)),
        out_shape=jax.ShapeDtypeStruct((bsz, c_pad), jnp.float32),
        interpret=interpret,
    )(x, prefix, *([w_rows] * sub_depth), *b_views)
