"""Fused sampled-head loss Pallas TPU kernel: forward + backward in one pass.

The sampled-head train step's hot chain is gather → einsum → loss →
scatter: XLA materializes the gathered (T, m, K) rows in HBM, the loss is a
handful of elementwise ops, and the backward pass re-gathers the same rows
to form ``dh`` and scatter the head gradient. This kernel streams each
touched row HBM→VMEM exactly once per step and computes *everything* that
depends on it in that pass:

  * the gather·dot candidate scores  xi = w[ids]·h + b[ids],
  * the per-token loss terms (logistic for the NS/NCE family, logQ-corrected
    logsumexp for sampled softmax, the OVE / A&R bounds),
  * the scatter coefficients  coeff = dL/d(raw score)  — for every sampled
    strategy the per-row head gradient is ``coeff · h`` (see
    :func:`loss_and_coeffs`), so coeff IS the backward pass,
  * the trunk cotangent  dh = coeff @ w[ids]  from the VMEM-resident rows.

The loss/coefficient math (:func:`loss_and_coeffs`) is plain jnp shared
verbatim between the kernel body and the pure-jnp oracle
(``repro.kernels.ref.sampled_head_loss_ref``) — the only thing the kernel
adds is the single-streaming row pipeline. Masking and the per-unique-row
deduplication live outside (``repro.optim.sparse.accumulate_rows``): they
are O(T) / O(T·m) and independent of K and C.

Grid: (T / blk_t, blk_t·m / 8); ids arrive via scalar prefetch (SMEM). The
inner axis gathers the block's blk_t·m rows into a slot-major fp32 VMEM
scratch (m, blk_t, K) the way :mod:`repro.kernels.gather_scores` does; its
last step runs the block math on the VPU: one row·h reduction per slot, the
(blk_t, m) loss tiles, and dh as a coefficient-weighted sum of the m row
tiles. The biases are one scalar per row and are gathered in XLA.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_scores import (GROUP, pad_tokens, row_tile_specs,
                                         stash_rows, token_block)

# Head kinds with a sampled candidate set (everything except `softmax`).
SAMPLED_KINDS = ("uniform_ns", "freq_ns", "adversarial_ns", "nce",
                 "sampled_softmax", "ove", "augment_reduce")
_NS_FAMILY = ("uniform_ns", "freq_ns", "adversarial_ns")


def loss_and_coeffs(scores, slot_logp, acc_hit, *, kind: str,
                    num_labels: int, reg: float = 0.0,
                    softcap: float = 0.0, mask_accidental: bool = True
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-token sampled loss + analytic score gradients, every strategy.

    scores: (T, m) RAW candidate scores, slot 0 the positive, slots 1..m-1
    the negatives. slot_logp: (T, m) stop-grad noise log-probs (zeros where
    a strategy ignores them). acc_hit: (T, m) bool, True where a negative
    slot equals the positive id (slot 0 always False).

    Returns (loss_vec (T,), coeff (T, m), xi (T, m)) with
    ``coeff[t, j] = d loss_vec[t] / d scores[t, j]`` — the per-row head
    gradient is then ``coeff · h`` (w) and ``coeff`` (b), which is what
    makes the sparse path O(B·K·n_neg): no autodiff ever touches the
    (C, K) gather. ``xi`` are the softcapped scores (for metrics).

    The closed forms are the exact derivatives of the per-strategy
    objectives in ``repro.core.heads.head_loss`` (pinned by
    tests/test_sparse_update.py against jax.vjp over this function's own
    loss output).
    """
    scores = scores.astype(jnp.float32)
    n = scores.shape[-1] - 1
    if softcap:
        xi = softcap * jnp.tanh(scores / softcap)
        chain = 1.0 - jnp.square(xi / softcap)        # d xi / d score
    else:
        xi = scores
        chain = jnp.ones_like(scores)
    pos, neg = xi[..., 0], xi[..., 1:]

    if kind in _NS_FAMILY:
        # Eq. 2 logistic loss (+ Eq. 6 unbiased-score regularizer).
        loss = (-jax.nn.log_sigmoid(pos)
                - jnp.mean(jax.nn.log_sigmoid(-neg), axis=-1))
        g_pos = -jax.nn.sigmoid(-pos)
        g_neg = jax.nn.sigmoid(neg) / n
        if reg:
            unb = xi + slot_logp
            loss = loss + reg * (jnp.square(unb[..., 0])
                                 + jnp.mean(jnp.square(unb[..., 1:]), -1))
            g_pos = g_pos + 2.0 * reg * unb[..., 0]
            g_neg = g_neg + (2.0 * reg / n) * unb[..., 1:]
        g = jnp.concatenate([g_pos[..., None], g_neg], axis=-1)
    elif kind == "nce":
        ln_nu = jnp.log(float(n))
        u = xi - slot_logp - ln_nu
        loss = (-jax.nn.log_sigmoid(u[..., 0])
                - jnp.sum(jax.nn.log_sigmoid(-u[..., 1:]), axis=-1))
        g = jnp.concatenate([-jax.nn.sigmoid(-u[..., :1]),
                             jax.nn.sigmoid(u[..., 1:])], axis=-1)
    elif kind == "sampled_softmax":
        cand = xi - slot_logp
        if mask_accidental:
            cand = jnp.where(acc_hit, -jnp.inf, cand)
        loss = jax.nn.logsumexp(cand, axis=-1) - cand[..., 0]
        p = jax.nn.softmax(cand, axis=-1)
        g = jnp.concatenate([p[..., :1] - 1.0, p[..., 1:]], axis=-1)
    elif kind == "ove":
        ind = (~acc_hit[..., 1:]).astype(jnp.float32)
        scl = (num_labels - 1) / n
        diff = neg - pos[..., None]
        loss = scl * jnp.mean(jax.nn.softplus(diff) * ind, axis=-1)
        g_neg = (scl / n) * jax.nn.sigmoid(diff) * ind
        g = jnp.concatenate([-jnp.sum(g_neg, -1, keepdims=True), g_neg], -1)
    elif kind == "augment_reduce":
        ln_rest = (jax.nn.logsumexp(neg, axis=-1)
                   + jnp.log((num_labels - 1) / n))
        loss = jnp.logaddexp(pos, ln_rest) - pos
        a = jax.nn.sigmoid(ln_rest - pos)             # rest-mass weight
        g_neg = a[..., None] * jax.nn.softmax(neg, axis=-1)
        g = jnp.concatenate([-a[..., None], g_neg], axis=-1)
    else:
        raise ValueError(f"{kind} has no sampled candidate loss")
    return loss, g * chain, xi


def _kernel(ids_ref, *refs, blk_t: int, m: int, kind: str, num_labels: int,
            reg: float, softcap: float, mask_accidental: bool):
    tile_refs = refs[:GROUP]
    (bias_ref, h_ref, lp_ref, hit_ref, loss_ref, coeff_ref, xi_ref, dh_ref,
     rows_ref) = refs[GROUP:]
    it, j = pl.program_id(0), pl.program_id(1)
    # Each touched row comes HBM→VMEM once; everything downstream reads
    # the VMEM-resident copy — the row never round-trips.
    stash_rows(ids_ref, tile_refs, rows_ref, it * blk_t * m)

    @pl.when(j == pl.num_programs(1) - 1)
    def _block():
        h = h_ref[...].astype(jnp.float32)             # (blk_t, K)
        scores = jnp.concatenate(
            [jnp.sum(rows_ref[c] * h, axis=-1, keepdims=True)
             for c in range(m)], axis=-1) + bias_ref[...]
        loss, coeff, xi = loss_and_coeffs(
            scores, lp_ref[...], hit_ref[...] != 0, kind=kind,
            num_labels=num_labels, reg=reg, softcap=softcap,
            mask_accidental=mask_accidental)
        loss_ref[...] = loss[:, None]
        coeff_ref[...] = coeff
        xi_ref[...] = xi
        dh = coeff[:, 0:1] * rows_ref[0]
        for c in range(1, m):
            dh = dh + coeff[:, c:c + 1] * rows_ref[c]
        dh_ref[...] = dh


def sampled_head_loss(w, b, h, ids, slot_logp, *, kind: str,
                      num_labels: int, reg: float = 0.0,
                      softcap: float = 0.0, mask_accidental: bool = True,
                      blk_t: int = 128, interpret: bool = False):
    """w: (C,K), b: (C,), h: (T,K), ids/slot_logp: (T,m) — slot 0 positive.

    Returns (loss_vec (T,), coeff (T,m), xi (T,m), dh (T,K)), all fp32.
    """
    t, k = h.shape
    m = ids.shape[-1]
    blk_t = token_block(t, m, blk_t)
    ids = ids.astype(jnp.int32)
    h, ids, slot_logp = pad_tokens(blk_t, h, ids,
                                   slot_logp.astype(jnp.float32))
    t_pad = h.shape[0]
    acc_hit = (ids == ids[:, :1]).astype(jnp.int32)
    acc_hit = acc_hit.at[:, 0].set(0)

    kernel = functools.partial(
        _kernel, blk_t=blk_t, m=m, kind=kind, num_labels=num_labels,
        reg=reg, softcap=softcap, mask_accidental=mask_accidental)
    tok = pl.BlockSpec((blk_t, m), lambda it, j, ids: (it, 0))
    wide = pl.BlockSpec((blk_t, k), lambda it, j, ids: (it, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t_pad // blk_t, blk_t * m // GROUP),
        in_specs=row_tile_specs(w, blk_t * m) + [tok, wide, tok, tok],
        out_specs=[pl.BlockSpec((blk_t, 1), lambda it, j, ids: (it, 0)),
                   tok, tok, wide],
        scratch_shapes=[pltpu.VMEM((m, blk_t, k), jnp.float32)],
    )
    loss, coeff, xi, dh = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, m), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, m), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids.reshape(-1), *([w] * GROUP), b[ids].astype(jnp.float32), h,
      slot_logp, acc_hit)
    return loss[:t, 0], coeff[:t], xi[:t], dh[:t]
