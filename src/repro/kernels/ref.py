"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, scale: float | None = None):
    """q: (B,H,Sq,hd); k,v: (B,H,Skv,hd). Returns (B,H,Sq,hd) in q.dtype.

    Positions are aligned at the END: q position i corresponds to absolute
    position (Skv - Sq + i) — the decode/prefill convention.
    """
    b, h, sq, hd = q.shape
    skv = k.shape[2]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(hd)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    q_pos = jnp.arange(sq) + (skv - sq)
    k_pos = jnp.arange(skv)
    delta = q_pos[:, None] - k_pos[None, :]
    valid = jnp.ones((sq, skv), bool)
    if causal:
        valid &= delta >= 0
    if window > 0:
        valid &= delta < window
    logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def tree_logprob_all_ref(w, b, x):
    """Dense per-leaf tree log-probs. w: (n_nodes,k), b: (n_nodes,),
    x: (B,k) -> (B, C_pad) over leaves in natural order."""
    n_nodes = b.shape[0]
    depth = (n_nodes + 1).bit_length() - 1
    bsz = x.shape[0]
    logp = jnp.zeros((bsz, 1), jnp.float32)
    for level in range(depth):
        lo, n_lvl = (1 << level) - 1, 1 << level
        z = x.astype(jnp.float32) @ w[lo:lo + n_lvl].T.astype(jnp.float32) \
            + b[lo:lo + n_lvl]
        children = jnp.stack([logp + jax.nn.log_sigmoid(-z),
                              logp + jax.nn.log_sigmoid(z)], axis=-1)
        logp = children.reshape(bsz, 2 * n_lvl)
    return logp


def segment_stats_ref(vals, seg, num_segments: int):
    """Segment-summed statistics: vals (N,D), seg (N,) int ->
    (num_segments, D) fp32. Out-of-range ids are dropped (padding)."""
    return jax.ops.segment_sum(vals.astype(jnp.float32), seg,
                               num_segments=num_segments)


def gather_scores_ref(w, b, h, ids):
    """Sampled-head scores: w: (C,K), b: (C,), h: (T,K), ids: (T,n) ->
    (T,n) fp32."""
    rows = w[ids]                                  # (T,n,K)
    return (jnp.einsum("tnk,tk->tn", rows.astype(jnp.float32),
                       h.astype(jnp.float32))
            + b[ids].astype(jnp.float32))


def sampled_head_loss_ref(w, b, h, ids, slot_logp, *, kind: str,
                          num_labels: int, reg: float = 0.0,
                          softcap: float = 0.0, mask_accidental: bool = True):
    """The fused sampled-loss chain, unfused: gather (materializes the
    (T, m, K) rows) → einsum → per-token loss/coefficients → second gather
    for dh. Same contract as ``sampled_loss.sampled_head_loss``:
    (loss_vec (T,), coeff (T,m), xi (T,m), dh (T,K)) fp32; slot 0 is the
    positive."""
    from repro.kernels.sampled_loss import loss_and_coeffs

    scores = gather_scores_ref(w, b, h, ids)
    acc_hit = ids == ids[:, :1]
    acc_hit = acc_hit.at[:, 0].set(False)
    loss, coeff, xi = loss_and_coeffs(
        scores, slot_logp, acc_hit, kind=kind, num_labels=num_labels,
        reg=reg, softcap=softcap, mask_accidental=mask_accidental)
    dh = jnp.einsum("tn,tnk->tk", coeff, w[ids].astype(jnp.float32))
    return loss, coeff, xi, dh


def ssd_intra_ref(cm, bm, seg, xbar):
    """The SSD's intra-chunk block as einsums: cm, bm (B,nc,Q,N), seg
    (B,nc,Q,H) the within-chunk cumulative dt·A, xbar (B,nc,Q,H,P) ->
    y_intra (B,nc,Q,H,P). XLA materializes the (Q, Q, H) decay tensor."""
    q = seg.shape[2]
    rel = seg[:, :, :, None, :] - seg[:, :, None, :, :]   # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((q, q), bool))
    # Mask before the exp: above the diagonal rel is a growing positive
    # sum, exp overflows there, and a mask applied after it turns the
    # backward pass's 0 * inf into NaN.
    l_mat = jnp.exp(jnp.where(tri[None, None, :, :, None], rel, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", cm, bm)            # (B,nc,Q,Q)
    return jnp.einsum("bcij,bcijh,bcjhp->bcihp", cb, l_mat, xbar)
