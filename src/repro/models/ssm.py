"""Mamba-2 (SSD, state-space duality) block — arXiv:2405.21060.

Training uses the chunked SSD algorithm: within a chunk the recurrence is a
masked, decay-weighted matmul (attention-shaped, MXU-friendly); across chunks
a short `lax.scan` carries the (H, N, P) state. ``ssd_sequential`` is the
step-by-step oracle used by tests; ``ssm_decode_step`` is the O(1)-per-token
serving path.

Shapes: x (B,S,H,P) heads×head_dim, dt (B,S,H), A (H,) negative,
B/C (B,S,N) (single group), D (H,). State: (B,H,N,P).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models.config import ModelConfig
from repro.models.layers import normal_init, rmsnorm


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_sequential(x, dt, a_neg, b_mat, c_mat, d_skip):
    """Step-by-step SSD reference (oracle for the chunked path).

    Returns (y, final_state). All fp32.
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    decay = jnp.exp(dt * a_neg)                       # (B,S,H)
    xbar = x * dt[..., None]                          # (B,S,H,P)

    def step(state, inp):
        dec_t, xb_t, b_t, c_t = inp
        state = state * dec_t[..., None, None] + \
            jnp.einsum("bn,bhp->bhnp", b_t, xb_t)
        y_t = jnp.einsum("bn,bhnp->bhp", c_t, state)
        return state, y_t

    init = jnp.zeros((bsz, h, n, p), jnp.float32)
    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(xbar, 1, 0),
          jnp.moveaxis(b_mat, 1, 0), jnp.moveaxis(c_mat, 1, 0))
    state, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1) + x * d_skip[None, None, :, None]
    return y, state


def ssd_chunked(x, dt, a_neg, b_mat, c_mat, d_skip, chunk: int,
                initial_state=None):
    """Chunked SSD (the Mamba-2 training algorithm). Returns (y, state).

    Arbitrary S: the tail is padded with dt = 0 steps (decay = 1, zero input
    contribution), which leaves the state invariant — exact, not approximate.
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
        y, state = ssd_chunked(x, dt, a_neg, b_mat, c_mat, d_skip, chunk,
                               initial_state)
        return y[:, :s], state
    nc, q = s // chunk, chunk

    da = (dt * a_neg).reshape(bsz, nc, q, h)          # (B,nc,Q,H)
    xbar = (x * dt[..., None]).reshape(bsz, nc, q, h, p)
    bm = b_mat.reshape(bsz, nc, q, n)
    cm = c_mat.reshape(bsz, nc, q, n)

    seg = jnp.cumsum(da, axis=2)                      # (B,nc,Q,H)
    # --- intra-chunk: masked decay-weighted "attention" ---
    y_intra = kernel_ops.ssd_intra(cm, bm, seg, xbar)

    # --- chunk states ---
    decay_to_end = jnp.exp(seg[:, :, -1:, :] - seg)   # (B,nc,Q,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bm, decay_to_end, xbar)
    chunk_decay = jnp.exp(seg[:, :, -1, :])           # (B,nc,H)

    # --- inter-chunk scan (carry state across chunks) ---
    def step(h_prev, inp):
        s_c, dec_c = inp
        h_new = h_prev * dec_c[:, :, None, None] + s_c
        return h_new, h_prev

    init = (jnp.zeros((bsz, h, n, p), jnp.float32)
            if initial_state is None else initial_state)
    xs = (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    final_state, h_prevs = jax.lax.scan(step, init, xs)
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)             # (B,nc,H,N,P)

    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", cm, jnp.exp(seg), h_prevs)
    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    y = y + x * d_skip[None, None, :, None]
    return y, final_state


# ---------------------------------------------------------------------------
# Mamba-2 block (in_proj -> conv1d -> SSD -> gated norm -> out_proj)
# ---------------------------------------------------------------------------

def init_ssm_block(rng, cfg: ModelConfig):
    d, di, n = cfg.d_model, cfg.ssm_inner, cfg.ssm_state
    h = cfg.ssm_heads
    conv_dim = di + 2 * n
    ks = jax.random.split(rng, 4)
    return {
        # in_proj -> [z (di), x (di), B (n), C (n), dt (h)]
        "w_in": normal_init(ks[0], (d, 2 * di + 2 * n + h), d),
        "conv_w": normal_init(ks[1], (cfg.ssm_conv_width, conv_dim),
                              cfg.ssm_conv_width),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),        # A = -exp(a_log)
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": {"scale": jnp.zeros((di,), jnp.float32)},
        "w_out": normal_init(ks[3], (di, d), di),
    }


def _split_in(cfg, proj):
    di, n, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * n]
    dt = proj[..., di + di + 2 * n:]
    return z, xbc, dt


def _causal_conv(xbc, conv_w, conv_b):
    """Depthwise causal conv1d, width W: y_t = sum_w w[w]*x_{t-W+1+w}."""
    w = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * conv_w[i][None, None, :]
              for i in range(w))
    return jax.nn.silu(out + conv_b)


def ssm_block(params, cfg: ModelConfig, x: jax.Array,
              cache: Tuple[jax.Array, jax.Array] = None,
              decode: bool = False):
    """Returns (out (B,S,d), new_cache=(conv_state, ssm_state))."""
    di, n, h, p = (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads,
                   cfg.ssm_head_dim)
    cdt = jnp.dtype(cfg.dtype)
    bsz, s, _ = x.shape
    proj = jnp.einsum("bsd,de->bse", x, params["w_in"].astype(cdt))
    z, xbc, dt_raw = _split_in(cfg, proj)
    xbc = xbc.astype(jnp.float32)

    if not decode:
        xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        conv_state = None  # filled by prefill wrapper below
        xs = xbc[..., :di].reshape(bsz, s, h, p)
        bm = xbc[..., di:di + n]
        cm = xbc[..., di + n:]
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        a_neg = -jnp.exp(params["a_log"])
        init_state = cache[1] if cache is not None else None
        y, state = ssd_chunked(xs, dt, a_neg, bm, cm, params["d_skip"],
                               cfg.ssm_chunk, initial_state=init_state)
        width = cfg.ssm_conv_width
        # conv state for serving: last (width-1) *pre-conv* inputs. A
        # prompt shorter than the window left-pads with zeros — exactly
        # the fresh-cache contents those positions held.
        pre = jnp.einsum("bsd,de->bse", x,
                         params["w_in"].astype(cdt))[..., di:di + di + 2 * n]
        pre = jnp.pad(pre, ((0, 0), (max(0, width - 1 - s), 0), (0, 0)))
        conv_state = pre[:, -(width - 1):, :].astype(jnp.float32)
    else:
        assert s == 1 and cache is not None
        conv_prev, ssm_state = cache
        width = cfg.ssm_conv_width
        seq = jnp.concatenate([conv_prev, xbc], axis=1)   # (B, width, conv)
        conv_out = jax.nn.silu(
            jnp.einsum("bwc,wc->bc", seq, params["conv_w"])
            + params["conv_b"])[:, None, :]
        xs = conv_out[..., :di].reshape(bsz, 1, h, p)
        bm = conv_out[..., di:di + n]
        cm = conv_out[..., di + n:]
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
        a_neg = -jnp.exp(params["a_log"])
        decay = jnp.exp(dt[:, 0] * a_neg)                 # (B,H)
        xbar = xs[:, 0] * dt[:, 0, :, None]               # (B,H,P)
        state = (ssm_state * decay[..., None, None]
                 + jnp.einsum("bn,bhp->bhnp", bm[:, 0], xbar))
        y = (jnp.einsum("bn,bhnp->bhp", cm[:, 0], state)
             + xs[:, 0] * params["d_skip"][None, :, None])[:, None]
        conv_state = seq[:, 1:, :]

    y = y.reshape(bsz, s, di)
    y = rmsnorm(params["norm"], y * jax.nn.silu(z.astype(jnp.float32)))
    out = jnp.einsum("bse,ed->bsd", y.astype(cdt),
                     params["w_out"].astype(cdt))
    return out, (conv_state, state)


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    di, n = cfg.ssm_inner, cfg.ssm_state
    conv = jnp.zeros((batch, cfg.ssm_conv_width - 1, di + 2 * n), dtype)
    state = jnp.zeros((batch, cfg.ssm_heads, n, cfg.ssm_head_dim), dtype)
    return conv, state
