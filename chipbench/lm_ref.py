"""Plain float32 pieces the configurations' references share.

Nothing here imports the program. Matmuls run at ``Precision.HIGHEST``;
``cast`` rounds their operands where a lower-precision control is wanted
(``exact`` keeps them, ``fp8`` rounds to float8 e4m3).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_LOGIT = 30.0


def exact(x):
    return x


def fp8(x):
    """Round to float8 e4m3 and back: the control's matmul operands."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def mm(cast: Callable, eq: str, *ops):
    return jnp.einsum(eq, *[cast(o) for o in ops], precision=HIGHEST)


def normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)


def rmsnorm(x, scale, eps=1e-6):
    """x / rms(x) * (1 + scale)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


# ---------------------------------------------------------------------------
# Mamba-2 SSD block
# ---------------------------------------------------------------------------

def ssm_dims(m: Dict[str, Any]) -> Dict[str, int]:
    di = m["ssm_expand"] * m["d_model"]
    return {"d": m["d_model"], "di": di, "n": m["ssm_state"],
            "h": di // m["ssm_head_dim"], "p": m["ssm_head_dim"],
            "w": m["ssm_conv_width"], "q": m["ssm_chunk"]}


def ssm_weights(keys, L: int, m: Dict[str, Any]) -> Dict[str, Any]:
    """One stacked SSM block per layer: normal projections scaled by
    1/sqrt(fan-in), Mamba-2's A in [1, 16] and dt in [1e-3, 1e-1]
    (log-uniform), gated-norm gain 1, skip D 1."""
    s = ssm_dims(m)
    d, di, n, h, w = s["d"], s["di"], s["n"], s["h"], s["w"]
    a = jax.random.uniform(next(keys), (L, h), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(keys), (L, h), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "w_in": normal(next(keys), (L, d, 2 * di + 2 * n + h), d),
        "conv_w": normal(next(keys), (L, w, di + 2 * n), w),
        "conv_b": jnp.zeros((L, di + 2 * n), jnp.float32),
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((L, h), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "norm": {"scale": jnp.zeros((L, di), jnp.float32)},
        "w_out": normal(next(keys), (L, di, d), di),
    }


def ssm_forward_flops(m: Dict[str, Any]) -> Dict[str, float]:
    """Forward FLOPs per token of one SSM block (2 per multiply-add). The
    intra-chunk products cover the causal triangle of their chunk,
    (Q + 1) / 2 positions on average; the chunk states and their read-out
    cost N·H·P each. Elementwise work is not counted."""
    s = ssm_dims(m)
    d, di, n, h, p, w, q = (s["d"], s["di"], s["n"], s["h"], s["p"],
                            s["w"], s["q"])
    return {"in_proj": 2 * d * (2 * di + 2 * n + h),
            "conv": 2 * w * (di + 2 * n),
            "ssd_cb": 2 * n * (q + 1) / 2,
            "ssd_intra": 2 * h * p * (q + 1) / 2,
            "ssd_states": 2 * n * h * p,
            "ssd_readout": 2 * n * h * p,
            "out_proj": 2 * di * d}


def segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] on and below the
    diagonal, -inf above it (masked before any exponential)."""
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (t,))
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, a, b, c, chunk, cast):
    """Mamba-2 SSD in the paper's chunked minimal form. x (B,S,H,P) already
    times dt, a (B,S,H) = dt·A, b/c (B,S,N); S a multiple of ``chunk``.
    Returns y (B,S,H,P) from a zero initial state."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc = s // chunk
    x = x.reshape(bsz, nc, chunk, h, p)
    b = b.reshape(bsz, nc, chunk, n)
    c = c.reshape(bsz, nc, chunk, n)
    a = jnp.moveaxis(a.reshape(bsz, nc, chunk, h), -1, 1)   # (B,H,nc,Q)
    a_cum = jnp.cumsum(a, -1)
    lmat = jnp.exp(segsum(a))                                # (B,H,nc,Q,Q)
    cb = mm(cast, "bcln,bcsn->bcls", c, b)
    y_diag = mm(cast, "bcls,bhcls,bcshp->bclhp", cb, lmat, x)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                 # (B,H,nc,Q)
    states = mm(cast, "bcln,bhcl,bclhp->bchpn", b, decay, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cum[..., -1],
                                         ((0, 0), (0, 0), (1, 0)))))
    states = mm(cast, "bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = mm(cast, "bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cum))
    return (y_diag + y_off).reshape(bsz, s, h, p)


def ssm_block(lp, x, m, cast):
    """in_proj -> causal depthwise conv + SiLU -> SSD (+ D skip) -> norm
    gated by SiLU(z) -> out_proj. x (B,S,d), S a multiple of the chunk."""
    s = ssm_dims(m)
    di, n, h, p, w = s["di"], s["n"], s["h"], s["p"], s["w"]
    bsz, slen, _ = x.shape
    proj = mm(cast, "bsd,de->bse", x, lp["w_in"])
    z, xbc, dt_raw = (proj[..., :di], proj[..., di:2 * di + 2 * n],
                      proj[..., 2 * di + 2 * n:])
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + slen] * lp["conv_w"][i] for i in range(w))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    xs = xbc[..., :di].reshape(bsz, slen, h, p)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt_raw + lp["dt_bias"])
    a = -jnp.exp(lp["a_log"])
    y = ssd(xs * dt[..., None], dt * a, bm, cm, m["ssm_chunk"], cast)
    y = y + xs * lp["d_skip"][:, None]
    y = rmsnorm(y.reshape(bsz, slen, di) * jax.nn.silu(z),
                lp["norm"]["scale"])
    return mm(cast, "bse,ed->bsd", y, lp["w_out"])


# ---------------------------------------------------------------------------
# The generator tree (Bamler & Mandt, ICLR 2020, section 3)
# ---------------------------------------------------------------------------

def tree_depth(num_labels: int) -> int:
    return max(2, 1 << (num_labels - 1).bit_length()).bit_length() - 1


def _force_left(num_labels: int, c_pad: int) -> np.ndarray:
    """Nodes whose right subtree holds padding leaves only (labels sit at
    leaves 0..C-1): their decision is pinned left."""
    depth = c_pad.bit_length() - 1
    force = np.zeros((c_pad - 1,), bool)
    for level in range(depth):
        per_child = c_pad >> (level + 1)
        for j in range(1 << level):
            if j * 2 * per_child + per_child >= num_labels:
                force[(1 << level) - 1 + j] = True
    return force


def make_tree(key, num_labels: int, k: int, scale: float) -> Dict[str, Any]:
    """A balanced tree before any fit: random node weights of ``scale``,
    labels in natural leaf order, padding subtrees pinned off."""
    c_pad = 1 << tree_depth(num_labels)
    return {
        "w": scale * jax.random.normal(key, (c_pad - 1, k), jnp.float32),
        "b": jnp.where(jnp.asarray(_force_left(num_labels, c_pad)),
                       -PAD_LOGIT, 0.0).astype(jnp.float32),
        "label_to_leaf": jnp.arange(num_labels, dtype=jnp.int32),
        "leaf_to_label": jnp.where(jnp.arange(c_pad) < num_labels,
                                   jnp.arange(c_pad), 0).astype(jnp.int32),
    }


def tree_walk(tree, x, u, depth):
    """Ancestral draw down the tree with uniforms u (..., depth): label and
    its log-probability."""
    idx = jnp.zeros(x.shape[:-1], jnp.int32)
    lp = jnp.zeros(x.shape[:-1], jnp.float32)
    for level in range(depth):
        z = jnp.sum(tree["w"][idx] * x, -1) + tree["b"][idx]
        right = u[..., level] < jax.nn.sigmoid(z)
        lp = lp + jnp.where(right, jax.nn.log_sigmoid(z),
                            jax.nn.log_sigmoid(-z))
        idx = 2 * idx + 1 + right.astype(jnp.int32)
    leaf = idx - ((1 << depth) - 1)
    return tree["leaf_to_label"][leaf], lp


def tree_log_prob(tree, x, y, depth):
    """log p(y | x): the sum of the decisions on the path to y's leaf."""
    leaf = tree["label_to_leaf"][y]
    lp = jnp.zeros(y.shape, jnp.float32)
    for level in range(depth):
        node = (1 << level) - 1 + (leaf >> (depth - level))
        bit = (leaf >> (depth - 1 - level)) & 1
        z = jnp.sum(tree["w"][node] * x, -1) + tree["b"][node]
        lp = lp + jax.nn.log_sigmoid(jnp.where(bit == 1, z, -z))
    return lp
