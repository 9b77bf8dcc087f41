"""Plain reference of hymba-1.5b serving with the adversarial head's beam,
and its weights drawn from a seed.

Nothing here imports the program. Per layer (arXiv:2411.13676, parallel
heads fused by their mean): ``x = rmsnorm(h)``; ``h += (attn(x) + ssm(x))/2``
with grouped-query attention under RoPE and a causal sliding window (every
layer windowed, the program's stated departure); then a SwiGLU MLP behind
its own RMSNorm. A final RMSNorm gives ``h``. The next token is chosen as
the adversarial head states it at serving (Bamler & Mandt, ICLR 2020,
Eq. 5): a beam descent of the generator tree on ``x_gen = h @ proj``
proposes ``beam`` labels, each scored ``w_y . h + b_y + log p_n(y | x_gen)``.
Everything runs in float32 with ``Precision.HIGHEST`` matmuls.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from chipbench.lm_ref import (HIGHEST, exact, make_tree, mm, normal,
                              rmsnorm, ssm_block, ssm_weights, tree_depth,
                              tree_log_prob)


def sizes(m: Dict[str, Any]) -> Dict[str, int]:
    mult = m.get("vocab_pad_multiple", 512)
    return {"L": m["num_layers"], "d": m["d_model"], "ff": m["d_ff"],
            "H": m["num_heads"], "KV": m["num_kv_heads"],
            "hd": m["head_dim"], "V": m["vocab_size"],
            "Vp": -(-m["vocab_size"] // mult) * mult,
            "k": m["gen_feature_dim"], "depth": tree_depth(m["vocab_size"])}


def make_weights(key, m: Dict[str, Any], wcfg: Dict[str, Any]):
    """(params, tree, proj), all float32: normal weights over
    1/sqrt(fan-in), norm gains 1, the SSM branch as ``lm_ref`` draws it."""
    s = sizes(m)
    L, d, ff, H, KV, hd, vp = (s["L"], s["d"], s["ff"], s["H"], s["KV"],
                               s["hd"], s["Vp"])
    keys = iter(jax.random.split(key, 20))
    params = {
        "embed": normal(next(keys), (vp, d), d),
        "layers": {
            "norm_mix": {"scale": jnp.zeros((L, d), jnp.float32)},
            "norm_ffn": {"scale": jnp.zeros((L, d), jnp.float32)},
            "attn": {"wq": normal(next(keys), (L, d, H, hd), d),
                     "wk": normal(next(keys), (L, d, KV, hd), d),
                     "wv": normal(next(keys), (L, d, KV, hd), d),
                     "wo": normal(next(keys), (L, H, hd, d), H * hd)},
            "ssm": ssm_weights(keys, L, m),
            "mlp": {"w_gate": normal(next(keys), (L, d, ff), d),
                    "w_up": normal(next(keys), (L, d, ff), d),
                    "w_down": normal(next(keys), (L, ff, d), ff)},
        },
        "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
        "head": {"w": normal(next(keys), (vp, d), d),
                 "b": jnp.zeros((vp,), jnp.float32)},
    }
    tree = make_tree(next(keys), s["V"], s["k"], wcfg["tree_scale"])
    return params, tree, normal(next(keys), (d, s["k"]), d)


def rope(x, theta):
    """Rotary embedding over the two halves of the head dim; x (B,S,H,hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, cast):
    s = sizes(m)
    groups = s["H"] // s["KV"]
    q = rope(mm(cast, "bsd,dhk->bshk", x, p["wq"]), m["rope_theta"])
    k = rope(mm(cast, "bsd,dhk->bshk", x, p["wk"]), m["rope_theta"])
    v = mm(cast, "bsd,dhk->bshk", x, p["wv"])
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    logits = mm(cast, "bqhk,bshk->bhqs", q / jnp.sqrt(float(s["hd"])), k)
    pos = jnp.arange(x.shape[1])
    delta = pos[:, None] - pos[None, :]
    valid = (delta >= 0) & (delta < m["window_size"])
    probs = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    out = mm(cast, "bhqs,bshk->bqhk", probs, v)
    return mm(cast, "bqhk,hkd->bqd", out, p["wo"])


def forward(params, tokens, m, cast: Callable = exact):
    """Final hiddens (B,S,d) of a whole sequence; S a multiple of the SSM
    chunk (pad at the end: every mixer is causal)."""
    def layer(h, lp):
        x = rmsnorm(h, lp["norm_mix"]["scale"])
        h = h + 0.5 * (attention(lp["attn"], x, m, cast)
                       + ssm_block(lp["ssm"], x, m, cast))
        f = rmsnorm(h, lp["norm_ffn"]["scale"])
        g = mm(cast, "bsd,df->bsf", f, lp["mlp"]["w_gate"])
        u = mm(cast, "bsd,df->bsf", f, lp["mlp"]["w_up"])
        return h + mm(cast, "bsf,fd->bsd", jax.nn.silu(g) * u,
                      lp["mlp"]["w_down"]), None

    h, _ = jax.lax.scan(layer, params["embed"][tokens], params["layers"])
    return rmsnorm(h, params["final_norm"]["scale"])


def beam(tree, x, width: int, depth: int):
    """Beam descent of the tree: the ``width`` labels of highest
    log p_n(y | x) found level by level, with those log-probabilities;
    padding leaves and dead slots come back as label -1."""
    lead = x.shape[:-1]
    nodes = jnp.zeros(lead + (width,), jnp.int32)
    logp = jnp.full(lead + (width,), -jnp.inf).at[..., 0].set(0.0)
    for _ in range(depth):
        z = jnp.sum(tree["w"][nodes] * x[..., None, :], -1) + tree["b"][nodes]
        cand = jnp.concatenate([logp + jax.nn.log_sigmoid(-z),
                                logp + jax.nn.log_sigmoid(z)], -1)
        kids = jnp.concatenate([2 * nodes + 1, 2 * nodes + 2], -1)
        logp, sel = jax.lax.top_k(cand, width)
        nodes = jnp.take_along_axis(kids, sel, -1)
    leaf = nodes - ((1 << depth) - 1)
    label = tree["leaf_to_label"][leaf]
    real = (tree["label_to_leaf"][label] == leaf) & jnp.isfinite(logp)
    return jnp.where(real, label, -1), jnp.where(real, logp, -jnp.inf)


def served_gaps(params, tree, proj, h, served, m, width: int):
    """For hiddens h (T,d) and the token served from each, the gap by which
    the served token's debiased score lies below the best of the beam.
    On a tree that is not fitted this gap separates nothing: the program's
    own cache-free path in bfloat16 reads up to 2.7 on it where
    ``dense_gaps`` reads 0.06 (PERF.md), the beam's picks moving with the
    rounding of x_gen."""
    depth = sizes(m)["depth"]
    x = jnp.einsum("td,dk->tk", h, proj, precision=HIGHEST)
    cand, logp = beam(tree, x, width, depth)
    w, b = params["head"]["w"], params["head"]["b"]

    def score(ids, lp):
        return (jnp.einsum("tnd,td->tn", w[jnp.maximum(ids, 0)], h,
                           precision=HIGHEST) + b[jnp.maximum(ids, 0)] + lp)

    best = jnp.max(jnp.where(cand >= 0, score(cand, logp), -jnp.inf), -1)
    mine = score(served[:, None], tree_log_prob(tree, x, served, depth)
                 [:, None])[:, 0]
    return jnp.maximum(best - mine, 0.0)


def dense_gaps(params, tree, proj, h, served, m):
    """As ``served_gaps`` for the dense head: the best of all labels'
    debiased scores ``w_y . h + b_y + log p_n(y | x_gen)``."""
    s = sizes(m)
    x = jnp.einsum("td,dk->tk", h, proj, precision=HIGHEST)
    labels = jnp.arange(s["V"])
    w, b = params["head"]["w"][:s["V"]], params["head"]["b"][:s["V"]]
    logp = jax.vmap(lambda xt: tree_log_prob(
        tree, jnp.broadcast_to(xt, (s["V"], xt.shape[-1])), labels,
        s["depth"]))(x)
    scores = jnp.einsum("td,cd->tc", h, w, precision=HIGHEST) + b + logp
    mine = jnp.take_along_axis(scores, served[:, None], -1)[:, 0]
    return jnp.maximum(jnp.max(scores, -1) - mine, 0.0)
