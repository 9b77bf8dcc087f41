"""Plain reference of mamba2-370m training with the adversarial head, its
weights drawn from a seed, and its FLOP count.

Nothing here imports the program. The model follows the Mamba-2 paper
(arXiv:2405.21060): per layer ``h += out_proj(gated_norm(SSD(conv(in_proj(
rmsnorm(h))))))``, a final RMSNorm, and an untied output head trained by the
adversarial sampled loss of Bamler & Mandt (ICLR 2020, Eq. 2 and Eq. 6):
one negative per token drawn from a probabilistic binary tree over the
vocabulary, conditioned on ``x_gen = stop_grad(h) @ proj``. The optimizer is
Adagrad behind a global-norm clip. Everything runs in float32 with
``Precision.HIGHEST`` matmuls; ``cast`` rounds matmul operands where a
lower-precision control is wanted.

The SSD block is the chunked "minimal" form given in the Mamba-2 paper
(``segsum`` masking before the exponential), at the configuration's chunk.
Parameter layout (the names and stacking the program's train state uses):
``embed (Vp, d)``, ``layers`` stacked over depth, ``final_norm``,
``head {w (Vp, d), b (Vp,)}``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lm_ref import (HIGHEST, exact, make_tree, normal, rmsnorm,
                              ssm_block, ssm_dims, ssm_forward_flops,
                              ssm_weights, tree_depth, tree_log_prob,
                              tree_walk)


def sizes(m: Dict[str, Any]) -> Dict[str, int]:
    mult = m.get("vocab_pad_multiple", 512)
    vocab = m["vocab_size"]
    return dict(ssm_dims(m), L=m["num_layers"], V=vocab,
                Vp=-(-vocab // mult) * mult, k=m["gen_feature_dim"],
                depth=tree_depth(vocab))


def forward_flops_per_token(m: Dict[str, Any]) -> Dict[str, float]:
    """Forward FLOPs per token of the trunk, by part (see
    ``lm_ref.ssm_forward_flops``)."""
    return {name: v * m["num_layers"]
            for name, v in ssm_forward_flops(m).items()}


def train_flops_per_token(m: Dict[str, Any], n_neg: int) -> float:
    """Training FLOPs per token: three times the trunk's forward (forward,
    and the backward's two products), the sampled head's K·(1 + n_neg)
    scores three times over (scores, dL/dh, dL/dw), and, forward only, the
    generator feature x_gen = h @ proj and one tree walk per negative. No
    recomputation and no generator fit are counted."""
    s = sizes(m)
    trunk = sum(forward_flops_per_token(m).values())
    head = 3 * 2 * s["d"] * (1 + n_neg)
    sampler = 2 * s["d"] * s["k"] + n_neg * s["depth"] * 2 * s["k"]
    return 3 * trunk + head + sampler


# ---------------------------------------------------------------------------
# Weights from a seed
# ---------------------------------------------------------------------------

def make_weights(key, m: Dict[str, Any], wcfg: Dict[str, Any]):
    """(params, tree, proj), all float32, from one key (``lm_ref`` draws
    each part); embeddings and head normal over 1/sqrt(d)."""
    s = sizes(m)
    L, d, vp = s["L"], s["d"], s["Vp"]
    keys = iter(jax.random.split(key, 12))
    params = {
        "embed": normal(next(keys), (vp, d), d),
        "layers": {
            "norm_mix": {"scale": jnp.zeros((L, d), jnp.float32)},
            "ssm": ssm_weights(keys, L, m),
        },
        "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
        "head": {"w": normal(next(keys), (vp, d), d),
                 "b": jnp.zeros((vp,), jnp.float32)},
    }
    tree = make_tree(next(keys), s["V"], s["k"], wcfg["tree_scale"])
    return params, tree, normal(next(keys), (d, s["k"]), d)


# ---------------------------------------------------------------------------
# Forward and the adversarial sampled loss
# ---------------------------------------------------------------------------

def trunk(params, tokens, m, cast):
    h = params["embed"][tokens]

    @jax.checkpoint
    def layer(h, lp):
        return h + ssm_block(lp["ssm"], rmsnorm(h, lp["norm_mix"]["scale"]),
                             m, cast), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return rmsnorm(h, params["final_norm"]["scale"])


def block_loss(params, tree, proj, tokens, labels, u, denom, m, reg, cast):
    """Summed adversarial loss of a block of rows, over ``denom``."""
    depth = sizes(m)["depth"]
    h = trunk(params, tokens, m, cast)
    x_gen = jax.lax.stop_gradient(
        jnp.einsum("bsd,dk->bsk", h, proj, precision=HIGHEST))
    neg, neg_lp = tree_walk(tree, x_gen[..., None, :], u, depth)
    neg_lp = jax.lax.stop_gradient(neg_lp)
    pos_lp = jax.lax.stop_gradient(tree_log_prob(tree, x_gen, labels, depth))
    ids = jnp.concatenate([labels[..., None], neg], -1)
    lq = jnp.concatenate([pos_lp[..., None], neg_lp], -1)
    hw = params["head"]
    scores = (jnp.einsum("bsnd,bsd->bsn", hw["w"][ids], h, precision=HIGHEST)
              + hw["b"][ids])
    pos, negs = scores[..., 0], scores[..., 1:]
    unb = scores + lq
    tok = (-jax.nn.log_sigmoid(pos) - jnp.mean(jax.nn.log_sigmoid(-negs), -1)
           + reg * (jnp.square(unb[..., 0])
                    + jnp.mean(jnp.square(unb[..., 1:]), -1)))
    return jnp.sum(tok) / denom


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(v)))
            for p, v in flat}


@partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@partial(jax.jit, static_argnames=("lr", "clip"), donate_argnums=(0, 1, 2))
def _adagrad(params, nu, grads, lr, clip):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gn + 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    nu = jax.tree.map(lambda n, g: n + g * g, nu, grads)
    params = jax.tree.map(lambda p, g, n: p - lr * g / (jnp.sqrt(n) + 1e-8),
                          params, grads, nu)
    return params, nu, leaf_norms(grads)


def train(m: Dict[str, Any], train_cfg: Dict[str, Any],
          weights: Callable[[], Any], batches: List[Dict[str, np.ndarray]],
          loop_key, cast: Callable = exact, rows=None,
          block_rows: int = 1) -> Dict[str, Any]:
    """Run ``len(batches)`` Adagrad steps from ``weights()`` and return the
    readings the harness compares: each step's loss, the per-leaf norm of
    the first (clipped) gradient, and the per-leaf norm of the parameters'
    change after the last step. Step ``s`` draws its negatives from
    uniforms of ``fold_in(loop_key, s)``, shape (B, S, n_neg, depth).
    ``rows`` restricts the loss to those rows of each batch (a planted
    fault); the gradient is accumulated over blocks of ``block_rows``
    rows so that the reference fits next to its state."""
    s = sizes(m)
    n_neg, reg = int(train_cfg["n_neg"]), float(train_cfg["reg"])
    lr, clip = float(train_cfg["lr"]), float(train_cfg["clip_norm"])
    vg = jax.jit(jax.value_and_grad(partial(block_loss, m=m, reg=reg,
                                            cast=cast)))
    params, tree, proj = weights()
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for step, batch in enumerate(batches):
        bsz, slen = batch["tokens"].shape
        use = list(range(bsz)) if rows is None else list(rows)
        denom = float(len(use) * slen)
        u = jax.random.uniform(jax.random.fold_in(loop_key, step),
                               (bsz, slen, n_neg, s["depth"]), jnp.float32)
        grads, loss = None, 0.0
        for i in range(0, len(use), block_rows):
            r = np.asarray(use[i:i + block_rows])
            lval, g = vg(params, tree, proj, jnp.asarray(batch["tokens"][r]),
                         jnp.asarray(batch["labels"][r]), u[r], denom)
            loss += float(lval)
            grads = g if grads is None else _add(grads, g)
        losses.append(loss)
        params, nu, gnorms = _adagrad(params, nu, grads, lr, clip)
        del grads
        if first is None:
            first = {k: float(v) for k, v in gnorms.items()}
    del nu
    start = weights()[0]
    change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": first,
            "changes": {k: float(v) for k, v in change.items()}}
