"""LM output head: the paper's adversarial softmax approximation wired into
the decoder, plus all baseline heads, with vocab padding + gemma2 softcap.

The generator feature x_gen (paper: PCA of the input) is a fixed linear
projection of the (stop-gradient) final hidden state — `LMHeadState.proj` —
refreshed together with the tree (DESIGN.md §2). Padded vocab rows are masked
out of full-logit paths; the samplers only ever draw real labels.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import heads as heads_lib
from repro.core import tree as tree_lib
from repro.core.heads import Generator, HeadConfig, HeadParams
from repro.models.config import ModelConfig


class LMHeadState(NamedTuple):
    """Non-trainable head state (generator + feature projection)."""
    gen: Generator
    proj: Optional[jax.Array] = None    # (d_model, k)


def default_head_state(rng, cfg: ModelConfig, kind: str) -> LMHeadState:
    """Head state before any generator fitting: random tree / uniform freq.
    Real runs refresh this via repro.train.generator_fit."""
    k1, k2 = jax.random.split(rng)
    proj = jax.random.normal(k1, (cfg.d_model, cfg.gen_feature_dim),
                             jnp.float32) / jnp.sqrt(cfg.d_model)
    gen = Generator()
    if kind in ("adversarial_ns", "nce", "sampled_softmax"):
        gen = Generator(tree=tree_lib.init_tree(
            k2, cfg.vocab_size, cfg.gen_feature_dim, scale=0.05))
    elif kind == "freq_ns":
        gen = heads_lib.make_freq_generator(
            jnp.ones((cfg.vocab_size,), jnp.float32))
    return LMHeadState(gen=gen, proj=proj)


def head_config(cfg: ModelConfig, kind: str, n_neg: int = 1,
                reg: float = 0.0) -> HeadConfig:
    return HeadConfig(num_labels=cfg.vocab_size, kind=kind, n_neg=n_neg,
                      reg=reg)


def gen_features(state: LMHeadState, h: jax.Array) -> jax.Array:
    """x_gen = stop_grad(h) @ proj — the O(d·k) generator feature."""
    h = jax.lax.stop_gradient(h).astype(jnp.float32)
    return h @ state.proj


def _softcap_score_fn(cap: float, base=heads_lib.candidate_scores):
    def fn(params: HeadParams, h, ids):
        s = base(params, h, ids)
        return cap * jnp.tanh(s / cap) if cap else s
    return fn


def serving_score_fn(cfg: ModelConfig, use_kernel: bool = False,
                     mesh=None) -> heads_lib.ScoreFn:
    """Candidate scorer for the serving paths, final softcap included.

    Selection (one place, shared by ``make_serve_step`` and the serve
    engine so the two stay byte-identical): ``mesh`` → vocab-sharded
    ``sharded_candidate_scores`` (each model shard scores only its rows,
    one psum of the tiny score tensor); ``use_kernel`` → the gather_scores
    Pallas kernel; else the plain O(beam·K) gather-and-dot.
    """
    if mesh is not None:
        from repro.parallel.collectives import sharded_candidate_scores

        def base(p: HeadParams, hh, ids):
            return sharded_candidate_scores(mesh, p.w, p.b, hh, ids)
    elif use_kernel:
        base = heads_lib.kernel_score_fn()
    else:
        base = heads_lib.candidate_scores
    if cfg.final_logit_softcap:
        return _softcap_score_fn(cfg.final_logit_softcap, base)
    return base


def masked_full_logits(cfg: ModelConfig, params: HeadParams, h: jax.Array
                       ) -> jax.Array:
    """(…, V_pad) logits with padded rows masked and final softcap applied."""
    logits = heads_lib.full_logits(params, h)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(
            logits / cfg.final_logit_softcap)
    pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(pad_mask, logits, -1e30)


def lm_head_loss(cfg: ModelConfig, hcfg: HeadConfig, params: HeadParams,
                 state: LMHeadState, h: jax.Array, labels: jax.Array,
                 rng: jax.Array, mask: Optional[jax.Array] = None,
                 score_fn=None, sampler=None):
    """Next-token loss on final hiddens h (…, d) and labels (…,).

    Dispatches to the configured head strategy; `softmax` uses the padded/
    softcapped full-logit path (the O(K·C) baseline the paper replaces).
    ``sampler`` overrides the negative-sampling proposal (a
    ``repro.core.samplers.NegativeSampler``); default derives it from
    ``hcfg.kind`` + the generator state. Runs under the ``head`` named
    scope, the generator's work under ``head/sample`` (DESIGN.md §10).
    """
    with jax.named_scope("head"):
        with jax.named_scope("sample"):
            x_gen = gen_features(state, h)
        if hcfg.kind == "softmax":
            logits = masked_full_logits(cfg, params, h)
            if mask is None:
                mask = jnp.ones(labels.shape, jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            pos = jnp.take_along_axis(
                logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            denom = jnp.maximum(mask.sum(), 1.0)
            loss = jnp.sum((logz - pos) * mask) / denom
            return loss, {"pos_score": jnp.sum(pos * mask) / denom}
        if score_fn is None:
            score_fn = (_softcap_score_fn(cfg.final_logit_softcap)
                        if cfg.final_logit_softcap
                        else heads_lib.candidate_scores)
        return heads_lib.head_loss(hcfg, params, state.gen, h, x_gen,
                                   labels, rng, score_fn=score_fn,
                                   mask=mask, sampler=sampler)


def lm_sparse_head_loss(cfg: ModelConfig, hcfg: HeadConfig,
                        params: HeadParams, state: LMHeadState,
                        h: jax.Array, labels: jax.Array, rng: jax.Array,
                        mask: Optional[jax.Array] = None,
                        use_kernel: bool = False, sampler=None):
    """Sampled-head loss with O(B·K·n_neg) analytic gradients (DESIGN.md
    §8): same loss/metrics stream as :func:`lm_head_loss` (softcap folded
    into the coefficients), plus the deduped ``SparseRows`` head gradient
    and the trunk cotangent ``dh``. Returns (loss, metrics, sparse, dh).
    Named scopes as in :func:`lm_head_loss`."""
    with jax.named_scope("head"):
        with jax.named_scope("sample"):
            x_gen = gen_features(state, h)
        return heads_lib.sparse_head_loss(
            hcfg, params, state.gen, h, x_gen, labels.astype(jnp.int32),
            rng, mask=mask, softcap=cfg.final_logit_softcap,
            use_kernel=use_kernel, sampler=sampler)


def lm_predictive_topk(cfg: ModelConfig, hcfg: HeadConfig,
                       params: HeadParams, state: LMHeadState, h: jax.Array,
                       topk: int, beam: Optional[int] = None,
                       use_kernel: bool = False,
                       score_fn: Optional[heads_lib.ScoreFn] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Top-``topk`` debiased (scores, labels) without the O(C) logits matmul.

    Adversarial head: beam search over the generator tree proposes ``beam``
    candidates, only those are scored (softcap applied per candidate, padded
    vocab rows unreachable since candidates are real labels), Eq. 5 debias
    on the candidate set. ``use_kernel`` routes candidate scoring through
    the gather_scores Pallas kernel; ``score_fn`` overrides the scorer
    entirely and is used as-is (build one with :func:`serving_score_fn`,
    which bakes in the softcap). Other heads fall back to the dense
    path + top_k.
    """
    if hcfg.kind == "adversarial_ns" and state.gen.tree is not None:
        x_gen = gen_features(state, h)
        if score_fn is None:
            score_fn = serving_score_fn(cfg, use_kernel=use_kernel)
        return heads_lib.predictive_topk(hcfg, params, state.gen, h, x_gen,
                                         topk, beam=beam, score_fn=score_fn)
    scores = lm_predictive_scores(cfg, hcfg, params, state, h)
    top, labels = jax.lax.top_k(scores, topk)
    return top, labels.astype(jnp.int32)


def lm_predictive_scores(cfg: ModelConfig, hcfg: HeadConfig,
                         params: HeadParams, state: LMHeadState,
                         h: jax.Array) -> jax.Array:
    """Full-vocab scores with Eq. 5 bias removal (adversarial head)."""
    scores = masked_full_logits(cfg, params, h)
    if not hcfg.debias:
        return scores
    if hcfg.kind == "adversarial_ns" and state.gen.tree is not None:
        x_gen = gen_features(state, h)
        log_pn = tree_lib.log_prob_all(state.gen.tree, x_gen)
        zeros = jnp.zeros(scores.shape[:-1] + (cfg.padded_vocab
                                               - cfg.vocab_size,))
        return scores + jnp.concatenate([log_pn, zeros], axis=-1)
    if hcfg.kind == "freq_ns":
        corr = jnp.zeros((cfg.padded_vocab,)).at[:cfg.vocab_size].set(
            state.gen.freq_log)
        return scores + corr
    return scores
