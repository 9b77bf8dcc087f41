"""Softmax-head strategies: the paper's adversarial negative sampling and all
baselines from §5 / appendix A.2, behind one interface.

Heads score ``C`` labels from a feature ``h in R^K`` with an affine model
``xi_y(h) = w_y . h + b_y`` (the paper's model; for LMs, ``h`` is the final
hidden state and ``(w, b)`` the output embedding). The *generator feature*
``x_gen in R^k`` fed to the auxiliary tree is passed separately (paper: a PCA
projection of the input; LM: a projection of a frozen feature snapshot —
DESIGN.md §2).

Strategies (paper reference):
  softmax         — full softmax CE, Eq. 1 (appendix A.2 baseline)
  uniform_ns      — negative sampling, uniform noise, Eq. 2   (baseline i)
  freq_ns         — unconditional empirical-frequency noise   (baseline ii)
  adversarial_ns  — **the paper**: conditional tree noise, Eq. 6 objective,
                    Eq. 5 bias removal at prediction
  nce             — NCE with the tree as base distribution    (baseline iii)
  sampled_softmax — Bengio & Senecal sampled softmax w/ logQ correction
  ove             — One-vs-Each (Titsias 2016), stochastic    (baseline v)
  augment_reduce  — A&R softmax bound, stochastic reduce step (baseline iv)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import samplers as samplers_lib
from repro.core import tree as tree_lib
from repro.core.samplers import NegativeSampler
from repro.kernels.sampled_loss import SAMPLED_KINDS, loss_and_coeffs
from repro.optim.sparse import SparseRows, accumulate_rows

HEAD_KINDS = ("softmax",) + SAMPLED_KINDS


@dataclasses.dataclass(frozen=True)
class HeadConfig:
    num_labels: int
    kind: str = "adversarial_ns"
    n_neg: int = 1          # negatives per positive (paper uses 1)
    reg: float = 0.0        # lambda in Eq. 6
    debias: bool = True     # apply Eq. 5 at prediction time
    mask_accidental: bool = True  # sampled_softmax: mask negatives == target

    def __post_init__(self):
        assert self.kind in HEAD_KINDS, self.kind


class HeadParams(NamedTuple):
    """Trainable head parameters phi (Eq. 2)."""
    w: jax.Array   # (C, K)
    b: jax.Array   # (C,)


class Generator(NamedTuple):
    """Non-trainable noise-distribution state (kept out of the optimizer;
    the paper keeps the generator constant while training the
    discriminator)."""
    tree: Optional[tree_lib.Tree] = None
    freq_log: Optional[jax.Array] = None   # (C,) log empirical frequencies
    freq_cdf: Optional[jax.Array] = None   # (C,) inclusive CDF


def init_head_params(rng: jax.Array, num_labels: int, feature_dim: int,
                     scale: float = 0.0,
                     dtype=jnp.float32) -> HeadParams:
    w = (scale * jax.random.normal(rng, (num_labels, feature_dim))
         ).astype(dtype)
    return HeadParams(w=w, b=jnp.zeros((num_labels,), dtype))


def make_freq_generator(label_counts: jax.Array) -> Generator:
    """Generator for `freq_ns`: empirical label frequencies (§2.2).

    ``freq_log`` carries 1e-12 smoothing (debiasing an observed label must
    stay finite even at count 0); ``freq_cdf`` is built from the raw
    counts so zero-count labels own an empty sampling interval — see
    :func:`repro.core.samplers.unigram_from_counts`, the single
    definition both paths share.
    """
    s = samplers_lib.unigram_from_counts(label_counts)
    return Generator(freq_log=s.freq_log, freq_cdf=s.freq_cdf)


def make_tree_generator(tree: tree_lib.Tree) -> Generator:
    return Generator(tree=tree)


# ---------------------------------------------------------------------------
# Negative sampling + noise log-probs, per strategy.
# ---------------------------------------------------------------------------

def sample_negatives(cfg: HeadConfig, gen: Generator, x_gen: jax.Array,
                     rng: jax.Array, batch_shape: Tuple[int, ...],
                     sampler: Optional[NegativeSampler] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Draw (ids, log_pn) with shapes batch_shape + (n_neg,).

    The proposal is a :class:`~repro.core.samplers.NegativeSampler`;
    with ``sampler=None`` (the compat shim) ``cfg.kind`` picks the
    proposal it historically hard-wired: uniform O(1) for
    uniform_ns/ove/augment_reduce, the unigram inverse-CDF O(log C) for
    freq_ns, tree ancestral sampling O(k log C) (paper §3) for
    adversarial_ns/nce/sampled_softmax.
    """
    if sampler is None:
        sampler = samplers_lib.sampler_from_config(cfg, gen)
    return sampler.sample(rng, x_gen, batch_shape + (cfg.n_neg,))


def noise_log_prob(cfg: HeadConfig, gen: Generator, x_gen: jax.Array,
                   y: jax.Array,
                   sampler: Optional[NegativeSampler] = None) -> jax.Array:
    """log p_n(y|x) for given labels under the proposal distribution."""
    if sampler is None:
        sampler = samplers_lib.sampler_from_config(cfg, gen)
    return sampler.log_prob(x_gen, y)


def candidate_scores(params: HeadParams, h: jax.Array, ids: jax.Array
                     ) -> jax.Array:
    """xi_{ids}(h) = w_{ids} . h + b_{ids}; ids: h.shape[:-1] + (n,).

    This is the O(K) gather-and-dot that replaces the O(K·C) logits matmul.
    The vocab-sharded fast path lives in repro.parallel.collectives.
    """
    w = params.w[ids]                                    # (..., n, K)
    return (jnp.einsum("...nk,...k->...n", w.astype(jnp.float32),
                       h.astype(jnp.float32))
            + params.b[ids].astype(jnp.float32))


def full_logits(params: HeadParams, h: jax.Array) -> jax.Array:
    """All-label scores, O(K·C): h @ W^T + b."""
    return (jnp.einsum("...k,ck->...c", h.astype(jnp.float32),
                       params.w.astype(jnp.float32))
            + params.b.astype(jnp.float32))


ScoreFn = Callable[[HeadParams, jax.Array, jax.Array], jax.Array]


def kernel_score_fn() -> ScoreFn:
    """Candidate scoring through the `gather_scores` Pallas kernel.

    Same contract as :func:`candidate_scores` (arbitrary batch dims) — the
    kernel wants flat (T, K)/(T, n) operands, so batch dims are collapsed
    around the call. On TPU each touched row streams HBM→VMEM exactly once;
    on the CPU backend the kernel runs in interpret mode (see
    repro.kernels.ops).
    """
    from repro.kernels import ops

    def fn(params: HeadParams, h: jax.Array, ids: jax.Array) -> jax.Array:
        batch_shape = ids.shape[:-1]
        n = ids.shape[-1]
        flat = ops.gather_scores(params.w, params.b,
                                 h.reshape((-1, h.shape[-1])),
                                 ids.reshape((-1, n)))
        return flat.reshape(batch_shape + (n,))

    return fn


# ---------------------------------------------------------------------------
# Losses.
# ---------------------------------------------------------------------------

def head_loss(cfg: HeadConfig, params: HeadParams, gen: Generator,
              h: jax.Array, x_gen: jax.Array, y: jax.Array, rng: jax.Array,
              score_fn: ScoreFn = candidate_scores,
              mask: Optional[jax.Array] = None,
              sampler: Optional[NegativeSampler] = None):
    """Per-strategy training loss, mean over batch. Returns (loss, metrics).

    h: (..., K); x_gen: (..., k); y: (...,) int labels; mask: (...,) in
    {0,1} — masked-out positions (e.g. padding tokens) contribute 0.
    ``sampler`` overrides the proposal distribution (default: the one
    ``cfg.kind`` implies — see :func:`sample_negatives`).
    """
    batch_shape = y.shape
    if mask is None:
        mask = jnp.ones(batch_shape, jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)

    def mean(v):
        return jnp.sum(v * mask) / denom

    metrics = {}
    if cfg.kind == "softmax":
        logits = full_logits(params, h)
        logz = jax.nn.logsumexp(logits, axis=-1)
        pos = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        loss = mean(logz - pos)
        if cfg.reg:  # score regularizer (cf. Eq. 6 with log p_n absorbed)
            loss = loss + cfg.reg * mean(jnp.mean(logits ** 2, axis=-1))
        metrics["pos_score"] = mean(pos)
        return loss, metrics

    # Sampled strategies share one candidate layout (slot 0 = positive) and
    # one objective definition: `kernels.sampled_loss.loss_and_coeffs` is
    # the per-strategy math (Eq. 2/6 logistic, NCE, logQ sampled softmax,
    # OVE, A&R), used here under autodiff for the dense gradient path and
    # re-used coefficient-for-coefficient by the sparse path
    # (:func:`sparse_head_loss`) and the fused Pallas kernel.
    y = y.astype(jnp.int32)
    ids, slot_logp, acc_hit = _sample_candidates(cfg, gen, x_gen, y, rng,
                                                 sampler=sampler)
    scores = score_fn(params, h, ids)                  # (..., 1 + n_neg)
    loss_vec, _, xi = loss_and_coeffs(
        scores, slot_logp, acc_hit, kind=cfg.kind,
        num_labels=cfg.num_labels, reg=cfg.reg, softcap=0.0,
        mask_accidental=cfg.mask_accidental)
    loss = mean(loss_vec)
    metrics.update(_sampled_metrics(cfg, xi, mean))
    return loss, metrics


def _sample_candidates(cfg: HeadConfig, gen: Generator, x_gen: jax.Array,
                       y: jax.Array, rng: jax.Array,
                       sampler: Optional[NegativeSampler] = None):
    """Candidate slots for a sampled strategy: ids (..., 1+n) with the
    positive in slot 0, stop-grad noise log-probs per slot (zeros where the
    strategy ignores them), and the accidental-hit mask. Everything that
    reads the generator runs under the ``sample`` named scope
    (DESIGN.md §10); the dense, sparse and kernel paths all come here."""
    with jax.named_scope("sample"):
        neg_ids, neg_logp = sample_negatives(cfg, gen, x_gen, rng, y.shape,
                                             sampler=sampler)
        neg_ids = jax.lax.stop_gradient(neg_ids)
        neg_logp = jax.lax.stop_gradient(neg_logp)
        need_pos_logp = (cfg.kind in ("nce", "sampled_softmax")
                         or (cfg.reg and cfg.kind in (
                             "uniform_ns", "freq_ns", "adversarial_ns")))
        pos_logp = (jax.lax.stop_gradient(
            noise_log_prob(cfg, gen, x_gen, y, sampler=sampler))
                    if need_pos_logp else jnp.zeros(y.shape, jnp.float32))
    ids = jnp.concatenate([y[..., None], neg_ids], axis=-1)
    slot_logp = jnp.concatenate([pos_logp[..., None], neg_logp], axis=-1)
    acc_hit = jnp.concatenate(
        [jnp.zeros(y.shape + (1,), bool), neg_ids == y[..., None]], axis=-1)
    return ids, slot_logp, acc_hit


def resolve_head_update(head_update: str, kind: str) -> str:
    """'auto' → sparse for sampled heads, dense for the softmax baseline.

    The single definition of the head-update policy — shared by the LM
    train step (repro.train.step) and the linear-XC trainer
    (repro.core.xc_train) so the two stacks cannot drift.
    """
    if head_update == "auto":
        return "dense" if kind == "softmax" else "sparse"
    assert head_update in ("dense", "sparse"), head_update
    if head_update == "sparse":
        assert kind != "softmax", "softmax has no sampled candidate set"
    return head_update


def _sampled_metrics(cfg: HeadConfig, xi: jax.Array, mean) -> dict:
    metrics = {"pos_score": mean(xi[..., 0])}
    if cfg.kind in ("uniform_ns", "freq_ns", "adversarial_ns", "nce"):
        metrics["neg_score"] = mean(jnp.mean(xi[..., 1:], axis=-1))
    # Online proxy of the Eq. A8/15 signal mass Σ_y α(x,y): at the
    # nonparametric optimum E_{y~p_D}[σ(-ξ)] and E_{y~p_n}[σ(ξ)] both
    # equal Σα (Eq. 13), which attains its Jensen bound 1/2 exactly when
    # p_n = p_D (Theorem 2) and decays as the proposal drifts off the data
    # distribution. Averaging the two one-sample estimates reuses the ξ
    # the sampled loss already computed — a refresh-trigger-grade signal,
    # not an η estimator (DESIGN.md §9).
    metrics["snr_proxy"] = 0.5 * (
        mean(jax.nn.sigmoid(-xi[..., 0]))
        + mean(jnp.mean(jax.nn.sigmoid(xi[..., 1:]), axis=-1)))
    return metrics


def sparse_head_loss(cfg: HeadConfig, params: HeadParams, gen: Generator,
                     h: jax.Array, x_gen: jax.Array, y: jax.Array,
                     rng: jax.Array, mask: Optional[jax.Array] = None,
                     softcap: float = 0.0, use_kernel: bool = False,
                     sampler: Optional[NegativeSampler] = None):
    """Sampled-head loss with O(B·K·n_neg) analytic gradients — no dense
    (C, K) buffer anywhere (DESIGN.md §8).

    Same sampling/rng stream, objective, and metrics as :func:`head_loss`
    (the equivalence is pinned by tests/test_sparse_update.py), but instead
    of relying on autodiff — whose backward through the candidate gather
    scatter-adds into a zero-initialized dense (C, K) array — the per-slot
    score gradients ``coeff`` come from the shared closed forms
    (`kernels.sampled_loss.loss_and_coeffs`), and the head gradient is
    assembled as deduplicated per-unique-row sums of the rank-1 terms
    ``coeff · h`` (``repro.optim.sparse.accumulate_rows``).

    Returns ``(loss, metrics, SparseRows, dh)``: loss/metrics as
    :func:`head_loss`; ``SparseRows`` the head gradient over touched rows
    (drop-in leaf for ``optim.apply_updates``); ``dh`` = dL/dh for the
    trunk backward. ``softcap`` applies the final-logit softcap (its chain
    rule is folded into ``coeff``, so the gradients stay exact);
    ``use_kernel`` routes the gather→loss→coefficient chain through the
    fused Pallas kernel (``ops.sampled_head_loss``).
    """
    assert cfg.kind != "softmax", "softmax has no sampled candidate set"
    batch_shape = y.shape
    if mask is None:
        mask = jnp.ones(batch_shape, jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)

    y = y.astype(jnp.int32)
    ids, slot_logp, acc_hit = _sample_candidates(cfg, gen, x_gen, y, rng,
                                                 sampler=sampler)
    m = ids.shape[-1]
    kdim = h.shape[-1]
    h2 = h.reshape(-1, kdim)
    ids2 = ids.reshape(-1, m)

    if use_kernel:
        from repro.kernels import ops
        loss_vec, coeff, xi, dh2 = ops.sampled_head_loss(
            params.w, params.b, h2, ids2, slot_logp.reshape(-1, m),
            kind=cfg.kind, num_labels=cfg.num_labels, reg=cfg.reg,
            softcap=softcap, mask_accidental=cfg.mask_accidental)
    else:
        scores = candidate_scores(params, h2, ids2)
        loss_vec, coeff, xi = loss_and_coeffs(
            scores, slot_logp.reshape(-1, m), acc_hit.reshape(-1, m),
            kind=cfg.kind, num_labels=cfg.num_labels, reg=cfg.reg,
            softcap=softcap, mask_accidental=cfg.mask_accidental)
        dh2 = jnp.einsum("tn,tnk->tk", coeff,
                         params.w[ids2].astype(jnp.float32))

    wmask = (mask.reshape(-1) / denom).astype(jnp.float32)
    loss = jnp.sum(loss_vec * wmask)
    coeff = coeff * wmask[:, None]

    def mean(v):
        return jnp.sum(v.reshape(batch_shape) * mask) / denom

    metrics = _sampled_metrics(cfg, xi.reshape(batch_shape + (m,)), mean)
    grads = accumulate_rows(
        ids2.reshape(-1), coeff.reshape(-1),
        jnp.broadcast_to(h2[:, None, :], h2.shape[:1] + (m, kdim)
                         ).reshape(-1, kdim),
        num_rows=params.w.shape[0])
    dh = (dh2 * wmask[:, None]).reshape(h.shape).astype(jnp.float32)
    return loss, metrics, grads, dh


# ---------------------------------------------------------------------------
# Prediction (bias removal, Eq. 5).
# ---------------------------------------------------------------------------

def predictive_scores(cfg: HeadConfig, params: HeadParams, gen: Generator,
                      h: jax.Array, x_gen: jax.Array,
                      sampler: Optional[NegativeSampler] = None
                      ) -> jax.Array:
    """Unbiased predictive scores over all C labels.

    For `adversarial_ns` this is Theorem 1 / Eq. 5:
        xi_softmax = xi_ns + log p_n(y|x) + const,
    with log p_n evaluated densely for all labels in O(C·k) via the
    level-recursive tree pass. For `freq_ns` the correction is the constant-
    per-label log-frequency. Uniform corrections are argmax-irrelevant.
    A head trained against an explicit ``sampler`` is debiased by *that*
    proposal's ``log_prob_all`` — Eq. 5 holds for any proposal with full
    support, which every NegativeSampler guarantees.
    """
    scores = full_logits(params, h)
    if not cfg.debias:
        return scores
    if sampler is not None:
        return scores + sampler.log_prob_all(x_gen)
    if cfg.kind == "adversarial_ns" and gen.tree is not None:
        return scores + tree_lib.log_prob_all(gen.tree, x_gen)
    if cfg.kind == "freq_ns":
        return scores + gen.freq_log
    return scores


def rescore_candidates(cfg: HeadConfig, params: HeadParams, h: jax.Array,
                       cand: jax.Array, log_pn: jax.Array, topk: int,
                       score_fn: ScoreFn = candidate_scores
                       ) -> Tuple[jax.Array, jax.Array]:
    """Score + Eq. 5 debias a proposed candidate set, keep the top ``topk``.

    The re-scoring tail shared by :func:`predictive_topk` and the serving
    engine's candidate-cache path (repro.serve.engine) — one implementation
    so the two stay byte-identical. ``cand`` entries < 0 are dead slots and
    come back as label -1 with score -inf.
    """
    valid = cand >= 0
    xi = score_fn(params, h, jnp.maximum(cand, 0))
    scores = xi + log_pn if cfg.debias else xi
    scores = jnp.where(valid, scores, -jnp.inf)
    top, sel = jax.lax.top_k(scores, topk)
    labels = jnp.take_along_axis(cand, sel, axis=-1)
    return top, labels


def predictive_topk(cfg: HeadConfig, params: HeadParams, gen: Generator,
                    h: jax.Array, x_gen: jax.Array, topk: int,
                    beam: Optional[int] = None,
                    score_fn: ScoreFn = candidate_scores,
                    sampler: Optional[NegativeSampler] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Top-``topk`` unbiased predictive (scores, labels) without any O(C) pass.

    For `adversarial_ns`, beam search over the generator tree proposes
    ``beam`` candidates ranked by log p_n(y|x) in O(beam·k·log C); only those
    are scored (`score_fn`, an O(beam·K) gather-and-dot or the gather_scores
    Pallas kernel) and Eq. 5 debiasing is applied on the candidate set:
    final score xi_y + log p_n(y|x). The generator is trained toward p_D
    (Theorem 2), so its high-probability set is exactly the candidate set
    the debiased argmax lives in; with ``beam >= C_pad`` the result equals
    the dense :func:`predictive_scores` top-k exactly.

    Other head kinds have no conditional candidate structure and fall back
    to dense scoring + top_k. Returns (scores, labels), each (..., topk);
    slots beyond the number of live candidates carry score -inf, label -1.
    With an explicit ``sampler``, the beam path runs iff the sampler is
    tree-backed (a :class:`~repro.core.samplers.TreeSampler`); every other
    proposal falls back to dense scoring debiased by that sampler.
    """
    if sampler is not None:
        tree = getattr(sampler, "tree", None)
    else:
        tree = gen.tree if cfg.kind == "adversarial_ns" else None
    if tree is None:
        scores = predictive_scores(cfg, params, gen, h, x_gen,
                                   sampler=sampler)
        top, labels = jax.lax.top_k(scores, topk)
        return top, labels.astype(jnp.int32)
    if beam is None:
        beam = max(4 * topk, 16)
    beam = min(beam, tree_lib.padded_size(cfg.num_labels))
    cand, log_pn = tree_lib.beam_search(tree, x_gen, beam, beam)
    top, labels = rescore_candidates(cfg, params, h, cand, log_pn,
                                     min(topk, beam), score_fn=score_fn)
    if topk > beam:    # keep the documented (..., topk) output shape
        pad = [(0, 0)] * (labels.ndim - 1) + [(0, topk - beam)]
        top = jnp.pad(top, pad, constant_values=-jnp.inf)
        labels = jnp.pad(labels, pad, constant_values=-1)
    return top, labels


def predictive_log_likelihood(cfg, params, gen, h, x_gen, y,
                              mask: Optional[jax.Array] = None,
                              sampler: Optional[NegativeSampler] = None):
    """Mean test log-likelihood log softmax(scores)[y] (paper Fig. 1)."""
    scores = predictive_scores(cfg, params, gen, h, x_gen, sampler=sampler)
    logp = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
    pos = jnp.take_along_axis(logp, y[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(pos)
    return jnp.sum(pos * mask) / jnp.maximum(mask.sum(), 1.0)


def predictive_accuracy(cfg, params, gen, h, x_gen, y,
                        mask: Optional[jax.Array] = None,
                        sampler: Optional[NegativeSampler] = None):
    scores = predictive_scores(cfg, params, gen, h, x_gen, sampler=sampler)
    correct = (jnp.argmax(scores, axis=-1) == y).astype(jnp.float32)
    if mask is None:
        return jnp.mean(correct)
    return jnp.sum(correct * mask) / jnp.maximum(mask.sum(), 1.0)
