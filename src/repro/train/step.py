"""train_step / eval_step / serve_step builders.

These close over static configs and take pure pytrees, so the same function
jits on one CPU device and pjits on the 512-chip production mesh (the launch
layer supplies in/out shardings). The head strategy — including the paper's
adversarial sampling — is a config knob; `serve_step` applies Eq. 5 bias
removal over the full vocabulary.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.heads import (HeadConfig, HeadParams,
                              resolve_head_update)  # noqa: F401 (re-export)
from repro.models import lm_head, transformer
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig, apply_updates, init_opt_state
from repro.optim import sparse as sparse_opt
from repro.train.state import TrainState, snr_reset_pair


def loss_fn(params, cfg: ModelConfig, hcfg: HeadConfig, head_state,
            batch: Dict[str, jax.Array], rng: jax.Array, sampler=None):
    with jax.named_scope("trunk"):
        h, _, fwd_metrics = transformer.forward(
            params, cfg, batch["tokens"],
            positions=batch.get("positions"),
            vision_embeds=batch.get("vision_embeds"))
    labels = batch["labels"]
    mask = batch.get("mask")
    if cfg.modality == "vision" and labels.shape[1] != h.shape[1]:
        # Vision prefix carries no next-token loss.
        nv = h.shape[1] - labels.shape[1]
        h = h[:, nv:]
    loss, head_metrics = lm_head.lm_head_loss(
        cfg, hcfg, HeadParams(**params["head"]), head_state, h, labels,
        rng, mask=mask, sampler=sampler)
    metrics = {"loss": loss, **fwd_metrics, **head_metrics}
    return loss, metrics


def make_train_step(cfg: ModelConfig, hcfg: HeadConfig,
                    opt_cfg: OptimizerConfig, head_update: str = "auto",
                    head_kernel: bool = False, mesh=None,
                    sampler=None, snr_alpha: float = 0.1,
                    embed_update: str = "auto",
                    skip_nonfinite: bool = False):
    """Returns train_step(state, batch, rng) -> (state, metrics).

    ``head_update`` picks the head-gradient path (DESIGN.md §8):

    * ``dense`` — ``jax.value_and_grad`` end to end: autodiff scatter-adds
      the candidate-score backward into a dense (C, K) gradient and the
      optimizer walks every row. O(C·K) per step regardless of sampling.
    * ``sparse`` — the trunk still backprops through ``jax.vjp`` (driven
      by the analytic head cotangent ``dh``), but the head gradient is a
      ``SparseRows`` leaf over the ≤ B·(1+n_neg) touched rows and the
      optimizer applies O(U·K) row updates. Identical math on the touched
      rows (exact for Adagrad/SGD, lazy-decay AdamW), cost independent
      of C.
    * ``auto`` (default) — sparse for sampled heads, dense for `softmax`.

    ``head_kernel`` routes the sparse path's gather→loss→coefficient chain
    through the fused Pallas kernel. ``mesh`` lets the sparse optimizer
    update run shard-local on a vocab-sharded head (each model shard
    applies only the rows it owns — ``parallel.collectives``).

    ``sampler`` overrides the negative-sampling proposal with an explicit
    :class:`repro.core.samplers.NegativeSampler` (closed over, so it is
    static for the life of the step function — generator refreshes only
    reach the default ``cfg.kind``-derived proposal, which is rebuilt from
    ``head_state`` every call). ``snr_alpha`` is the EWMA weight of the
    online SNR proxy tracked in ``TrainState.snr_ewma`` for the
    SNR-driven refresh trigger (DESIGN.md §9).

    ``skip_nonfinite`` arms the DESIGN.md §13 skip-step guard *inside*
    the jitted step: when the loss (or grad norm) is non-finite, every
    params/opt/EWMA leaf selects its pre-step value, so a poisoned batch
    costs one wasted step instead of corrupting the run. The select must
    live in-graph because the loop donates the input state — by the time
    the host sees the metrics, the pre-step buffers are gone. The step
    counter still advances (data/rng streams are step-indexed and must
    not replay the bad batch) and ``metrics["nonfinite"]`` reports the
    skip for the loop's counter / consecutive-skip limit.

    ``embed_update`` extends the sparse treatment to the *input* embedding
    (DESIGN.md §11): the token gather runs outside the trunk vjp, its
    cotangent rows are deduped into a SparseRows leaf, and the optimizer
    applies O(touched-tokens·d) row updates instead of scatter-adding a
    dense (V, d) gradient. ``auto`` (default) rides with the head: sparse
    when the head path is sparse, dense otherwise; ``dense`` forces the
    old behaviour.
    """
    mode = resolve_head_update(head_update, hcfg.kind)
    assert not (head_kernel and mode == "dense"), (
        "head_kernel routes the SPARSE path through the fused Pallas "
        "kernel; the resolved head_update here is 'dense', which would "
        "silently ignore it")
    assert embed_update in ("auto", "sparse", "dense"), embed_update
    emode = embed_update
    if emode == "auto":
        emode = "sparse" if mode == "sparse" else "dense"
    assert not (emode == "sparse" and mode == "dense"), (
        "sparse embed updates ride the sparse-head step (jax.vjp); the "
        "dense value_and_grad path cannot deliver them")

    def dense_step(state: TrainState, batch, rng):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, metrics), grads = grad_fn(state.params, cfg, hcfg,
                                         state.head_state, batch, rng,
                                         sampler)
        return grads, metrics

    def sparse_step(state: TrainState, batch, rng):
        params = state.params
        # Sparse embed path: run the token gather OUTSIDE the trunk vjp
        # (forward takes inputs_embeds) and collect its cotangent rows as
        # SparseRows instead of letting autodiff scatter-add a dense
        # (V, d) gradient. Trace-time Python check: params without an
        # embedding table (standalone-head configs) fall back to dense.
        embed_sparse = emode == "sparse" and "embed" in params
        drop = {"head", "embed"} if embed_sparse else {"head"}
        trunk = {k: v for k, v in params.items() if k not in drop}
        tokens = batch["tokens"]

        # The trunk's forward runs inside jax.vjp, so its ops carry
        # `jvp(trunk)` and their backward `transpose(jvp(trunk))`; the
        # head scope is opened by lm_head itself.
        if embed_sparse:
            cdt = jnp.dtype(cfg.dtype)
            with jax.named_scope("trunk"):
                h0 = jnp.take(params["embed"], tokens, axis=0).astype(cdt)

            @jax.named_scope("trunk")
            def trunk_fwd(tp, h0_in):
                ve = batch.get("vision_embeds")
                ie = (h0_in if ve is None
                      else jnp.concatenate([ve.astype(cdt), h0_in],
                                           axis=1))
                h, _, fwd_metrics = transformer.forward(
                    tp, cfg, tokens, positions=batch.get("positions"),
                    inputs_embeds=ie)
                return h, fwd_metrics

            h, trunk_vjp, fwd_metrics = jax.vjp(trunk_fwd, trunk, h0,
                                                has_aux=True)
        else:
            @jax.named_scope("trunk")
            def trunk_fwd(tp):
                h, _, fwd_metrics = transformer.forward(
                    tp, cfg, tokens, positions=batch.get("positions"),
                    vision_embeds=batch.get("vision_embeds"))
                return h, fwd_metrics

            h, trunk_vjp, fwd_metrics = jax.vjp(trunk_fwd, trunk,
                                                has_aux=True)
        labels = batch["labels"]
        n_vis = 0
        if cfg.modality == "vision" and labels.shape[1] != h.shape[1]:
            n_vis = h.shape[1] - labels.shape[1]
        loss, head_metrics, sparse, dh = lm_head.lm_sparse_head_loss(
            cfg, hcfg, HeadParams(**params["head"]), state.head_state,
            h[:, n_vis:] if n_vis else h, labels, rng,
            mask=batch.get("mask"), use_kernel=head_kernel, sampler=sampler)
        with jax.named_scope("trunk"):
            if n_vis:   # vision prefix carries no next-token loss
                dh = jnp.pad(dh, ((0, 0), (n_vis, 0), (0, 0)))
            if embed_sparse:
                trunk_grads, dh0 = trunk_vjp(dh.astype(h.dtype))
                vocab = params["embed"].shape[0]
                grads = {**trunk_grads, "head": sparse,
                         "embed": sparse_opt.accumulate_embed_rows(
                             tokens.reshape(-1),
                             dh0.reshape(-1, dh0.shape[-1]), vocab)}
            else:
                (trunk_grads,) = trunk_vjp(dh.astype(h.dtype))
                grads = {**trunk_grads, "head": sparse}
        metrics = {"loss": loss, **fwd_metrics, **head_metrics}
        return grads, metrics

    def train_step(state: TrainState, batch, rng):
        # Three top-level named scopes cover the step (DESIGN.md §10):
        # `trunk` and `head` (with `head/sample`, the generator tree)
        # inside the loss and gradient, `optimizer` from here to the end.
        # Each op's HLO metadata carries its scope, so a device trace's
        # op time splits by layer.
        grads, metrics = (dense_step if mode == "dense"
                          else sparse_step)(state, batch, rng)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = apply_updates(
                opt_cfg, state.params, grads, state.opt_state, mesh=mesh)
            metrics.update(opt_metrics)
            # Fold the per-batch signal-mass proxy into the EWMA the SNR
            # refresh trigger watches. "snr_proxy" presence is a trace-time
            # Python check (the head kind is static), so the dense-softmax
            # path compiles without the extra arithmetic. snr_ref is armed
            # host-side by the loop; the step only smooths.
            snr_ewma = state.snr_ewma
            if "snr_proxy" in metrics:
                p = metrics["snr_proxy"].astype(jnp.float32)
                snr_ewma = jnp.where(
                    state.snr_ewma < 0, p,
                    (1.0 - snr_alpha) * state.snr_ewma + snr_alpha * p)
                metrics["snr_ewma"] = snr_ewma
            if skip_nonfinite:
                ok = jnp.isfinite(metrics["loss"])
                if "grad_norm" in metrics:
                    ok = ok & jnp.isfinite(metrics["grad_norm"])
                sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                new_params = jax.tree.map(sel, new_params, state.params)
                new_opt = jax.tree.map(sel, new_opt, state.opt_state)
                snr_ewma = jnp.where(ok, snr_ewma, state.snr_ewma)
                metrics["nonfinite"] = (~ok).astype(jnp.float32)
            return TrainState(step=state.step + 1, params=new_params,
                              opt_state=new_opt,
                              head_state=state.head_state,
                              gen_fit_step=state.gen_fit_step,
                              snr_ewma=snr_ewma,
                              snr_ref=state.snr_ref), metrics

    return train_step


# Jitted-metric name -> repro.obs gauge (DESIGN.md §10 `snr/*` and
# `train/*` namespaces). The SNR triple drives the refresh trigger
# (snr_proxy = per-batch Eq. 13 signal mass, snr_ewma = its smoothed
# TrainState series, snr_ref = the armed post-install reference), and
# publishing them as gauges is what makes --gen-refresh-mode snr
# observable outside TrainState.
STEP_METRIC_GAUGES = {
    "loss": "train/loss",
    "grad_norm": "train/grad_norm",
    "snr_proxy": "snr/proxy",
    "snr_ewma": "snr/ewma",
}


def publish_step_metrics(registry, host_metrics: Dict[str, float],
                         snr_ref: Optional[float] = None,
                         head_state_bytes: Optional[int] = None) -> None:
    """Host-side bridge from a jitted step's metrics dict to the obs
    registry. The step function runs under jit and cannot touch host
    state, so the loop device_gets the (tiny, already-computed) metrics
    once per step and publishes through this mapping; ``snr_ref`` lives
    on TrainState, not in the metrics dict, and is passed separately.
    ``head_state_bytes`` (optim.head_state_bytes — a static byte count,
    computed once at loop start) lands on the ``train/head_state_bytes``
    gauge so the DESIGN.md §11 memory model is observable in prod."""
    registry.counter("train/steps").inc()
    for src, name in STEP_METRIC_GAUGES.items():
        if src in host_metrics:
            registry.gauge(name).set(host_metrics[src])
    if snr_ref is not None:
        registry.gauge("snr/ref").set(snr_ref)
    if head_state_bytes is not None:
        registry.gauge("train/head_state_bytes").set(head_state_bytes)


def make_eval_step(cfg: ModelConfig, hcfg: HeadConfig):
    """Debiased predictive log-likelihood + accuracy (paper Fig. 1 axes)."""

    def eval_step(state: TrainState, batch):
        h, _, _ = transformer.forward(
            state.params, cfg, batch["tokens"],
            positions=batch.get("positions"),
            vision_embeds=batch.get("vision_embeds"))
        scores = lm_head.lm_predictive_scores(
            cfg, hcfg, HeadParams(**state.params["head"]),
            state.head_state, h)
        labels = batch["labels"].astype(jnp.int32)
        mask = batch.get("mask", jnp.ones(labels.shape, jnp.float32))
        logp = scores - jax.nn.logsumexp(scores, axis=-1, keepdims=True)
        pos = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        acc = (jnp.argmax(scores, -1) == labels).astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        return {"eval_loglik": jnp.sum(pos * mask) / denom,
                "eval_acc": jnp.sum(acc * mask) / denom}

    return eval_step


def make_serve_step(cfg: ModelConfig, hcfg: HeadConfig,
                    topk_beam: int = 0, use_kernel: bool = False,
                    mesh=None):
    """Greedy decode step: one token in, one token out, cache updated.

    With ``topk_beam == 0`` (default) the predictive scores are dense: the
    paper's bias removal (Eq. 5) as an O(C·k) tree pass riding on top of the
    O(C·K) logits matmul. With ``topk_beam > 0`` the decode never touches
    O(C): beam search over the generator tree proposes ``topk_beam``
    candidates in O(beam·k·log C) and only those are scored + debiased
    (``use_kernel`` routes the scoring through the gather_scores Pallas
    kernel). Both paths pick the same argmax whenever the true top-1 label
    survives the beam.

    ``mesh`` routes the beam path's candidate scoring through
    ``parallel.collectives.sharded_candidate_scores``: each model shard
    scores only the candidate rows it owns and one psum of the tiny
    (batch, beam) score tensor replicates the result — no all-gather of
    the vocab-sharded output embedding.
    """
    score_fn = (lm_head.serving_score_fn(cfg, use_kernel=use_kernel,
                                         mesh=mesh)
                if topk_beam else None)

    def serve_step(params, head_state, token, cache, cache_pos,
                   positions=None):
        h, new_cache, _ = transformer.forward(
            params, cfg, token, positions=positions, cache=cache,
            cache_pos=cache_pos)
        head_params = HeadParams(**params["head"])
        if topk_beam:
            _, labels = lm_head.lm_predictive_topk(
                cfg, hcfg, head_params, head_state, h[:, -1], topk=1,
                beam=topk_beam, use_kernel=use_kernel, score_fn=score_fn)
            next_token = labels[..., 0].astype(jnp.int32)
        else:
            scores = lm_head.lm_predictive_scores(
                cfg, hcfg, head_params, head_state, h[:, -1])
            next_token = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        return next_token[:, None], new_cache

    return serve_step


def make_prefill(cfg: ModelConfig):
    def prefill(params, tokens, cache, vision_embeds=None, positions=None):
        h, new_cache, _ = transformer.forward(
            params, cfg, tokens, positions=positions,
            vision_embeds=vision_embeds, cache=cache,
            cache_pos=jnp.int32(0))
        return h, new_cache

    return prefill


def make_batched_prefill(cfg: ModelConfig, page_len: int, sink_page: int,
                         cache_dtype=jnp.bfloat16):
    """Prefill a *batch* of newly-admitted requests into their pages
    (repro.serve). Generalizes the old one-request-per-call
    ``make_prefill_into_slot``: every request admitted in an engine
    iteration runs through ONE padded forward instead of N sequential
    single-row calls.

    Returns ``batched_prefill(params, tokens, lengths, lanes, arena,
    page_tables)`` → ``(h, arena)``:

    - ``tokens`` (N, S): right-padded prompts. Padding is invisible to the
      real tokens (causal attention never looks forward), so each row's
      K/V and hiddens are bit-identical to an unpadded single-request
      prefill — the property the batched-vs-sequential oracle pins.
    - ``lengths`` (N,): true prompt lengths; positions at or beyond a
      row's length scatter into ``sink_page`` (the allocator's garbage
      page) instead of a mapped page.
    - ``lanes`` (N,): decode-lane index per row, for the lane-indexed SSM
      conv/state leaves. Padding rows carry an out-of-range lane and are
      dropped by the scatter.
    - ``page_tables`` (N, max_pages): each row's logical→physical page
      map; logical position p lands at ``(page_tables[n, p // page_len],
      p % page_len)``.

    The forward runs against a fresh contiguous (N, S) cache — identical
    math to :func:`make_prefill` — and only the final scatter re-addresses
    the resulting K/V into the paged arena. N and S are shape-traced, so
    the engine buckets both (rows to a power of two, lengths to a power of
    two) to bound recompiles.
    """

    def batched_prefill(params, tokens, lengths, lanes, arena, page_tables):
        n, s = tokens.shape
        fresh = transformer.init_cache(cfg, n, s, dtype=cache_dtype)
        h, new_cache, _ = transformer.forward(
            params, cfg, tokens, cache=fresh, cache_pos=jnp.int32(0))
        pos = jnp.arange(s, dtype=jnp.int32)
        pid = page_tables[:, pos // page_len]               # (N, S)
        pid = jnp.where(pos[None, :] < lengths[:, None], pid, sink_page)
        off = jnp.broadcast_to((pos % page_len)[None], (n, s))
        out = dict(arena)
        for key in ("k", "v"):
            if key in arena:      # (L, P, page_len, KV, hd) ← (L, N, S, ..)
                out[key] = arena[key].at[:, pid, off].set(
                    new_cache[key].astype(arena[key].dtype))
        for key in ("conv", "state"):
            if key in arena:      # lane-indexed; padding lanes drop
                out[key] = arena[key].at[:, lanes].set(
                    new_cache[key].astype(arena[key].dtype), mode="drop")
        return h, out

    return batched_prefill


def make_paged_prefill(cfg: ModelConfig):
    """Multi-token forward *against the paged arena*: per-lane start
    positions and page tables, S tokens per row.

    Returns ``paged_prefill(params, tokens, start, lengths, arena,
    page_tables)`` → ``(h (B, S, d), arena)``. Two serving paths share this
    one compiled step (repro.serve, DESIGN.md §12):

    - **shared-prefix suffix prefill**: a newly admitted request whose
      prompt prefix is already resident in shared pages runs its *suffix*
      through here — row r's tokens are ``prompt[start[r]:]``, attention
      gathers the shared prefix pages through the page table, and only the
      suffix K/V is computed and written. The prefix pays its prefill once
      across every request that shares it.
    - **speculative verify**: the draft chain ``[y_last, d1..dk]`` runs as
      one batched multi-token step; the returned per-position hiddens feed
      next-token selection at every draft position in a single launch.

    ``start`` (B,) is each row's first logical position, ``lengths`` (B,)
    its true token count — positions at or beyond a row's length (row/
    length padding) write to the allocator's sink page via ``write_mask``
    and their hiddens are garbage the caller masks host-side. Unlike
    :func:`make_batched_prefill` there is no fresh contiguous cache: the
    forward reads and writes the arena directly, so earlier tokens'
    K/V — shared prefix pages or the rows' own prior decode writes — are
    visible exactly as the contiguous layout would present them
    (byte-identity pinned by the sharing/speculation oracle tests).
    Attention-family models only: an SSM branch carries recurrent state
    that is neither paged nor position-local.
    """
    assert cfg.block == "attn", (
        "paged multi-token steps (prefix sharing / speculative verify) "
        "need position-local state; SSM/hybrid caches are recurrent")

    def paged_prefill(params, tokens, start, lengths, arena, page_tables):
        s = tokens.shape[1]
        wmask = jnp.arange(s, dtype=jnp.int32)[None] < lengths[:, None]
        h, new_arena, _ = transformer.forward(
            params, cfg, tokens, cache=arena, cache_pos=start,
            page_table=page_tables, write_mask=wmask)
        return h, new_arena

    return paged_prefill


def make_paged_decode(cfg: ModelConfig):
    """Masked decode step over a paged pool: per-lane ``cache_pos`` and
    page tables.

    Returns ``paged_decode(params, token, arena, cache_pos, page_table)``
    → ``(h_last (B, d), arena)``. ``token`` is (B, 1) — one in-flight
    token per decode lane — ``cache_pos`` is a (B,) int32 vector (each
    lane at its own depth), and ``page_table`` (B, max_pages) maps each
    lane's logical pages onto the shared arena. Free lanes ride along as
    garbage: their page-table rows point at the sink page, so their
    writes land in the garbage page and every consumer of ``h_last``
    masks them out host-side. Head scoring is deliberately NOT fused here
    — the serve engine owns it so the candidate cache can skip the tree
    descent per step.
    """

    def paged_decode(params, token, arena, cache_pos, page_table):
        h, new_arena, _ = transformer.forward(
            params, cfg, token, cache=arena, cache_pos=cache_pos,
            page_table=page_table)
        return h[:, -1], new_arena

    return paged_decode


def init_train_state(rng, cfg: ModelConfig, opt_cfg: OptimizerConfig,
                     head_kind: str) -> TrainState:
    k_p, k_h = jax.random.split(rng)
    params = transformer.init_params(k_p, cfg)
    ewma0, ref0 = snr_reset_pair()
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=init_opt_state(opt_cfg, params),
        head_state=lm_head.default_head_state(k_h, cfg, head_kind),
        gen_fit_step=jnp.full((), -1, jnp.int32),
        snr_ewma=ewma0, snr_ref=ref0)
