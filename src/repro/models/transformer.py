"""Decoder-only LM assembly: scan-over-layers, embeddings, caches.

One scanned block body serves all four families (attn / ssm / hybrid / moe):
per-layer heterogeneity (gemma2 local↔global windows) rides along as scanned
arrays, so the HLO stays O(1) in depth — essential for the 70-cell dry-run
and for remat-policy control at scale.

Modes:
  forward(..., cache=None)        — training / teacher forcing
  forward(..., cache, cache_pos)  — serving prefill (writes cache) and
                                    single-token decode (S == 1)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_layer(rng, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {
        "norm_mix": L.init_rmsnorm(cfg.d_model),
        "norm_ffn": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.block in ("attn", "hybrid"):
        p["attn"] = L.init_attention(ks[0], cfg)
    if cfg.block in ("ssm", "hybrid"):
        p["ssm"] = ssm_lib.init_ssm_block(ks[1], cfg)
    if cfg.is_moe:
        p["moe"] = moe_lib.init_moe(ks[2], cfg)
    elif cfg.d_ff > 0:
        p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff)
    else:
        del p["norm_ffn"]     # mamba2: the SSM block is the whole layer
    return p


def init_params(rng, cfg: ModelConfig) -> Dict[str, Any]:
    k_e, k_l, k_h = jax.random.split(rng, 3)
    vp, d = cfg.padded_vocab, cfg.d_model
    layer_keys = jax.random.split(k_l, cfg.num_layers)
    if cfg.scan_layers:
        layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    else:
        layers = [init_layer(k, cfg) for k in layer_keys]
    params = {
        "embed": L.normal_init(k_e, (vp, d), d),
        "layers": layers,
        "final_norm": L.init_rmsnorm(d),
        "head": {
            "w": L.normal_init(k_h, (vp, d), d),
            "b": jnp.zeros((vp,), jnp.float32),
        },
    }
    return params


# ---------------------------------------------------------------------------
# Caches (stacked over layers for scan)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Decode cache pytree, leaves stacked on a leading layer dim."""
    ell = cfg.num_layers
    cache: Dict[str, Any] = {}
    if cfg.block in ("attn", "hybrid"):
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((ell, batch, max_len, kv, hd), dtype)
        cache["v"] = jnp.zeros((ell, batch, max_len, kv, hd), dtype)
    if cfg.block in ("ssm", "hybrid"):
        conv, state = ssm_lib.init_ssm_cache(cfg, batch)
        cache["conv"] = jnp.tile(conv[None], (ell,) + (1,) * conv.ndim)
        cache["state"] = jnp.tile(state[None], (ell,) + (1,) * state.ndim)
    return cache


def init_paged_cache(cfg: ModelConfig, n_lanes: int, n_pages: int,
                     page_len: int, dtype=jnp.bfloat16):
    """Paged decode cache: attention K/V live in a shared page arena
    (``n_pages`` physical pages of ``page_len`` positions each, including
    the allocator's sink page) addressed through per-lane page tables, not
    in per-row ``max_len`` buffers. SSM conv/state have no sequence dim to
    page, so they stay lane-indexed (``n_lanes`` rows) as before."""
    ell = cfg.num_layers
    cache: Dict[str, Any] = {}
    if cfg.block in ("attn", "hybrid"):
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((ell, n_pages, page_len, kv, hd), dtype)
        cache["v"] = jnp.zeros((ell, n_pages, page_len, kv, hd), dtype)
    if cfg.block in ("ssm", "hybrid"):
        conv, state = ssm_lib.init_ssm_cache(cfg, n_lanes)
        cache["conv"] = jnp.tile(conv[None], (ell,) + (1,) * conv.ndim)
        cache["state"] = jnp.tile(state[None], (ell,) + (1,) * state.ndim)
    return cache


# ---------------------------------------------------------------------------
# Block body
# ---------------------------------------------------------------------------

def _block(layer_params, cfg: ModelConfig, h, positions, window,
           cache_l, cache_pos, decode: bool, attn_mask=None,
           page_table=None, write_mask=None):
    """One decoder block. Returns (h, new_cache_l, metrics)."""
    from repro.parallel.hints import hint_residual
    h = hint_residual(h)   # seq-parallel residual (no-op unless hinted)
    metrics = {}
    mix_in = L.rmsnorm(layer_params["norm_mix"], h)
    new_cache: Dict[str, Any] = {}
    mix_out = 0.0
    n_branches = 0
    if cfg.block in ("attn", "hybrid"):
        kvc = (cache_l["k"], cache_l["v"]) if cache_l is not None else None
        a_out, a_cache = L.attention(layer_params["attn"], cfg, mix_in,
                                     positions, window, kv_cache=kvc,
                                     cache_pos=cache_pos, mask=attn_mask,
                                     page_table=page_table,
                                     write_mask=write_mask)
        if cache_l is not None:
            new_cache["k"], new_cache["v"] = a_cache
        mix_out = mix_out + a_out
        n_branches += 1
    if cfg.block in ("ssm", "hybrid"):
        sc = ((cache_l["conv"], cache_l["state"])
              if cache_l is not None else None)
        s_out, s_cache = ssm_lib.ssm_block(layer_params["ssm"], cfg, mix_in,
                                           cache=sc, decode=decode)
        if cache_l is not None:
            new_cache["conv"], new_cache["state"] = s_cache
        mix_out = mix_out + s_out
        n_branches += 1
    # hymba: mean of parallel heads. Cast keeps the scan carry dtype stable
    # regardless of cache dtype promotion.
    h = h + (mix_out / float(n_branches)).astype(h.dtype)

    if cfg.is_moe or cfg.d_ff > 0:
        ffn_in = L.rmsnorm(layer_params["norm_ffn"], h)
        if cfg.is_moe:
            f_out, moe_metrics = moe_lib.moe_ffn(layer_params["moe"], cfg,
                                                 ffn_in)
            metrics.update(moe_metrics)
        else:
            f_out = L.mlp(layer_params["mlp"], ffn_in, jnp.dtype(cfg.dtype))
        h = h + f_out.astype(h.dtype)
    return h, new_cache, metrics


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, tokens: jax.Array,
                 vision_embeds: Optional[jax.Array] = None) -> jax.Array:
    """Token embedding; vision/audio frontends prepend precomputed embeddings
    (modality stub per the task statement)."""
    cdt = jnp.dtype(cfg.dtype)
    h = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if vision_embeds is not None:
        h = jnp.concatenate([vision_embeds.astype(cdt), h], axis=1)
    return h


def forward(params, cfg: ModelConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None,
            cache=None, cache_pos: Optional[jax.Array] = None,
            page_table: Optional[jax.Array] = None,
            write_mask: Optional[jax.Array] = None,
            inputs_embeds: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Any, Dict[str, jax.Array]]:
    """Run the stack. Returns (hidden (B,S,d), new_cache, metrics).

    - training:        cache=None
    - serving prefill: cache=init_cache(...), cache_pos=0, S=prompt len
    - serving decode:  cache from prefill, cache_pos=current, S=1;
      cache_pos may be a (B,) vector for slotted decode (repro.serve),
      writing each row's KV at its own depth
    - paged decode:    cache=init_paged_cache(...), cache_pos (B,) vector,
      page_table (B, max_pages) mapping each lane's logical pages onto the
      shared arena (repro.serve.PagedPool); the page table is shared by
      every layer. S may exceed 1 (shared-prefix suffix prefill and the
      speculative verify step, repro.serve): row r's tokens occupy logical
      positions cache_pos[r] .. cache_pos[r]+S-1, and ``write_mask``
      (B, S) reroutes padding positions' K/V writes to the sink page

    ``inputs_embeds`` bypasses the embedding gather entirely: the caller
    supplies the (B, S, d) hidden input (already cast, vision embeds
    already concatenated). This is the sparse-embedding training path
    (DESIGN.md §11): the gather runs *outside* the trunk vjp so its
    cotangent can be collected as SparseRows instead of a dense (V, d)
    scatter-add.
    """
    h = (inputs_embeds if inputs_embeds is not None
         else embed_inputs(params, cfg, tokens, vision_embeds))
    bsz, s, _ = h.shape
    auto_positions = positions is None
    if positions is None:
        # cache_pos may be a scalar (lock-step decode / prefill) or a (B,)
        # vector (slotted decode: each row at its own depth).
        base = jnp.asarray(0 if cache_pos is None else cache_pos, jnp.int32)
        positions = base[..., None] + jnp.arange(s, dtype=jnp.int32)
        if positions.ndim == 1:
            positions = positions[None]
        positions = jnp.broadcast_to(positions, (bsz, s))
    decode = cache is not None and s == 1
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)       # (L,)

    # Hoist the training-mode attention mask out of the layer scan: one
    # (2, S, S) constant (full-causal / windowed) instead of per-layer
    # (B, S, S) index arithmetic (§Perf C1). Only valid when positions are
    # the default arange and there is no cache (pure self-attention).
    masks = None
    if auto_positions and cache is None and cfg.block in ("attn", "hybrid"):
        masks = jnp.stack([
            L.causal_window_mask(s, 0),
            L.causal_window_mask(s, cfg.window_size or 0)])

    body = functools.partial(_block, cfg=cfg, positions=positions,
                             cache_pos=cache_pos, decode=decode,
                             page_table=page_table, write_mask=write_mask)

    if cfg.scan_layers:
        # Under a transform, jax hoists the body's loop-invariant ops (the
        # SSD's causal mask) out of the scan with only the names opened
        # inside the body, so the `trunk` scope (DESIGN.md §10) is opened
        # here as well as around the caller's forward.
        @jax.named_scope("trunk")
        def scan_body(carry, xs):
            lp, window, cache_l = xs
            attn_mask = (None if masks is None
                         else jnp.where(window > 0, masks[1], masks[0]))
            new_h, new_cache_l, metrics = body(lp, h=carry, window=window,
                                               cache_l=cache_l,
                                               attn_mask=attn_mask)
            return new_h, (new_cache_l, metrics)

        if cfg.remat:
            scan_body = jax.checkpoint(
                scan_body,
                policy=jax.checkpoint_policies.nothing_saveable)
        h, (new_cache, metrics) = jax.lax.scan(
            scan_body, h, (params["layers"], windows, cache))
        metrics = jax.tree.map(jnp.mean, metrics)
    else:
        new_cache_layers, metrics = [], {}
        for i in range(cfg.num_layers):
            cache_l = (None if cache is None
                       else jax.tree.map(lambda c: c[i], cache))
            attn_mask = (None if masks is None else
                         masks[1 if cfg.window_for_layer(i) > 0 else 0])
            h, nc, metrics = body(params["layers"][i], h=h,
                                  window=windows[i], cache_l=cache_l,
                                  attn_mask=attn_mask)
            new_cache_layers.append(nc)
        new_cache = (jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *new_cache_layers)
                     if cache is not None else None)

    h = L.rmsnorm(params["final_norm"], h)
    return h, new_cache, metrics
