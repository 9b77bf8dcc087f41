"""jit'd wrappers for the Pallas kernels with interpret-mode dispatch.

On a TPU the kernels compile to Mosaic. On the CPU backend they run with
``interpret=True`` (the Pallas interpreter executes the kernel body, for
correctness tests); any other platform is an error, never a silent
interpreter run. ``use_pallas(False)`` routes everything to the pure-jnp
references (repro.kernels.ref) for A/B testing.

The ``use_pallas`` flag is read at *call* time and passed into the jitted
impls as a static argument: each setting gets its own jit cache entry, so
toggling mid-process really switches the executed path (a trace-time read
would be baked into the first trace and silently ignored afterwards).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_lib
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gather_scores import gather_scores as _gather
from repro.kernels.sampled_loss import sampled_head_loss as _sampled_loss
from repro.kernels.segment_scores import segment_stats as _segstats
from repro.kernels.ssd_intra import LANES, ssd_intra as _ssd_intra
from repro.kernels.tree_logprob import tree_logprob_all as _treelp

_STATE = {"use_pallas": True, "interpret": None}


def use_pallas(on: bool):
    _STATE["use_pallas"] = on


def _interpret() -> bool:
    if _STATE["interpret"] is None:
        platform = jax.devices()[0].platform
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"Pallas kernels compile for TPU and are interpreted on CPU; "
                f"no path exists for platform {platform!r}")
        _STATE["interpret"] = platform == "cpu"
    return _STATE["interpret"]


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "pallas"))
def _flash_impl(q, k, v, causal: bool, window: int, softcap: float,
                pallas: bool):
    if not pallas:
        return ref_lib.flash_attention_ref(q, k, v, causal=causal,
                                           window=window, softcap=softcap)
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  interpret=_interpret())


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0):
    return _flash_impl(q, k, v, causal, window, softcap,
                       _STATE["use_pallas"])


@functools.partial(jax.jit, static_argnames=("pallas",))
def _treelp_impl(w, b, x, pallas: bool):
    if not pallas:
        return ref_lib.tree_logprob_all_ref(w, b, x)
    return _treelp(w, b, x, interpret=_interpret())


def tree_logprob_all(w, b, x):
    return _treelp_impl(w, b, x, _STATE["use_pallas"])


@functools.partial(jax.jit, static_argnames=("pallas",))
def _gather_impl(w, b, h, ids, pallas: bool):
    if not pallas:
        return ref_lib.gather_scores_ref(w, b, h, ids)
    return _gather(w, b, h, ids, interpret=_interpret())


def gather_scores(w, b, h, ids):
    return _gather_impl(w, b, h, ids, _STATE["use_pallas"])


@functools.partial(jax.jit, static_argnames=(
    "kind", "num_labels", "reg", "softcap", "mask_accidental", "pallas"))
def _sampled_loss_impl(w, b, h, ids, slot_logp, kind: str, num_labels: int,
                       reg: float, softcap: float, mask_accidental: bool,
                       pallas: bool):
    if not pallas:
        return ref_lib.sampled_head_loss_ref(
            w, b, h, ids, slot_logp, kind=kind, num_labels=num_labels,
            reg=reg, softcap=softcap, mask_accidental=mask_accidental)
    return _sampled_loss(w, b, h, ids, slot_logp, kind=kind,
                         num_labels=num_labels, reg=reg, softcap=softcap,
                         mask_accidental=mask_accidental,
                         interpret=_interpret())


def sampled_head_loss(w, b, h, ids, slot_logp, *, kind: str,
                      num_labels: int, reg: float = 0.0,
                      softcap: float = 0.0, mask_accidental: bool = True):
    """Fused sampled-head loss fwd+bwd (repro.kernels.sampled_loss):
    (loss_vec, coeff, xi, dh) — slot 0 of ``ids`` is the positive."""
    return _sampled_loss_impl(w, b, h, ids, slot_logp, kind, num_labels,
                              reg, softcap, mask_accidental,
                              _STATE["use_pallas"])


@functools.partial(jax.jit, static_argnames=("num_segments", "pallas"))
def _segstats_impl(vals, seg, num_segments: int, pallas: bool):
    if not pallas:
        return ref_lib.segment_stats_ref(vals, seg, num_segments)
    return _segstats(vals, seg, num_segments, interpret=_interpret())


def segment_stats(vals, seg, num_segments: int):
    """Segment-summed fit statistics (repro.genfit hot reduction)."""
    return _segstats_impl(vals, seg, num_segments, _STATE["use_pallas"])


@functools.partial(jax.jit, static_argnames=("pallas",))
def _ssd_intra_impl(cm, bm, seg, xbar, pallas: bool):
    if not pallas or seg.shape[2] % LANES:
        return ref_lib.ssd_intra_ref(cm, bm, seg, xbar)
    interpret = _interpret()
    # XLA's default precision for an f32 matmul: one bf16 pass on a TPU,
    # f32 on the CPU. The kernel's matmuls take the same operands.
    return _ssd_intra(cm, bm, seg, xbar, interpret,
                      jnp.float32 if interpret else jnp.bfloat16)


def ssd_intra(cm, bm, seg, xbar):
    """The SSD's intra-chunk block (repro.kernels.ssd_intra), differentiable.
    A chunk length that is not a multiple of 128 lanes stays on the
    einsums (repro.kernels.ref.ssd_intra_ref)."""
    return _ssd_intra_impl(cm, bm, seg, xbar, _STATE["use_pallas"])
