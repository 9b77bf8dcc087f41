"""Sharding rules: DP/FSDP over 'data' (+ pure-DP 'pod'), TP/EP over 'model'.

Policy (baseline; §Perf iterates on it):
  * activations: batch over ('pod','data'); d_model replicated on 'model'
  * attention:  q/kv heads over 'model' when divisible by TP, else FSDP-only
    (d_model dim over 'data') — awkward head counts (hymba 25H, qwen2-vl
    28H, musicgen 24H) fall back rather than padding the architecture
  * MLP: d_ff over 'model' (always divisible), d_model over 'data' (FSDP)
  * MoE: expert dim over 'model' when divisible (deepseek-moe 64e), else
    per-expert d_ff over 'model' (mixtral 8e)
  * embedding + head: vocab over 'model' — GSPMD partitions the token
    gather as masked-local-gather + all-reduce (verified), which is exactly
    the paper-head-friendly layout: candidate score gathers touch only the
    owning shard, and the sparse-head optimizer update (SparseRows leaves,
    DESIGN.md §8) writes shard-local through
    collectives.sharded_rows_update — no all-gather on read or write
  * optimizer state mirrors parameter sharding (ZeRO-style for free)
  * KV cache: batch over data axes; sequence over 'model' (decode attends
    with sharded-S logits; softmax reductions become psums). long-context
    B=1 shards the sequence over every axis.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey, tree_map_with_path

from repro.models.config import ModelConfig


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    """Returns (data_axes, model_axis). 'pod' folds into data parallelism."""
    names = mesh.axis_names
    assert names[-1] == "model", names
    return tuple(names[:-1]), "model"


def _tp(mesh: Mesh) -> int:
    return mesh.shape["model"]


def _dp(mesh: Mesh) -> int:
    dp_axes, _ = mesh_axes(mesh)
    n = 1
    for a in dp_axes:
        n *= mesh.shape[a]
    return n


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        if isinstance(p, DictKey):
            out.append(str(p.key))
        elif isinstance(p, SequenceKey):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def param_spec(cfg: ModelConfig, mesh: Mesh, path, leaf) -> P:
    """PartitionSpec for one parameter leaf (path-driven rules)."""
    names = _path_names(path)
    tp = _tp(mesh)
    shape = leaf.shape
    scan = cfg.scan_layers
    lead = (None,) if scan else ()   # stacked layer dim

    def div(n):
        return n % tp == 0

    if "embed" in names:
        return P("model", None)
    if "head" in names:
        return P("model", None) if len(shape) == 2 else P("model")
    if "attn" in names:
        d_over_data = "data"
        if names[-1] == "wq":
            return P(*lead, d_over_data,
                     "model" if div(cfg.num_heads) else None, None)
        if names[-1] in ("wk", "wv"):
            return P(*lead, d_over_data,
                     "model" if div(cfg.num_kv_heads) else None, None)
        if names[-1] == "wo":
            return P(*lead, "model" if div(cfg.num_heads) else None, None,
                     d_over_data)
    if "moe" in names:
        e_div = div(cfg.n_experts)
        if names[-1] == "router":
            return P(*lead, "data", None)
        if "shared" in names:
            if names[-1] == "w_down":
                return P(*lead, "model", "data")
            return P(*lead, "data", "model")
        if names[-1] in ("w_gate", "w_up"):
            return (P(*lead, "model", "data", None) if e_div
                    else P(*lead, None, "data", "model"))
        if names[-1] == "w_down":
            return (P(*lead, "model", None, "data") if e_div
                    else P(*lead, None, "model", "data"))
    if "mlp" in names:
        if names[-1] == "w_down":
            return P(*lead, "model", "data")
        return P(*lead, "data", "model")
    if "ssm" in names:
        if names[-1] == "w_in":
            return P(*lead, "data", None)
        if names[-1] == "w_out":
            return P(*lead, "model" if div(cfg.ssm_inner) else None, "data")
        return P(*lead) if scan else P()
    # norms, scalars, biases, conv weights: replicated.
    return P(*([None] * len(shape)))


def params_shardings(cfg: ModelConfig, mesh: Mesh, params_abstract: Any):
    return tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh,
                                         param_spec(cfg, mesh, path, leaf)),
        params_abstract)


def replicated(mesh: Mesh, tree: Any):
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, P(*([None] * len(getattr(leaf, "shape", ()))))), tree)


def batch_shardings(cfg: ModelConfig, mesh: Mesh, batch_abstract: Any):
    """Inputs: batch dim over data axes (replicate if batch == 1)."""
    dp_axes, _ = mesh_axes(mesh)

    def spec(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        if names and names[-1] == "positions":        # (3, B, S)
            b = shape[1] if len(shape) > 1 else 0
            ax = dp_axes if b and b % _dp(mesh) == 0 else None
            return NamedSharding(mesh, P(None, ax, None))
        if not shape or shape[0] % _dp(mesh) != 0:
            return NamedSharding(mesh, P(*([None] * len(shape))))
        rest = [None] * (len(shape) - 1)
        return NamedSharding(mesh, P(dp_axes, *rest))

    return tree_map_with_path(spec, batch_abstract)


def cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_abstract: Any,
                    batch: int):
    """KV/SSM cache sharding per the decode policy above."""
    dp_axes, model = mesh_axes(mesh)
    tp = _tp(mesh)
    big_batch = batch % _dp(mesh) == 0

    def spec(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        if names[-1] in ("k", "v"):                   # (L,B,S,KV,hd)
            if big_batch:
                return NamedSharding(mesh, P(None, dp_axes, model, None,
                                             None))
            all_axes = tuple(dp_axes) + (model,)
            return NamedSharding(mesh, P(None, None, all_axes, None, None))
        if names[-1] == "state":                      # (L,B,H,N,P)
            h_ax = model if cfg.ssm_heads % tp == 0 else None
            b_ax = dp_axes if big_batch else None
            return NamedSharding(mesh, P(None, b_ax, h_ax, None, None))
        if names[-1] == "conv":                       # (L,B,W,conv_dim)
            b_ax = dp_axes if big_batch else None
            return NamedSharding(mesh, P(None, b_ax, None, None))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    return tree_map_with_path(spec, cache_abstract)


def paged_cache_shardings(cfg: ModelConfig, mesh: Mesh, cache_abstract: Any,
                          n_lanes: int):
    """Sharding for the paged KV arena (repro.serve.PagedPool).

    K/V pages: any lane gathers any page, so the page dim stays replicated
    across data axes; the within-page sequence dim goes over 'model' when
    TP divides page_len, carrying the decode policy above (sharded-S
    logits, psum'd softmax) into the paged layout. SSM conv/state leaves
    are lane-indexed and keep the contiguous-cache rules. Every spec
    divides its dim, so placing the arena never fails on shape.
    """
    dp_axes, model = mesh_axes(mesh)
    tp = _tp(mesh)
    big_batch = n_lanes % _dp(mesh) == 0

    def spec(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        if names[-1] in ("k", "v"):                   # (L,P,page_len,KV,hd)
            s_ax = model if shape[2] % tp == 0 else None
            return NamedSharding(mesh, P(None, None, s_ax, None, None))
        if names[-1] == "state":                      # (L,lanes,H,N,P)
            h_ax = model if cfg.ssm_heads % tp == 0 else None
            b_ax = dp_axes if big_batch else None
            return NamedSharding(mesh, P(None, b_ax, h_ax, None, None))
        if names[-1] == "conv":                       # (L,lanes,W,conv_dim)
            b_ax = dp_axes if big_batch else None
            return NamedSharding(mesh, P(None, b_ax, None, None))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    return tree_map_with_path(spec, cache_abstract)


def _state_leaf_sharding(mesh: Mesh, p_spec: P, s) -> Any:
    """Sharding for one optimizer-state leaf given its param's spec.

    Moment leaves are no longer exact shape mirrors of their param
    (DESIGN.md §11): an SM3 cover keeps a (C,) row + (K,) col vector, a
    QuantizedRows box keeps an int8 payload + (C,) scale, lazy-AdamW adds
    a (C,) int32 ``last`` vector. Row-indexed pieces take the param's
    dim-0 axis; the SM3 column cover is replicated (it is K elements and
    recombined by pmax); full-shape pieces mirror the param spec.
    """
    from repro.optim.compression import QuantizedRows
    from repro.optim.optimizers import Sm3Cover

    row_spec = P(p_spec[0]) if len(p_spec) else P()
    if s is None:
        return None
    if isinstance(s, Sm3Cover):
        return Sm3Cover(row=NamedSharding(mesh, row_spec),
                        col=NamedSharding(mesh, P(None)))
    if isinstance(s, QuantizedRows):
        return QuantizedRows(q=NamedSharding(mesh, p_spec),
                             scale=NamedSharding(mesh, row_spec))
    if s.ndim == 0:
        return NamedSharding(mesh, P())
    if s.ndim == 1 and len(p_spec) >= 1:
        return NamedSharding(mesh, row_spec)
    return NamedSharding(mesh, p_spec)


def train_state_shardings(cfg: ModelConfig, mesh: Mesh, state_abstract):
    """TrainState sharding: params rules; opt state mirrors params
    (row-indexed factored/quantized leaves follow the param's dim-0 axis,
    see :func:`_state_leaf_sharding`); head generator state replicated
    (it is small and read-everywhere)."""
    from repro.optim.optimizers import _is_state_leaf, _state_leaves
    from repro.train.state import TrainState

    p_sh = params_shardings(cfg, mesh, state_abstract.params)

    def opt_mirror(opt_abs):
        flat_p, treedef = jax.tree_util.tree_flatten_with_path(
            state_abstract.params)
        specs = [param_spec(cfg, mesh, path, leaf) for path, leaf in flat_p]
        n = len(flat_p)

        def map_state_tree(tree):
            if tree is None:
                return None
            leaves = _state_leaves(tree, n)
            return jax.tree_util.tree_unflatten(
                treedef, [_state_leaf_sharding(mesh, sp, s)
                          for sp, s in zip(specs, leaves)])

        return type(opt_abs)(
            step=NamedSharding(mesh, P()),
            mu=map_state_tree(opt_abs.mu),
            nu=map_state_tree(opt_abs.nu),
            last=map_state_tree(getattr(opt_abs, "last", None)))

    return TrainState(
        step=NamedSharding(mesh, P()),
        params=p_sh,
        opt_state=opt_mirror(state_abstract.opt_state),
        head_state=replicated(mesh, state_abstract.head_state),
        gen_fit_step=NamedSharding(mesh, P()),
        snr_ewma=NamedSharding(mesh, P()),
        snr_ref=NamedSharding(mesh, P()))
