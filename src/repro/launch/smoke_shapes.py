"""What the chip smoke (``chip_smoke.py``) runs, in one place: its models,
its step sizes, and the shapes each Pallas kernel sees there, read from the
models' configs. ``tests/test_tpu_compile.py`` compiles the kernels at
these shapes for a TPU v5e without a chip; the smoke's kernels phase runs
them on the chip against their references.
"""
from __future__ import annotations

from repro import configs as cfg_lib
from repro.core import tree as tree_lib

TRAIN_ARCH = "mamba2-370m"
SERVE_ARCH = "hymba-1.5b"
# A 128-wide attention head, as in the 7B-class configs.
WIDE_HEAD_ARCH = "deepseek-7b"
BATCH, SEQ = 8, 1024           # training step: BATCH x SEQ tokens
N_NEG = 1                      # launch/train's default --n-neg
BEAM = 16                      # serving's --topk-beam
FIT_TOKENS = 8192              # launch/train fits the generator on these


def head_shape(arch: str):
    """(rows, K) of the model's output embedding: padded vocab x d_model."""
    cfg = cfg_lib.get_config(arch)
    return cfg.padded_vocab, cfg.d_model


def tree_shape(arch: str):
    """(nodes, k) of the model's generator tree over its vocabulary."""
    cfg = cfg_lib.get_config(arch)
    return tree_lib.padded_size(cfg.vocab_size) - 1, cfg.gen_feature_dim


def sampled_loss_shape():
    """(C, K, T, m): the fused head loss in one training step."""
    c, k = head_shape(TRAIN_ARCH)
    return c, k, BATCH * SEQ, 1 + N_NEG


def gather_scores_shapes():
    """(C, K) per model whose beam candidates the kernel scores."""
    return [head_shape(SERVE_ARCH), head_shape(TRAIN_ARCH)]


def flash_attention_shapes():
    """(head_dim, window) pairs: the serving model's local attention and a
    128-wide head at the same window."""
    serve = cfg_lib.get_config(SERVE_ARCH)
    wide = cfg_lib.get_config(WIDE_HEAD_ARCH)
    return [(serve.resolved_head_dim, serve.window_size),
            (wide.resolved_head_dim, serve.window_size)]


def segment_stats_shapes():
    """(rows, width, segments) of the generator fit's two reductions at
    k = the tree width: gradient rows (k + 1 wide) over the 1024 nodes
    of level 10, Hessian rows ((k + 1)^2 wide) over the 4 of level 2."""
    _, k = tree_shape(TRAIN_ARCH)
    return [(FIT_TOKENS, k + 1, 1024), (FIT_TOKENS, (k + 1) ** 2, 4)]
