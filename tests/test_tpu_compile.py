"""Compile every Pallas kernel for a TPU v5e at the widths the chip smoke
(``chip_smoke.py``) runs, with no chip attached: the TPU compiler is
installed, and it refuses here what Mosaic would refuse on the chip
(tiling alignment, VMEM limits). Nothing runs, so these say nothing about
results; interpret-mode tests in test_kernels.py cover those.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as cfg_lib
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gather_scores import gather_scores
from repro.kernels.sampled_loss import SAMPLED_KINDS, sampled_head_loss
from repro.kernels.segment_scores import segment_stats
from repro.kernels.ssd_intra import ssd_intra
from repro.kernels.tree_logprob import tree_logprob_all
from repro.launch import smoke_shapes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of these compiles.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_on(one_chip, no_compile_cache):
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled
    return compile_


@pytest.mark.parametrize("kind", SAMPLED_KINDS)
def test_sampled_loss(compile_on, kind):
    """The train step's fused head loss (mamba2-370m: K=1024, m=2 over
    8192 tokens)."""
    c, k, t, m = smoke_shapes.sampled_loss_shape()
    compile_on(
        lambda w, b, h, ids, lp: sampled_head_loss(
            w, b, h, ids, lp, kind=kind, num_labels=c, reg=1e-4,
            softcap=30.0),
        ((c, k), jnp.float32), ((c,), jnp.float32), ((t, k), jnp.float32),
        ((t, m), jnp.int32), ((t, m), jnp.float32))


@pytest.mark.parametrize("model,t,table", [(0, 8, jnp.float32),
                                           (1, 8, jnp.float32),
                                           (1, 256, jnp.bfloat16)])
def test_gather_scores(compile_on, model, t, table):
    """Beam candidate scoring: hymba (K=1600) and mamba2 (K=1024)."""
    c, k = smoke_shapes.gather_scores_shapes()[model]
    compile_on(gather_scores, ((c, k), table), ((c,), jnp.float32),
               ((t, k), jnp.bfloat16), ((t, smoke_shapes.BEAM), jnp.int32))


@pytest.mark.parametrize("arch", [smoke_shapes.TRAIN_ARCH,
                                  smoke_shapes.SERVE_ARCH])
@pytest.mark.parametrize("bsz", [8, 128])
def test_tree_logprob(compile_on, arch, bsz):
    """Dense Eq. 5 tree log-probs, k=32 over each model's padded leaves
    (2^16 for mamba2, 2^15 for hymba)."""
    nodes, k = smoke_shapes.tree_shape(arch)
    compile_on(tree_logprob_all, ((nodes, k), jnp.float32),
               ((nodes,), jnp.float32), ((bsz, k), jnp.float32))


@pytest.mark.parametrize("which", [0, 1])
def test_flash_attention(compile_on, which):
    """Sliding-window attention at 128-blocks: head_dim 64 (hymba) and
    128, hymba's window."""
    hd, window = smoke_shapes.flash_attention_shapes()[which]
    shape = ((1, 4, 1024, hd), jnp.bfloat16)
    compile_on(lambda q, k, v: flash_attention(q, k, v, window=window),
               shape, shape, shape)


@pytest.mark.parametrize("which", [0, 1])
def test_segment_stats(compile_on, which):
    """Generator-fit reductions at k=32 over 8192 tokens: gradient rows
    (d = k+1) at level 10, Hessian rows (d = (k+1)^2) at level 2."""
    n, d, s = smoke_shapes.segment_stats_shapes()[which]
    compile_on(lambda v, g: segment_stats(v, g, s),
               ((n, d), jnp.float32), ((n,), jnp.int32))


@pytest.mark.parametrize("arch", ["mamba2-370m", "hymba-1.5b"])
@pytest.mark.parametrize("grad", [False, True])
def test_ssd_intra(compile_on, arch, grad):
    """The SSD intra-chunk block, forward and backward, at the benchmark
    cell's step (16 rows of 2,048 tokens, chunk 256): mamba2-370m's
    32 heads with state 128 and hymba-1.5b's 50 with state 16."""
    cfg = cfg_lib.get_config(arch)
    bsz, nc, q = 16, 2048 // cfg.ssm_chunk, cfg.ssm_chunk
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    fn = ssd_intra
    if grad:
        fn = jax.grad(lambda *a: jnp.sum(ssd_intra(*a) * a[3]),
                      argnums=(0, 1, 2, 3))
    compile_on(fn, ((bsz, nc, q, n), jnp.float32),
               ((bsz, nc, q, n), jnp.float32), ((bsz, nc, q, h), jnp.float32),
               ((bsz, nc, q, h, p), jnp.float32))
