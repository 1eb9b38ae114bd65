"""The Pallas kernels compile for a TPU v5e at real widths.

Each case compiles one kernel with ``interpret=False`` for a described
(not attached) v5e chip, so the TPU compiler's block-shape and memory
rules are checked on every run, chip or no chip. The topology is
described inside a fixture: only the worker that runs these tests loads
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_prune import apply_block_mask, block_norms
from repro.kernels.block_sparse_matmul import block_sparse_matmul
from repro.kernels.stochastic_quant import stochastic_quant_dyn

# 4096x4096: an LM-sized matrix; 4608x512: the paper ResNet's largest conv
# (3x3x512x512) viewed as 2-D, the way the round step feeds the kernels
WIDTHS = [(4096, 4096), (4608, 512)]
# Nemotron-4 340B's embedding (vocab 256000 x d_model 18432, bf16): the
# largest tileable leaf of the repo's datacenter configs that one chip
# holds whole; its 2000 x 144 tile grid outgrows SMEM if held whole
BLOCK_WIDTHS = [pytest.param(WIDTHS[0], jnp.float32, id="mn0"),
                pytest.param(WIDTHS[1], jnp.float32, id="mn1"),
                pytest.param((256000, 18432), jnp.bfloat16, id="mn2")]
TILE = 128
CLIENTS = 30          # the paper's cohort, vmapped as in the round step


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


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("mn", WIDTHS)
def test_quantizer_compiles(one_chip, mn):
    txt = _compiled_text(
        lambda g, r, s: stochastic_quant_dyn(g, r, s[0], s[1], s[2],
                                             interpret=False),
        _spec(one_chip, mn), _spec(one_chip, mn), _spec(one_chip, (3,)))
    assert "tpu_custom_call" in txt


def test_quantizer_compiles_under_client_vmap(one_chip):
    m, n = WIDTHS[1]
    txt = _compiled_text(
        jax.vmap(lambda g, r, s: stochastic_quant_dyn(
            g, r, s[0], s[1], s[2], interpret=False)),
        _spec(one_chip, (CLIENTS, m, n)), _spec(one_chip, (CLIENTS, m, n)),
        _spec(one_chip, (CLIENTS, 3)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mn,dtype", BLOCK_WIDTHS)
def test_block_norms_compiles(one_chip, mn, dtype):
    txt = _compiled_text(lambda w: block_norms(w, interpret=False),
                         _spec(one_chip, mn, dtype))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mn,dtype", BLOCK_WIDTHS)
def test_apply_block_mask_compiles(one_chip, mn, dtype):
    m, n = mn
    txt = _compiled_text(
        lambda w, k: apply_block_mask(w, k, interpret=False),
        _spec(one_chip, mn, dtype),
        _spec(one_chip, (m // TILE, n // TILE), jnp.bool_))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("mn,dtype", BLOCK_WIDTHS)
def test_block_sparse_matmul_compiles(one_chip, mn, dtype):
    k, n = mn
    txt = _compiled_text(
        lambda x, w, msk: block_sparse_matmul(x, w, msk, interpret=False),
        _spec(one_chip, (512, k), dtype), _spec(one_chip, mn, dtype),
        _spec(one_chip, (k // TILE, n // TILE), jnp.bool_))
    assert "tpu_custom_call" in txt


def test_quantizer_compiles_with_128_lanes(one_chip):
    """DeepSeek-V2-Lite's stacked expert leaf (4 layers x 8 experts x 2048,
    1408 = 11 x 128) viewed as 2-D, in 128-lane blocks."""
    mn = (4 * 8 * 2048, 1408)
    txt = _compiled_text(
        lambda g, r, s: stochastic_quant_dyn(g, r, s[0], s[1], s[2],
                                             block=(256, 128),
                                             interpret=False),
        _spec(one_chip, mn), _spec(one_chip, mn), _spec(one_chip, (3,)))
    assert "tpu_custom_call" in txt


def test_expert_products_compile_with_their_gradient(one_chip):
    """The grouped products of one chip's 8 experts (2048 -> 1408) over
    a buffer of 8,192 tokens' top-6 assignments, forward and backward,
    run one client after another under the client vmap: kernels the
    compiler names ``ragged-dot``."""
    from repro.models.moe import expert_products

    def loss(x, w, sizes):
        return jnp.sum(jnp.tanh(expert_products(x, w, sizes)))

    txt = _compiled_text(
        jax.vmap(jax.grad(loss, argnums=(0, 1))),
        _spec(one_chip, (2, 8192 * 6, 2048)),
        _spec(one_chip, (2, 8, 2048, 1408)),
        _spec(one_chip, (2, 8), jnp.int32))
    assert "ragged-dot" in txt and "tpu_custom_call" in txt
