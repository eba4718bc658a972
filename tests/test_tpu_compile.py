"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks what the kernels compute; it
cannot see what the chip's compiler refuses: blocks not aligned to the
(8, 128) / (16, 128) tiling, unsupported primitives, more VMEM than a
kernel may use.  These tests lower each kernel at the widths its phase of
``chip_smoke.py`` runs and compile it for a v5e described without a chip
attached, so nothing executes.  The topology is described inside a
fixture, never at import, because only one process at a time may load the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# XLM-R base's MLP leaf stacked over its 12 layers: (12, 768·3072)
COHORT, L, F = 4, 12, 768 * 3072


def test_masked_sgd_update_vmapped_bf16(one_chip):
    """The τ-scan's apply step, vmapped over the cohort as core/client.py
    calls it: one kernel launch for the whole cohort."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def apply(p, g, m):
        return ops.masked_sgd_update(p, g, m, 0.01, mode="pallas",
                                     interpret=False)

    text = _compiled_text(jax.vmap(apply, in_axes=(None, 0, 0)),
                          sds((L, F), jnp.bfloat16),
                          sds((COHORT, L, F), jnp.bfloat16),
                          sds((COHORT, L), jnp.float32))
    assert "tpu_custom_call" in text


def test_layer_grad_norms_vmapped_bf16(one_chip):
    """The probe's per-layer reduction over the same leaf, vmapped over the
    cohort as the vectorized probe calls it."""
    def norms(g):
        return ops.layer_grad_norms({"w": g}, mode="pallas", interpret=False)

    g = jax.ShapeDtypeStruct((COHORT, L, F), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(jax.vmap(norms), g)


# TinyLlama-1.1B decode projections: (d_in, d_out)
TINYLLAMA_PROJ = [(2048, 2048),    # q / o
                  (2048, 256),     # k / v (4 kv heads of 64)
                  (2048, 5632),    # gate / up
                  (5632, 2048)]    # down


@pytest.mark.parametrize("d,f", TINYLLAMA_PROJ)
def test_base_delta_matmul_tinyllama(one_chip, d, f):
    """The serving projection with B=4 slots and C=8 f32 overlay entries
    over a bf16 base weight, as serve/engine.py's overlay holds them."""
    B, C = 4, 8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def proj(x, w, dw, slots):
        return ops.base_delta_matmul(x, w, dw, slots, mode="pallas",
                                     interpret=False)

    text = _compiled_text(proj, sds((B, 1, d), jnp.bfloat16),
                          sds((d, f), jnp.bfloat16),
                          sds((C, d, f), jnp.float32),
                          sds((C,), jnp.int32))
    assert "tpu_custom_call" in text
