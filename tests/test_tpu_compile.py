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
import re

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
    over a bf16 base weight, read in place from the 22-layer stacks as
    serve/engine.py's overlay and the decode scan hold them."""
    B, C, L = 4, 8, 22

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def proj(x, w, dw, slots, layer):
        return ops.base_delta_matmul(x, w, dw, slots, layer, mode="pallas",
                                     interpret=False)

    text = _compiled_text(proj, sds((B, 1, d), jnp.bfloat16),
                          sds((L, d, f), jnp.bfloat16),
                          sds((L, C, d, f), jnp.float32),
                          sds((L, C), jnp.int32), sds((), jnp.int32))
    assert "tpu_custom_call" in text


def _result_shape(line: str) -> str:
    """``f32[8,960,960]`` of an HLO instruction line, else ''."""
    m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])", line)
    return m.group(1) if m else ""


def test_delta_decode_smollm_reads_overlay_in_place(one_chip, monkeypatch):
    """The whole delta decode step at SmolLM-360M widths (d 960, no
    multiple of 128), B 8 slots over an overlay of C 8 per layer, in the
    layouts the chip gives them by default: the kernel reads the stacked
    overlay and base weights in place, so the optimised program pads
    nothing for it, slices no (C, d, f) f32 slab and copies no stack."""
    from repro.configs.base import RuntimeConfig, get_arch
    from repro.models.model import Model, delta_projections
    from repro.serve.engine import overlay_shapes

    # the model picks the kernel by the platform, which here is the CPU
    monkeypatch.setattr(ops, "_resolve_mode", lambda mode, interpret: "pallas")
    monkeypatch.setattr(ops, "_auto_interpret", lambda interpret: False)
    cfg = get_arch("smollm-360m")
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=32))
    B, C, S, L = 8, 8, 1281, cfg.n_layers

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    shapes = overlay_shapes(cfg)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(B, S, per_slot=True)))
    delta = on_chip({
        "slots": jax.ShapeDtypeStruct((L, C), jnp.int32),
        "leaves": {n: jax.ShapeDtypeStruct((L, C) + shp, jnp.float32)
                   for n, shp in shapes.items()}})
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def decode(params, tokens, pos, cache, delta):
        return model.decode_step(params, tokens, pos, cache, delta=delta)

    text = _compiled_text(decode, params, vec, vec, cache, delta)
    assert text.count("tpu_custom_call") >= 6     # one a projection
    lines = text.splitlines()
    # the rotary embedding's concatenate lowers to pads of its own
    pads = [ln for ln in lines
            if re.search(r"\bpad\(", ln) and "delta_matmul" in ln]
    assert not pads, pads[:3]
    slabs = {f"f32[{C},{a},{b}]" for shp in shapes.values() if len(shp) == 2
             for a, b in (shp, shp[::-1])}
    sliced = [ln for ln in lines
              if "dynamic-slice" in ln and _result_shape(ln) in slabs]
    assert not sliced, sliced[:3]
    bases = delta_projections(params["blocks"], delta["leaves"])
    stacks = ({f"f32[{L},{C},{a},{b}]" for shp in shapes.values()
               if len(shp) == 2 for a, b in (shp, shp[::-1])}
              | {f"bf16[{L},{a},{b}]" for w in bases.values()
                 for a, b in (w.shape[1:], w.shape[:0:-1])})
    copied = [ln for ln in lines
              if re.search(r"\bcopy\(", ln) and _result_shape(ln) in stacks]
    assert not copied, copied[:3]
