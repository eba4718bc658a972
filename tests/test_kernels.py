"""Pallas kernel sweeps: shapes × dtypes vs the pure-jnp oracles in ref.py.

All kernels run in interpret mode on CPU (the kernel bodies execute exactly
as they would on TPU, minus the hardware tiling)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.layer_grad_norm import layer_sq_norms_2d
from repro.kernels.masked_update import masked_sgd_update_2d
from repro.kernels.ssd_scan import ssd_scan

ATTN_CASES = [
    # (B, H, K, S, D, causal, window, dtype)
    (2, 4, 2, 128, 64, True, 0, jnp.float32),
    (1, 4, 4, 256, 64, False, 0, jnp.float32),
    (2, 8, 2, 128, 128, True, 64, jnp.float32),
    (1, 2, 1, 256, 64, True, 96, jnp.float32),
    (1, 4, 2, 128, 64, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,K,S,D,causal,window,dtype", ATTN_CASES)
def test_flash_attention_sweep(B, H, K, S, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, K, S, D), dtype)
    v = jax.random.normal(ks[2], (B, K, S, D), dtype)
    out = fa_raw(q, k, v, causal=causal, window=window, block_q=64,
                 block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


SSD_CASES = [
    # (BH, S, P, N, chunk, dtype)
    (4, 128, 64, 32, 32, jnp.float32),
    (2, 256, 32, 64, 64, jnp.float32),
    (6, 64, 64, 16, 16, jnp.float32),
    (2, 128, 64, 32, 128, jnp.float32),   # single chunk
    (2, 128, 32, 32, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("BH,S,P,N,chunk,dtype", SSD_CASES)
def test_ssd_scan_sweep(BH, S, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (BH, S, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (BH, S))).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[2], (BH,), minval=-1.0, maxval=0.5))
    Bm = (jax.random.normal(ks[3], (BH, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (BH, S, N)) * 0.5).astype(dtype)
    D = jnp.ones((BH,))
    y = ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, A, Bm, Cm, D)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


NORM_CASES = [(1, 7), (3, 4096), (8, 5000), (2, 17)]


@pytest.mark.parametrize("L,F", NORM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_sq_norms_sweep(L, F, dtype):
    g = jax.random.normal(jax.random.PRNGKey(2), (L, F), dtype)
    out = layer_sq_norms_2d(g, block_rows=16, interpret=True)
    want = ref.layer_sq_norms_ref(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("L,F", [(4, 64), (6, 1000), (1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_update_sweep(L, F, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    p = jax.random.normal(ks[0], (L, F), dtype)
    g = jax.random.normal(ks[1], (L, F), dtype)
    mask = (jax.random.uniform(ks[2], (L,)) > 0.5).astype(jnp.float32)
    out = masked_sgd_update_2d(p, g, mask, 0.1, block_rows=16, interpret=True)
    want = ref.masked_sgd_update_ref(p, g, mask, 0.1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # masked rows unchanged exactly
    for l in range(L):
        if mask[l] == 0:
            np.testing.assert_array_equal(np.asarray(out[l]), np.asarray(p[l]))


# ---------------------------------------------------------------------------
# Kernel ⇄ jnp-fallback pins for the mask-aware hot path (DESIGN.md §7).
# The FL hot paths call the kernels through dispatching wrappers (mode
# "pallas" on TPU, the pure-jnp fallback elsewhere); these tests pin the two
# implementations bit-identical under like-for-like jit compilation, so any
# kernel/core drift fails CI (the examples smoke job runs this file's
# masked_update/grad_norm oracles).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L,F", NORM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_sq_norms_jnp_fallback_bit_identical(L, F, dtype):
    """The fallback replays the kernel's per-block accumulation order, so
    the results agree bit-for-bit (not just allclose)."""
    from repro.kernels.layer_grad_norm import layer_sq_norms_2d_jnp
    g = jax.random.normal(jax.random.PRNGKey(2), (L, F), dtype)
    kernel = layer_sq_norms_2d(g, block_rows=16, interpret=True)
    fallback = jax.jit(lambda g: layer_sq_norms_2d_jnp(g, block_rows=16))(g)
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(fallback))


@pytest.mark.parametrize("L,F", [(4, 64), (6, 1000), (3, 5000), (1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_update_jnp_fallback_bit_identical(L, F, dtype):
    """Same elementwise expression, same fusion: kernel (interpret) and the
    jitted fallback produce bit-identical updates."""
    from repro.kernels.masked_update import masked_sgd_update_2d_jnp
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    p = jax.random.normal(ks[0], (L, F), dtype)
    g = jax.random.normal(ks[1], (L, F), dtype)
    mask = (jax.random.uniform(ks[2], (L,)) > 0.5).astype(jnp.float32)
    kernel = masked_sgd_update_2d(p, g, mask, 0.1, block_rows=16,
                                  interpret=True)
    fallback = jax.jit(masked_sgd_update_2d_jnp)(p, g, mask, 0.1)
    np.testing.assert_array_equal(np.asarray(kernel, np.float32),
                                  np.asarray(fallback, np.float32))


@pytest.mark.parametrize("B,H,K,S,D,causal,window,dtype", ATTN_CASES)
def test_flash_attention_jnp_fallback_bit_identical(B, H, K, S, D, causal,
                                                    window, dtype):
    """The fallback replays the kernel's blocked streaming softmax (same
    block shapes, same f32 running max/normaliser), so kernel (interpret)
    and fallback agree bit-for-bit — not just allclose like the dense
    ref.py oracle."""
    from repro.kernels.flash_attention import flash_attention_jnp
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, K, S, D), dtype)
    v = jax.random.normal(ks[2], (B, K, S, D), dtype)
    kernel = fa_raw(q, k, v, causal=causal, window=window, block_q=64,
                    block_k=64, interpret=True)
    fallback = flash_attention_jnp(q, k, v, causal=causal, window=window,
                                   block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(kernel, np.float32),
                                  np.asarray(fallback, np.float32))


@pytest.mark.parametrize("BH,S,P,N,chunk,dtype", SSD_CASES)
def test_ssd_scan_jnp_fallback_bit_identical(BH, S, P, N, chunk, dtype):
    """The fallback replays the kernel's chunked semiseparable scan (same
    chunking, same carried (P,N) f32 state), so kernel (interpret) and
    fallback agree bit-for-bit."""
    from repro.kernels.ssd_scan import ssd_scan_jnp
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (BH, S, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (BH, S))).astype(dtype)
    A = -jnp.exp(jax.random.uniform(ks[2], (BH,), minval=-1.0, maxval=0.5))
    Bm = (jax.random.normal(ks[3], (BH, S, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (BH, S, N)) * 0.5).astype(dtype)
    D = jnp.ones((BH,))
    kernel = ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    fallback = ssd_scan_jnp(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(kernel, np.float32),
                                  np.asarray(fallback, np.float32))


def test_ops_attention_ssd_mode_dispatch():
    """The ops-layer wrappers route mode='jnp' to the fallbacks and
    mode='pallas' to the kernels; both paths agree on model-layout
    inputs (GQA attention + grouped SSD)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 8)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    a_p = ops.flash_attention(q, k, v, window=32, interpret=True,
                              mode="pallas")
    a_j = ops.flash_attention(q, k, v, window=32, mode="jnp")
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_j))

    x = jax.random.normal(ks[3], (2, 64, 4, 32))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (2, 64, 4)))
    A_log = jax.random.uniform(ks[5], (4,), minval=-1.0, maxval=1.0)
    Bm = jax.random.normal(ks[6], (2, 64, 2, 16)) * 0.5
    Cm = jax.random.normal(ks[7], (2, 64, 2, 16)) * 0.5
    D = jnp.ones((4,))
    s_p = ops.ssd(x, dt, A_log, Bm, Cm, D, chunk=32, interpret=True,
                  mode="pallas")
    s_j = ops.ssd(x, dt, A_log, Bm, Cm, D, chunk=32, mode="jnp")
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_j))


DELTA_MM_CASES = [
    # (B, d, f, C, block_f, dtype): one layer, its last entry free
    (4, 64, 128, 2, 512, jnp.float32),
    (6, 128, 512, 4, 128, jnp.float32),
    (3, 32, 100, 1, 64, jnp.float32),      # f no 128-multiple: whole
    (8, 4096, 128, 8, 128, jnp.float32),   # d tiled: two contraction steps
    (4, 64, 256, 3, 512, jnp.bfloat16),
    (2, 16, 48, 2, 32, jnp.bfloat16),
]
# stacked overlays: (..., L, layer, live, transposed) — the kernel reads
# layer ``layer`` of (L, d, f) / (L, C, d, f) stacks whose other layers hold
# other data, or of (L, f, d) / (L, C, f, d) ones when ``transposed`` (the
# overlay's storage of a matrix the TPU lays out d-minor); ``live`` is that
# layer's owner pattern: no live entry, one, all, or live entries behind
# free ones ("late")
DELTA_MM_STACKED = [
    (4, 64, 128, 4, 512, jnp.float32, 3, 0, "none", False),
    (4, 64, 256, 4, 128, jnp.float32, 3, 2, "one", False),
    (6, 128, 512, 4, 128, jnp.float32, 2, 1, "all", False),
    (6, 128, 256, 6, 128, jnp.bfloat16, 3, 1, "late", False),
    (5, 200, 100, 3, 64, jnp.float32, 2, 1, "late", False),   # whole axes
    (4, 100, 64, 3, 512, jnp.float32, 2, 1, "late", True),
    (6, 256, 512, 4, 128, jnp.bfloat16, 2, 0, "all", True),   # both tiled
    (8, 960, 960, 8, 512, jnp.bfloat16, 2, 1, "late", False),  # SmolLM q / o
    (8, 960, 320, 8, 512, jnp.bfloat16, 2, 0, "one", True),    # k / v
    (4, 2560, 960, 4, 512, jnp.bfloat16, 2, 1, "all", True),   # down
]


def _delta_mm_params():
    def case_id(c):
        head = "-".join(str(v) for v in c[:5]) + "-" + c[5].__name__
        if len(c) == 6:
            return head
        return head + "-L{}-l{}-{}".format(*c[6:9]) + ("-T" if c[9] else "")
    return [pytest.param(*c[:6], c[6:] or None, id=case_id(c))
            for c in DELTA_MM_CASES + DELTA_MM_STACKED]


def _delta_mm_inputs(B, d, f, C, dtype, stack=None, seed=5):
    """x (B, d), w (L, d, f), dw (L, C, d, f), slots (L, C), layer.  The
    serving invariant holds in every layer: ≤ 1 entry per slot."""
    L, layer, live = (stack or (1, 0, None))[:3]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (B, d), dtype)
    w = jax.random.normal(ks[1], (L, d, f), dtype)
    dw = (jax.random.normal(ks[2], (L, C, d, f)) * 0.1).astype(dtype)
    rs = np.random.RandomState(seed)
    owners = rs.permutation(B)[:C].astype(np.int32)
    if live is None and C > 1:
        owners[-1] = -1
    elif live == "none":
        owners[:] = -1
    elif live == "one":
        owners[np.arange(C) != C // 2] = -1
    elif live == "late":
        owners[:C // 2] = -1
    slots = np.stack([np.where(rs.rand(C) < 0.5, -1,
                               rs.permutation(B)[:C]).astype(np.int32)
                      for _ in range(L)])
    slots[layer] = owners
    return x, w, dw, jnp.asarray(slots), jnp.int32(layer)


def _transposed(stack) -> bool:
    return bool(stack and stack[3])


def _stored(w, dw, stack):
    """The stacks as the kernel reads them: each matrix as (f, d) when the
    case stores it transposed."""
    if _transposed(stack):
        return jnp.swapaxes(w, 1, 2), jnp.swapaxes(dw, 2, 3)
    return w, dw


@pytest.mark.parametrize("B,d,f,C,block_f,dtype,stack", _delta_mm_params())
def test_base_delta_matmul_sweep(B, d, f, C, block_f, dtype, stack):
    """Fused base+delta GEMM vs the unfused oracle: y[b] = x[b]@W[l], plus
    x[b]@dw[l, e] for the entry e owned by slot b (DESIGN.md §9)."""
    from repro.kernels.delta_matmul import base_delta_matmul_2d
    x, w, dw, slots, layer = _delta_mm_inputs(B, d, f, C, dtype, stack)
    out = base_delta_matmul_2d(x, *_stored(w, dw, stack), slots, layer,
                               transposed=_transposed(stack),
                               block_f=block_f, interpret=True)
    l = int(layer)
    x32 = np.asarray(x, np.float32)
    want = x32 @ np.asarray(w[l], np.float32)
    for e, s in enumerate(np.asarray(slots[l])):
        if s >= 0:
            want[s] += x32[s] @ np.asarray(dw[l, e], np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,d,f,C,block_f,dtype,stack", _delta_mm_params())
def test_base_delta_matmul_jnp_fallback_bit_identical(B, d, f, C, block_f,
                                                      dtype, stack):
    """Kernel (interpret) and the jitted jnp fallback compute the same
    sums; the kernel accumulates them over (block_d, block_f) tiles and
    live entries only, the fallback in one contraction per term over all C
    entries.  So they agree to f32 accumulation order: within 1e-5
    relative in f32, and within one bf16 ulp (2^-7 relative) where the f32
    result is rounded to bf16."""
    from repro.kernels.delta_matmul import (base_delta_matmul_2d,
                                            base_delta_matmul_2d_jnp)
    x, w, dw, slots, layer = _delta_mm_inputs(B, d, f, C, dtype, stack)
    w, dw = _stored(w, dw, stack)
    t = _transposed(stack)
    kernel = base_delta_matmul_2d(x, w, dw, slots, layer, transposed=t,
                                  block_f=block_f, interpret=True)
    fallback = jax.jit(functools.partial(
        base_delta_matmul_2d_jnp, transposed=t))(x, w, dw, slots, layer)
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(kernel, np.float32),
                               np.asarray(fallback, np.float32),
                               rtol=tol, atol=tol * float(jnp.sqrt(d)))


def test_delta_matmul_d_minor_matches_the_tpu_layout():
    """The overlay stores transposed exactly the matrices whose default
    TPU layout is d-minor (read off a compile for a described v5e): SmolLM
    k / v and down, not q / o / gate-up; no TinyLlama-1.1B projection."""
    from repro.kernels.delta_matmul import d_minor
    smollm = {(960, 960): False, (960, 320): True, (960, 5120): False,
              (2560, 960): True}
    tinyllama = {(2048, 2048): False, (2048, 256): False,
                 (2048, 5632): False, (5632, 2048): False}
    for (d, f), want in {**smollm, **tinyllama}.items():
        assert d_minor(d, f) == want, (d, f)
        assert not (d_minor(d, f) and d_minor(f, d))


def test_delta_matmul_live_first():
    """Entries are visited live-first; past the live ones the fetch index
    repeats the last live entry's, so the pipeline reads nothing more."""
    from repro.kernels.delta_matmul import live_first
    slots = jnp.asarray([[-1, 3, -1, 0], [-1, -1, -1, -1], [2, 1, 0, 5]],
                        jnp.int32)
    fetch, owner = live_first(slots)
    np.testing.assert_array_equal(
        np.asarray(fetch).reshape(3, 4), [[1, 3, 3, 3], [0, 0, 0, 0],
                                          [0, 1, 2, 3]])
    np.testing.assert_array_equal(
        np.asarray(owner).reshape(3, 4), [[3, 0, -1, -1], [-1] * 4,
                                          [2, 1, 0, 5]])


def test_ops_base_delta_matmul_dispatch():
    """The ops-layer wrapper: (B,1,d) decode activations route through the
    2-D path; empty slot table degenerates to the plain GEMM exactly."""
    x, w, dw, slots, layer = _delta_mm_inputs(3, 16, 32, 2, jnp.float32,
                                              (2, 1, None, False))
    out3 = ops.base_delta_matmul(x[:, None], w, dw, slots, layer, mode="jnp")
    out2 = ops.base_delta_matmul(x, w, dw, slots, layer, mode="jnp")
    np.testing.assert_array_equal(np.asarray(out3[:, 0]), np.asarray(out2))
    empty = ops.base_delta_matmul(x, w, dw, jnp.full((2, 2), -1, jnp.int32),
                                  layer, mode="jnp")
    np.testing.assert_allclose(
        np.asarray(empty),
        np.asarray(x, np.float32) @ np.asarray(w[1], np.float32), atol=1e-5)


def _small_world():
    from repro.configs.base import RuntimeConfig, get_arch, reduced
    from repro.models.model import Model
    cfg = reduced(get_arch("tinyllama_1_1b"), n_layers=3, d_model=64)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size)}
    return model, params, batch


def test_hot_path_masked_sgd_site_kernel_parity():
    """The masked engine's apply-step call site (client.masked_suffix_sgd):
    Pallas kernel (interpret) vs the jnp fallback it runs off-TPU."""
    from repro.core.client import masked_suffix_sgd
    from repro.models.model import trainable_slice
    model, params, batch = _small_world()
    cfg = model.cfg
    cut = 1
    tr = trainable_slice(params, cut, cfg)
    g = jax.grad(lambda t: model.loss(params, batch, trainable=t,
                                      cut=cut))(tr)
    mask = jnp.asarray([0.0, 1.0, 0.0], jnp.float32)
    out_k = masked_suffix_sgd(tr, g, mask, 0.1, cut, cfg, mode="pallas")
    out_j = jax.jit(lambda tr, g: masked_suffix_sgd(tr, g, mask, 0.1, cut,
                                                    cfg, mode="jnp"))(tr, g)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out_k, out_j)
    # masked rows (mask 0 above the cut) unchanged exactly
    jax.tree.map(lambda t, o: np.testing.assert_array_equal(
        np.asarray(t[-1]), np.asarray(o[-1])), tr, out_j)


def test_hot_path_probe_reduction_kernel_parity():
    """The probe's grad-norm reduction call site (masks.per_layer_sq_norms
    routed through ops.layer_grad_norms): kernel vs jnp fallback, pinned
    bit-identical on a real gradient tree."""
    from repro.core.masks import per_layer_sq_norms
    model, params, batch = _small_world()
    g = jax.grad(model.loss)(params, batch)
    out_k = per_layer_sq_norms(g, model.cfg, mode="pallas", interpret=True)
    out_j = jax.jit(lambda g: per_layer_sq_norms(g, model.cfg,
                                                 mode="jnp"))(g)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_j))


def test_ops_layer_grad_norms_matches_core():
    """The fused kernel equals core.masks.per_layer_sq_norms on a real tree."""
    from repro.configs.base import RuntimeConfig, get_arch, reduced
    from repro.core.masks import per_layer_sq_norms
    from repro.models.model import Model
    cfg = reduced(get_arch("tinyllama_1_1b"), n_layers=3, d_model=64)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size)}
    g = jax.grad(model.loss)(params, batch)
    want = np.asarray(per_layer_sq_norms(g, cfg))
    got = np.asarray(ops.layer_grad_norms(g["blocks"], interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_ops_ssd_matches_model_path():
    from repro.models.ssd import ssd_chunked
    b, s, h, p, g, n = 2, 64, 4, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A_log = jax.random.uniform(ks[2], (h,), minval=-1.0, maxval=1.0)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.5
    D = jnp.ones((h,))
    y_k = ops.ssd(x, dt, A_log, Bm, Cm, D, chunk=32, interpret=True)
    y_j, _ = ssd_chunked(x, dt, A_log, Bm, Cm, D, 32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_j), atol=1e-4)
