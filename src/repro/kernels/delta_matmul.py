"""Fused "base + per-slot delta" matmul — Pallas TPU kernel.

The personalized-delta serving path (serve/engine.py, DESIGN.md §9)
batch-decodes B slots against ONE shared weight ``w`` while a small
capacity-C entry table carries the per-slot selected-layer deltas active at
the current layer:

    y[b] = x[b] @ w  +  Σ_{e : slots[e] == b}  x[b] @ dw[e]

Entries with ``slots[e] == -1`` are padding (skipped).  The serving
invariant is ≤ 1 entry per (slot, layer) — a client selects a layer at
most once — so per output row there is at most one correction term.

Why this shape wins over per-user dense params: the base product streams
``w`` ONCE for the whole batch (B·d·f MACs at full weight reuse), and the
correction streams only the C ≤ B delta slabs, so per-step weight traffic
is (1 + C)·d·f instead of the B·d·f of B private weight copies.  At the
paper's operating point (a few selected layers of L) C ≪ B.

The kernel tiles both the output (f) and the contraction (d) axes, so a
(d, f) weight of any width streams through VMEM in (block_d, block_f)
slabs; the entry's row of ``x`` and of the f32 accumulator are addressed
with ``pl.ds`` ref slices at the slot id read from SMEM.  The pure-jnp
fallback computes the same sums in one contraction per term, so the two
agree to f32 accumulation order (tolerances in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DW_VMEM_BUDGET = 8 << 20      # bytes of double-buffered delta slabs per step


def _tile(n: int, target: int) -> tuple[int, int]:
    """(block, padded n) for one axis: the whole axis when it is at most
    ``target``, else the largest 128-multiple ≤ target dividing n rounded
    up to 128 lanes."""
    if n <= target:
        return n, n
    padded = -(-n // LANES) * LANES
    q = padded // LANES
    k = max(k for k in range(1, max(target // LANES, 1) + 1) if q % k == 0)
    return k * LANES, padded


def base_delta_matmul_2d_jnp(x: jax.Array, w: jax.Array, dw: jax.Array,
                             slots: jax.Array) -> jax.Array:
    """Pure-jnp fallback for :func:`base_delta_matmul_2d` — the off-TPU
    serving hot path.  x: (B, d); w: (d, f); dw: (C, d, f); slots: (C,)
    int32 with -1 padding.  Returns (B, f) in x.dtype."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    slots = slots.astype(jnp.int32)
    safe = jnp.maximum(slots, 0)
    corr = jnp.einsum("cd,cdf->cf", x[safe].astype(jnp.float32),
                      dw.astype(jnp.float32))
    corr = corr * (slots >= 0).astype(jnp.float32)[:, None]
    return y.at[safe].add(corr).astype(x.dtype)


def _delta_mm_kernel(slots_s, x_ref, w_ref, dw_ref, out_ref, acc_ref, xf_ref,
                     *, C: int, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)
    # an f32 copy of x: a packed bf16 tile cannot be sliced at a dynamic row
    xf_ref[...] = x_ref[...].astype(jnp.float32)
    for e in range(C):
        s = slots_s[e]

        @pl.when(s >= 0)
        def _correct():
            row = xf_ref[pl.ds(s, 1), :]
            acc_ref[pl.ds(s, 1), :] += jnp.dot(
                row, dw_ref[e].astype(jnp.float32),
                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _fin():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def base_delta_matmul_2d(x: jax.Array, w: jax.Array, dw: jax.Array,
                         slots: jax.Array, *, block_f: int = 512,
                         interpret: bool = False) -> jax.Array:
    """x: (B, d); w: (d, f); dw: (C, d, f); slots: (C,) int32 (-1 = pad).

    Grid (f-blocks, d-blocks) with d innermost: the (B, block_f) f32
    accumulator stays in VMEM across the contraction, the entry slot ids
    are scalar-prefetched into SMEM.  ``block_d`` is sized so the C
    double-buffered delta slabs fit ``DW_VMEM_BUDGET``.  Returns (B, f) in
    x.dtype.
    """
    B, d = x.shape
    f = w.shape[1]
    C = dw.shape[0]
    bf, fp = _tile(f, block_f)
    dw_item = jnp.dtype(dw.dtype).itemsize
    d_target = max(LANES, DW_VMEM_BUDGET // (2 * max(C, 1) * dw_item * bf))
    bd, dp = _tile(d, d_target)
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))
    if dp != d or fp != f:
        w = jnp.pad(w, ((0, dp - d), (0, fp - f)))
        dw = jnp.pad(dw, ((0, 0), (0, dp - d), (0, fp - f)))
    nk = dp // bd
    blocks = (B * bd * x.dtype.itemsize + bd * bf * w.dtype.itemsize
              + C * bd * bf * dw_item + B * bf * x.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(fp // bf, nk),
        in_specs=[
            pl.BlockSpec((B, bd), lambda j, k, *_: (0, k)),
            pl.BlockSpec((bd, bf), lambda j, k, *_: (k, j)),
            pl.BlockSpec((C, bd, bf), lambda j, k, *_: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((B, bf), lambda j, k, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((B, bf), jnp.float32),
                        pltpu.VMEM((B, bd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_delta_mm_kernel, C=C, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, fp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * blocks + B * (bf + bd) * 4 + (4 << 20)),
        interpret=interpret,
    )(slots.astype(jnp.int32), x, w, dw)
    return out[:, :f] if fp != f else out
