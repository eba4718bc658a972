"""Fused "base + per-slot delta" matmul — Pallas TPU kernel.

The personalized-delta serving path (serve/engine.py, DESIGN.md §9)
batch-decodes B slots against ONE shared weight ``w`` while a small
capacity-C entry table carries the per-slot selected-layer deltas active at
the current layer ``l``:

    y[b] = x[b] @ w[l]  +  Σ_{e : slots[l, e] == b}  x[b] @ dw[l, e]

Entries with ``slots[l, e] == -1`` are free.  The serving invariant is ≤ 1
entry per (slot, layer) — a client selects a layer at most once — so per
output row there is at most one correction term.

Why this shape wins over per-user dense params: the base product streams
``w[l]`` ONCE for the whole batch (B·d·f MACs at full weight reuse), and
the correction streams only the live entries' delta slabs, so per-step
weight traffic is (1 + live)·d·f instead of the B·d·f of B private weight
copies.  At the paper's operating point (a few selected layers of L) most
of the overlay's (layer, entry) places are free and cost nothing.

The kernel reads the stacked operands in place: ``w`` is the (L, d, f)
stack and ``dw`` the (L, C, d, f) overlay leaf, and the layer index is a
scalar-prefetch operand the index maps select with, so the decode scan
slices neither.  Its grid is (f-blocks, d-blocks, entries) with the
entries innermost: the base product runs at entry 0, then each live entry
adds its row's correction.  Entries are visited live-first
(:func:`live_first`), and every free one maps to the block the step before
it fetched, which the pipeline does not fetch again — so only live delta
slabs are read.  An axis is tiled by a 128-multiple that divides it, or
taken whole when none does (d 960), so nothing is padded.  A matrix the
TPU lays out d-minor (:func:`d_minor`) is stored and read as (f, d)
blocks, so no operand is relaid out either.  The pure-jnp
fallback computes the same sums over all C entries of layer ``l`` in one
contraction per term; the two agree to f32 accumulation order (tolerances
in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DW_VMEM_BUDGET = 8 << 20      # bytes of the double-buffered delta slab


def _tile(n: int, target: int) -> int:
    """Block of one axis: the whole axis when it is at most ``target`` or
    no multiple of 128 lanes (a full-extent block), else the largest
    128-multiple ≤ target that divides it."""
    if n <= target or n % LANES:
        return n
    q = n // LANES
    return LANES * max(k for k in range(1, max(target // LANES, 1) + 1)
                       if q % k == 0)


def d_minor(d: int, f: int) -> bool:
    """Whether a TPU lays a (d, f) f32 matrix out d-minor by default: it
    takes whichever of the two orders needs fewer (8, 128) tiles, and
    row-major on a tie.  The overlay stores such a matrix as (f, d), which
    is then row-major, as the kernel reads its operands: read in place as
    (d, f), it would be relaid out whole on every call."""
    def tiles(rows, cols):
        return -(-rows // 8) * -(-cols // LANES)
    return tiles(f, d) < tiles(d, f)


def live_first(slots: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per layer, the overlay entries in live-first order.

    slots: (L, C) int32 owners (-1 = free).  Returns ``(fetch, owner)``,
    each (L·C,) int32 and row-major in (layer, position): position e of
    layer l holds the e-th live entry's overlay index and its owner slot.
    Past the live ones, ``owner`` is -1 and ``fetch`` repeats the last
    live entry's index (0 in a layer with none).
    """
    L, C = slots.shape
    live = slots >= 0
    order = jnp.argsort(~live, axis=1, stable=True).astype(jnp.int32)
    n = live.sum(axis=1, keepdims=True)
    e = jnp.arange(C, dtype=jnp.int32)[None]
    owner = jnp.where(e < n, jnp.take_along_axis(slots, order, axis=1), -1)
    fetch = jnp.take_along_axis(order, jnp.clip(e, 0, jnp.maximum(n - 1, 0)),
                                axis=1)
    return fetch.reshape(-1), owner.astype(jnp.int32).reshape(-1)


def base_delta_matmul_2d_jnp(x: jax.Array, w: jax.Array, dw: jax.Array,
                             slots: jax.Array, layer, *,
                             transposed: bool = False) -> jax.Array:
    """Pure-jnp fallback for :func:`base_delta_matmul_2d` — the off-TPU
    serving hot path.  x: (B, d); w: (L, d, f); dw: (L, C, d, f), or both
    with each matrix as (f, d) when ``transposed``; slots: (L, C) int32
    with -1 for free; layer: int32 scalar.  Computes over all C entries of
    layer ``layer``.  Returns (B, f) in x.dtype."""
    w = lax.dynamic_index_in_dim(w, layer, keepdims=False)
    dw = lax.dynamic_index_in_dim(dw, layer, keepdims=False)
    slots = lax.dynamic_index_in_dim(slots, layer,
                                     keepdims=False).astype(jnp.int32)
    y = lax.dot_general(x, w, (((1,), (1 if transposed else 0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    safe = jnp.maximum(slots, 0)
    corr = jnp.einsum("cd,cfd->cf" if transposed else "cd,cdf->cf",
                      x[safe].astype(jnp.float32), dw.astype(jnp.float32))
    corr = corr * (slots >= 0).astype(jnp.float32)[:, None]
    return y.at[safe].add(corr).astype(x.dtype)


def _delta_mm_kernel(layer_s, fetch_s, owner_s, x_ref, w_ref, dw_ref,
                     out_ref, acc_ref, xf_ref, *, C: int, nk: int,
                     transposed: bool):
    k, e = pl.program_id(1), pl.program_id(2)
    # a (block_f, block_d) block when the operands are stored transposed
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    @pl.when((k == 0) & (e == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e == 0)
    def _base():
        acc_ref[...] += lax.dot_general(x_ref[...], w_ref[...], dims,
                                        preferred_element_type=jnp.float32)
        # an f32 copy of x: a packed bf16 tile cannot be sliced at a
        # dynamic row
        xf_ref[...] = x_ref[...].astype(jnp.float32)

    s = owner_s[layer_s[0] * C + e]

    @pl.when(s >= 0)
    def _correct():
        row = xf_ref[pl.ds(s, 1), :]
        acc_ref[pl.ds(s, 1), :] += lax.dot_general(
            row, dw_ref[...].astype(jnp.float32), dims,
            preferred_element_type=jnp.float32)

    @pl.when((k == nk - 1) & (e == C - 1))
    def _fin():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("transposed", "block_f", "interpret"))
def base_delta_matmul_2d(x: jax.Array, w: jax.Array, dw: jax.Array,
                         slots: jax.Array, layer, live=None, *,
                         transposed: bool = False, block_f: int = 512,
                         interpret: bool = False) -> jax.Array:
    """x: (B, d); w: (L, d, f); dw: (L, C, d, f); slots: (L, C) int32 (-1 =
    free); layer: int32 scalar; live: ``live_first(slots)`` where the
    caller has it (the decode computes it once a step, outside its layer
    scan).  ``transposed``: ``w`` and ``dw`` hold each matrix as (f, d),
    the overlay's storage where :func:`d_minor` holds.  Returns (B, f) in
    x.dtype.

    Grid (f-blocks, d-blocks, entries), entries innermost: the (B, block_f)
    f32 accumulator stays in VMEM across the contraction and the entries.
    The layer index and the live-first entry table are scalar-prefetched
    into SMEM.  ``block_d`` is sized so the double-buffered delta slab fits
    ``DW_VMEM_BUDGET``.
    """
    B, d = x.shape
    f = w.shape[-2 if transposed else -1]
    C = dw.shape[1]
    dw_item = jnp.dtype(dw.dtype).itemsize
    bf = _tile(f, block_f)
    bd = _tile(d, max(LANES, DW_VMEM_BUDGET // (2 * dw_item * bf)))
    nk = d // bd
    fetch, owner = live if live is not None else live_first(
        slots.astype(jnp.int32))

    def block(k, j):
        return (j, k) if transposed else (k, j)

    def w_map(j, k, e, layer_s, *_):
        return (layer_s[0],) + block(k, j)

    def dw_map(j, k, e, layer_s, fetch_s, owner_s):
        l = layer_s[0]
        # a layer with no live entry pins one block for the whole call
        any_live = owner_s[l * C] >= 0
        return (l, fetch_s[l * C + e]) + block(jnp.where(any_live, k, 0),
                                               jnp.where(any_live, j, 0))

    slab = block(bd, bf)
    blocks = (B * bd * x.dtype.itemsize + bd * bf * w.dtype.itemsize
              + bd * bf * dw_item + B * bf * x.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(f // bf, nk, C),
        in_specs=[
            pl.BlockSpec((B, bd), lambda j, k, e, *_: (0, k)),
            pl.BlockSpec((None,) + slab, w_map),
            pl.BlockSpec((None, None) + slab, dw_map),
        ],
        out_specs=pl.BlockSpec((B, bf), lambda j, k, e, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((B, bf), jnp.float32),
                        pltpu.VMEM((B, bd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_delta_mm_kernel, C=C, nk=nk,
                          transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * blocks + B * (bf + bd) * 4 + (4 << 20)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fetch, owner, x, w, dw)
