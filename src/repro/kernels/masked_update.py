"""Fused masked-SGD parameter update — Pallas TPU kernel.

Eq. (3)/(6): θ_l ← θ_l − η · m(l) · g_l applied to the stacked-(L, …)
layout.  Fusing the (L,) mask broadcast with the AXPY means one HBM
read-modify-write per parameter instead of materialising the masked
gradient.

Each (L, F) leaf is viewed as (L, R, 128) lane rows (F padded up to whole
blocks only when it does not divide) and streamed in (1, block_rows, 128)
tiles, so every block is a whole number of (16, 128) tiles — legal for
f32 and bf16 alike.  The per-row scale η·m(l) is a (L, 1, 128) lane
vector rather than a scalar-prefetch operand: the τ-scan calls this under
``jax.vmap`` over the cohort, and a batched tensor operand becomes one
more grid axis where a batched scalar-prefetch operand would fall back to
a per-client loop of kernel launches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 16          # bf16 tile height; a multiple of f32's 8


def lane_tiling(F: int, block_rows: int) -> tuple[int, int]:
    """(rows per block, padded row count) for a length-F row viewed as
    128-lane rows: blocks are whole (16, 128) tiles, at most
    ``block_rows`` rows, and the padded count is a multiple of the block."""
    rows = -(-F // LANES)
    br = min(-(-block_rows // SUBLANES) * SUBLANES,
             -(-rows // SUBLANES) * SUBLANES)
    return br, -(-rows // br) * br


def as_lane_rows(a: jax.Array, rows: int) -> jax.Array:
    """(L, F) → (L, rows, 128), zero-padding F up to rows·128."""
    L, F = a.shape
    pad = rows * LANES - F
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
    return a.reshape(L, rows, LANES)


def masked_sgd_update_2d_jnp(p: jax.Array, g: jax.Array, mask: jax.Array,
                             lr) -> jax.Array:
    """Pure-jnp fallback for :func:`masked_sgd_update_2d` — the off-TPU hot
    path.  Elementwise with the kernel's exact expression order
    ``p − (lr·m)·g`` in f32, so the two are bit-identical (pinned in
    tests/test_kernels.py)."""
    scale = jnp.asarray(lr, jnp.float32) * mask.astype(jnp.float32)
    scale = scale.reshape((mask.shape[0],) + (1,) * (p.ndim - 1))
    return (p.astype(jnp.float32)
            - scale * g.astype(jnp.float32)).astype(p.dtype)


def _masked_update_kernel(scale_ref, p_ref, g_ref, out_ref):
    out_ref[...] = (p_ref[...].astype(jnp.float32)
                    - scale_ref[...] * g_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def masked_sgd_update_2d(p: jax.Array, g: jax.Array, mask: jax.Array,
                         lr, *, block_rows: int = 512,
                         interpret: bool = False) -> jax.Array:
    """p, g: (L, F); mask: (L,); lr scalar. Returns updated (L, F)."""
    L, F = p.shape
    br, rows = lane_tiling(F, block_rows)
    scale = jnp.asarray(lr, jnp.float32) * mask.astype(jnp.float32)
    scale = jnp.broadcast_to(scale[:, None, None], (L, 1, LANES))
    tile = pl.BlockSpec((1, br, LANES), lambda l, b: (l, b, 0))
    out = pl.pallas_call(
        _masked_update_kernel,
        grid=(L, rows // br),
        in_specs=[pl.BlockSpec((1, 1, LANES), lambda l, b: (l, 0, 0)),
                  tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((L, rows, LANES), p.dtype),
        interpret=interpret,
    )(scale, as_lane_rows(p, rows), as_lane_rows(g, rows))
    return out.reshape(L, rows * LANES)[:, :F]
