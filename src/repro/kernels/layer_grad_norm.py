"""Fused per-layer squared gradient norm — Pallas TPU kernel.

The paper's selection step (§4.2) needs ‖g_{i,l}‖² for every selectable
layer l, every selection round.  On the stacked-(L, …) gradient layout this
is a row-wise reduction over possibly hundreds of MB; doing it leaf-by-leaf
launches L×leaves reductions and re-reads HBM.  This kernel streams each
stacked leaf once: the (L, F) leaf is viewed as (L, R, 128) lane rows and
read in (1, block_rows, 128) tiles, grid = (L, n_blocks) with the block
axis sequential.  Each tile's squares are folded into an (8, 128) f32
accumulator with vector adds only; the row's accumulator is written out on
the last block and its 1024 lanes are summed outside the kernel.

The wrapper (ops.layer_grad_norms) flattens each stacked leaf to (L, F)
and sums results across leaves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.masked_update import LANES, as_lane_rows, lane_tiling

ACC_ROWS = 8           # f32 tile height: the accumulator is one vreg


def _sqnorm_kernel(g_ref, out_ref, acc_scr, *, n_blocks: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    g = g_ref[0].astype(jnp.float32)
    acc_scr[...] += jnp.sum((g * g).reshape(-1, ACC_ROWS, LANES), axis=0)

    @pl.when(bi == n_blocks - 1)
    def _fin():
        out_ref[0] = acc_scr[...]


def layer_sq_norms_2d_jnp(g: jax.Array, *, block_rows: int = 512
                          ) -> jax.Array:
    """Pure-jnp fallback for :func:`layer_sq_norms_2d` — the off-TPU hot
    path.  Replays the kernel's accumulation order exactly (per-block
    (8, 128) f32 partial sums, a sequential left fold across blocks, then
    the same final lane sum), so the two are bit-identical (pinned in
    tests/test_kernels.py)."""
    L, F = g.shape
    br, rows = lane_tiling(F, block_rows)
    nb = rows // br
    g3 = as_lane_rows(g, rows).astype(jnp.float32)
    gb = (g3 * g3).reshape(L, nb, br // ACC_ROWS, ACC_ROWS, LANES)
    per_block = jnp.sum(gb, axis=2)                    # (L, nb, 8, 128)
    # the kernel's sequential left fold across blocks, as an O(1)-size
    # graph (an unrolled Python loop would emit nb adds per leaf)
    acc = jax.lax.fori_loop(
        1, nb,
        lambda b, acc: acc + jax.lax.dynamic_index_in_dim(
            per_block, b, axis=1, keepdims=False),
        per_block[:, 0])
    # the kernel's final lane sum is a separate reduction: keep XLA from
    # merging it with the per-block one into a reduction of another order
    return jnp.sum(jax.lax.optimization_barrier(acc), axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def layer_sq_norms_2d(g: jax.Array, *, block_rows: int = 512,
                      interpret: bool = False) -> jax.Array:
    """Row-wise squared norms of a (L, F) array → (L,) f32."""
    L, F = g.shape
    br, rows = lane_tiling(F, block_rows)
    nb = rows // br
    acc = pl.pallas_call(
        functools.partial(_sqnorm_kernel, n_blocks=nb),
        grid=(L, nb),
        in_specs=[pl.BlockSpec((1, br, LANES), lambda l, b: (l, b, 0))],
        out_specs=pl.BlockSpec((1, ACC_ROWS, LANES), lambda l, b: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, ACC_ROWS, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ACC_ROWS, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(as_lane_rows(g, rows))
    return jnp.sum(acc, axis=(1, 2))
