"""Federated aggregation, Eq. (5)-(7).

Given the cohort's accumulated updates Δ_i^t (Eq. 4), the mask matrix and
sample sizes, form the global update

    Δ^t = Σ_{l∈L_t} Σ_{i∈S_t} w_{i,l}^t Δ_{i,l}^t ,
    θ^{t+1} = θ^t − η Δ^t .

The per-layer weights w_{i,l} (Eq. 7) renormalise over exactly the clients
that selected layer l.  Two simulator paths compute the same sum:

* :func:`aggregate` — the sequential oracle: explicit per-client pytrees,
  one scale-and-add per cohort member (paper-literal, easy to audit).
* :func:`aggregate_stacked` — the vectorized engine's path: one einsum
  contraction over the stacked (n, ...) delta pytree, traceable inside a
  single jitted round step (core/client.py ``cohort_update``).

The distributed path fuses the same weighting into a single backward pass
via gradient scaling (sharding/fl_step.py).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.masks import aggregation_weights
from repro.models.model import (layer_layout, segment_cuts, split_mask,
                                split_mask_matrix)

Array = jax.Array
PyTree = Any


def scale_by_layer(tree: PyTree, scale_vec: Array, cfg) -> PyTree:
    """Multiply each selectable layer's subtree by its entry of scale_vec (L,).

    Frozen groups (embed/head/norms) are zeroed — they carry no update.
    """
    parts = split_mask(scale_vec, cfg)
    out = {}
    for key, sub in tree.items():
        if key in parts:
            s = parts[key]
            if key == "shared_attn":
                out[key] = jax.tree.map(lambda x: x * s[0].astype(x.dtype), sub)
            else:
                out[key] = jax.tree.map(
                    lambda x: x * s.astype(x.dtype).reshape(
                        (s.shape[0],) + (1,) * (x.ndim - 1)), sub)
        else:
            out[key] = jax.tree.map(jnp.zeros_like, sub)
    return out


def aggregate(deltas: Sequence[PyTree], mask_matrix: Array, sizes: Array,
              cfg) -> PyTree:
    """Eq. (5): Δ^t = Σ_l Σ_i w_{i,l} Δ_{i,l}."""
    W = aggregation_weights(mask_matrix, sizes)          # (n, L)
    total = None
    for i, d in enumerate(deltas):
        scaled = scale_by_layer(d, W[i], cfg)
        total = scaled if total is None else jax.tree.map(jnp.add, total, scaled)
    return total


def aggregate_stacked(deltas: PyTree, weights: Array, cfg) -> PyTree:
    """Eq. (5) over a *stacked* cohort delta pytree (leaves carry a leading
    (n,) client axis, as produced by ``jax.vmap`` of the local update).

    weights: the (n, L) Eq.(7) matrix from :func:`aggregation_weights`.
    Returns the unstacked global update; frozen groups (embed/head/norms)
    are zeroed, matching :func:`aggregate`.
    """
    parts = split_mask_matrix(weights, cfg)                  # path -> (n, c)
    out = {}
    for key, sub in deltas.items():
        if key in parts:
            w = parts[key]
            if key == "shared_attn":   # unstacked single block: (n,) weight
                out[key] = jax.tree.map(
                    lambda x: jnp.einsum("n,n...->...", w[:, 0],
                                         x.astype(jnp.float32)), sub)
            else:
                out[key] = jax.tree.map(
                    lambda x: jnp.einsum("nc,nc...->c...", w,
                                         x.astype(jnp.float32)), sub)
        else:
            out[key] = jax.tree.map(
                lambda x: jnp.zeros(x.shape[1:], jnp.float32), sub)
    return out


def apply_update(params: PyTree, update: PyTree, lr: float) -> PyTree:
    """Eq. (6): θ^{t+1} = θ^t − η Δ^t, in the params' own dtype (a traced
    f32 ``lr`` would otherwise promote bf16 params to f32)."""
    return jax.tree.map(lambda p, u: (p - lr * u.astype(p.dtype)
                                      ).astype(p.dtype), params, update)


# ---------------------------------------------------------------------------
# Fault-guarded aggregation helpers (DESIGN.md §12): injected corruption +
# the device-side finite guard, all runtime data through ONE jitted program
# ---------------------------------------------------------------------------

def _bcast(row: Array, leaf: Array) -> Array:
    """(n,) row values broadcast against an (n, ...) stacked leaf."""
    return row.reshape((row.shape[0],) + (1,) * (leaf.ndim - 1))


def corrupt_delta_rows(deltas: PyTree, codes: Array,
                       explode_scale) -> PyTree:
    """Apply per-row injected corruption to a stacked (n, ...) delta tree.

    ``codes`` (n,) int32 uses :data:`repro.faults.CORRUPT_CODES`:
    0 = clean, 1 = NaN-fill, 2 = Inf-fill, 3 = ×``explode_scale``.  Codes
    are runtime data, so every fault pattern replays the same compiled
    round program (the no-recompile contract, jit_cache_stats pinned).
    """
    codes = jnp.asarray(codes, jnp.int32)

    def one(x):
        c = _bcast(codes, x)
        x = jnp.where(c == 3, x * jnp.asarray(explode_scale, x.dtype), x)
        x = jnp.where(c == 2, jnp.inf, x)
        return jnp.where(c == 1, jnp.nan, x)

    return jax.tree.map(one, deltas)


def finite_row_mask(deltas: PyTree, max_sq) -> Array:
    """(n,) f32 quarantine mask over a stacked delta tree: 1 where every
    leaf entry of the row is finite AND the row's total Δ sq-norm is at
    most ``max_sq`` (accumulated in f32, like everything else on device —
    an exploding row that overflows f32 reads as non-finite and is
    quarantined by the first predicate).
    """
    leaves = jax.tree.leaves(deltas)
    fin = None
    sq = None
    for x in leaves:
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim))
        f = jnp.all(jnp.isfinite(x), axis=axes)
        s = jnp.sum(x * x, axis=axes)
        fin = f if fin is None else fin & f
        sq = s if sq is None else sq + s
    ok = fin & (sq <= jnp.asarray(max_sq, jnp.float32))
    return ok.astype(jnp.float32)


def zero_delta_rows(deltas: PyTree, ok: Array) -> PyTree:
    """Zero the rows ``ok`` marks dead/quarantined.  Mandatory before the
    Eq.(5) contraction: a zero Eq.(7) weight does NOT neutralise a NaN/Inf
    delta (0·NaN = NaN inside the einsum) — the rows must be zeroed
    *before* they meet the weights."""
    ok = jnp.asarray(ok, jnp.float32)
    return jax.tree.map(
        lambda x: jnp.where(_bcast(ok, x) > 0, x, jnp.zeros((), x.dtype)),
        deltas)


# ---------------------------------------------------------------------------
# Mask-aware (prefix-cut) aggregation: Eq. (5)-(6) over the trainable slice
# ---------------------------------------------------------------------------

def aggregate_stacked_suffix(deltas: PyTree, weights: Array, cut: int,
                             cfg) -> PyTree:
    """Eq. (5) over the *trainable suffix* only (DESIGN.md §7).

    ``deltas``: the ``trainable_slice``-shaped pytree with a leading (n,)
    client axis, as produced by ``jax.vmap`` of the mask-aware local update
    — each segment carries only its rows at or above the prefix cut.
    ``weights``: the full (n, L) Eq.(7) matrix (frozen columns are all-zero
    by construction, so nothing is lost by never contracting them).
    Returns the suffix-shaped global update; the frozen prefix and the
    non-selectable groups carry no update and are left to
    :func:`apply_update_suffix` to pass through untouched.
    """
    parts = split_mask_matrix(weights, cfg)                  # path -> (n, c)
    cuts = segment_cuts(cut, cfg)
    out = {}
    for key, sub in deltas.items():
        w = parts[key][:, cuts[key]:]
        out[key] = jax.tree.map(
            lambda x, w=w: jnp.einsum("nc,nc...->c...", w,
                                      x.astype(jnp.float32)), sub)
    return out


def apply_update_suffix(params: PyTree, update: PyTree, lr: float, cut: int,
                        cfg) -> PyTree:
    """Eq. (6) on the trainable suffix, scattered back into the full tree.

    Matches :func:`apply_update` bit-for-bit: suffix rows get the identical
    ``p − η·u`` expression; frozen rows — where the dense path computes
    ``p − η·0 = p`` exactly — pass through untouched.
    """
    cuts = segment_cuts(cut, cfg)
    out = {}
    for key, sub in params.items():
        if key not in update:
            out[key] = sub
            continue
        c = cuts[key]

        def upd(p, u, c=c):
            new = (p[c:] - lr * u.astype(p.dtype)).astype(p.dtype)
            return new if c == 0 else jnp.concatenate([p[:c], new], axis=0)

        out[key] = jax.tree.map(upd, sub, update[key])
    return out


def apply_delta_rows(params: PyTree, rows: dict, deltas: dict,
                     scale: float = 1.0) -> PyTree:
    """Scatter additive per-layer delta rows into the full tree.

    The row-indexed analogue of :func:`apply_update_suffix` for
    personalized-delta serving (DESIGN.md §9): ``rows`` maps a segment path
    to the (k,) local layer indices a user fine-tuned, ``deltas`` to the
    matching ``{leaf_name: (k, *shape)}`` delta rows.  Segments absent from
    ``rows`` pass through untouched — exactly the frozen layers.
    """
    out = {}
    for key, sub in params.items():
        if key not in rows:
            out[key] = sub
            continue
        idx = jnp.asarray(rows[key], jnp.int32)
        out[key] = jax.tree.map(
            lambda p, d: p.at[idx].add(
                scale * jnp.asarray(d).astype(p.dtype)),
            sub, deltas[key])
    return out
