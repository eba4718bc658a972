"""Masking vectors m_i^t ∈ {0,1}^L and per-layer gradient utilities (§3).

The selected layer set of client i is L_i^t = {l : m_i^t(l) = 1}; the round's
union is L_t = ∪_i L_i^t.  Aggregation weights (Eq. 7) are computed from the
cohort's mask matrix and relative sample sizes.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def mask_from_indices(indices, n_layers: int) -> np.ndarray:
    m = np.zeros(n_layers, dtype=np.float32)
    m[np.asarray(list(indices), dtype=int)] = 1.0
    return m


def indices_from_mask(mask) -> tuple[int, ...]:
    return tuple(int(i) for i in np.nonzero(np.asarray(mask) > 0)[0])


def union_mask(mask_matrix: np.ndarray) -> np.ndarray:
    """L_t = ∪_i L_i^t from the (cohort, L) mask matrix."""
    return (np.asarray(mask_matrix).sum(0) > 0).astype(np.float32)  # repro: allow[host-sync] -- mask matrices are host np by contract (select stage)


def first_trainable_layer(mask_matrix: np.ndarray) -> int:
    """Host-side prefix cut for the mask-aware compute engine (DESIGN.md §7).

    The smallest mask index any cohort member selects this round: layers
    below it are frozen for *everyone*, so the round's update program can
    skip their backward pass entirely.  An all-empty mask matrix returns L
    (nothing trainable — the forward-only program variant).
    """
    cols = np.flatnonzero(np.asarray(mask_matrix).sum(0) > 0)  # repro: allow[host-sync] -- mask matrices are host np by contract (select stage)
    return int(cols[0]) if cols.size else int(np.asarray(mask_matrix).shape[-1])  # repro: allow[host-sync] -- host np indices, no device value


def aggregation_weights(mask_matrix: Array, sizes: Array) -> Array:
    """Eq. (7): w_{i,l} = d_i·m_i(l) / Σ_j d_j·m_j(l)   (0 where denom is 0).

    mask_matrix: (n, L) 0/1;  sizes: (n,) client dataset sizes d_i.
    Returns (n, L) float32.
    """
    mm = jnp.asarray(mask_matrix, jnp.float32)
    d = jnp.asarray(sizes, jnp.float32)[:, None]
    denom = jnp.sum(mm * d, axis=0, keepdims=True)          # (1, L)
    return jnp.where(denom > 0, mm * d / jnp.where(denom > 0, denom, 1.0), 0.0)


def chi_divergence(weights: Array, alpha: Array) -> Array:
    """χ²_{w_l ‖ α} = Σ_i (w_{i,l} − α_i)² / α_i per layer (Lemma 4.6).

    weights: (n, L) realized aggregation weights over the *population*
    (non-cohort clients have w = 0); alpha: (n,) data ratios over the same
    index set.
    """
    a = jnp.asarray(alpha, jnp.float32)[:, None]
    return jnp.sum((weights - a) ** 2 / a, axis=0)          # (L,)


# ---------------------------------------------------------------------------
# Per-layer gradient norms (the strategy inputs)
# ---------------------------------------------------------------------------

def per_layer_sq_norms(grads: Any, cfg, *, mode: str | None = None,
                       interpret: bool | None = None) -> Array:
    """‖g_{i,l}‖² for every selectable layer l — the L-vector clients upload.

    Works on the stacked-parameter layout: each segment's leaves carry a
    leading (count,) axis; reduction is over all remaining axes.  This is
    the probe reduction of the selection step, routed through the fused
    Pallas kernel (kernels/layer_grad_norm.py via kernels.ops): the real
    kernel on TPU, its bit-identical pure-jnp fallback elsewhere.  ``mode``
    forces ``"pallas"``/``"jnp"`` (the kernel-parity tests pin both against
    each other in interpret mode).
    """
    from repro.kernels import ops
    from repro.models.model import layer_layout
    parts = []
    for seg in layer_layout(cfg):
        sub = grads[seg.path]
        if seg.path == "shared_attn":   # unstacked single block: one row
            sub = jax.tree.map(lambda x: x[None], sub)
        parts.append(ops.layer_grad_norms(sub, mode=mode,
                                          interpret=interpret))
    return jnp.concatenate(parts)


def per_layer_param_sq_norms(params: Any, cfg) -> Array:
    """‖θ_l‖² per layer (for the RGN baseline)."""
    return per_layer_sq_norms(params, cfg)


def per_layer_stats(grads: Any, cfg) -> tuple[Array, Array, Array]:
    """(sq_norm, mean, var) of gradient elements per layer (for SNR)."""
    from repro.models.model import layer_layout
    sq, mean, var = [], [], []
    for seg in layer_layout(cfg):
        leaves = [x.astype(jnp.float32) for x in jax.tree.leaves(grads[seg.path])]
        if seg.path == "shared_attn":
            n = sum(x.size for x in leaves)
            s1 = sum(jnp.sum(x) for x in leaves)
            s2 = sum(jnp.sum(jnp.square(x)) for x in leaves)
            mu = s1 / n
            sq.append(s2[None]); mean.append(mu[None])
            var.append((s2 / n - mu ** 2)[None])
        else:
            n = sum(int(np.prod(x.shape[1:])) for x in leaves)
            s1 = sum(jnp.sum(x, axis=tuple(range(1, x.ndim))) for x in leaves)
            s2 = sum(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
                     for x in leaves)
            mu = s1 / n
            sq.append(s2); mean.append(mu)
            var.append(s2 / n - mu ** 2)
    return jnp.concatenate(sq), jnp.concatenate(mean), jnp.concatenate(var)


def count_layer_params(params: Any, cfg) -> np.ndarray:
    """Number of parameters per selectable layer (cost model R(m))."""
    from repro.models.model import layer_layout
    out = []
    for seg in layer_layout(cfg):
        leaves = jax.tree.leaves(params[seg.path])
        if seg.path == "shared_attn":
            out.append(np.array([sum(x.size for x in leaves)]))  # repro: allow[host-sync] -- shape-only accounting, computed once per run
        else:
            per = sum(int(np.prod(x.shape[1:])) for x in leaves)  # repro: allow[host-sync] -- static shape arithmetic, no device value
            out.append(np.full(seg.count, per))
    return np.concatenate(out).astype(np.int64)
