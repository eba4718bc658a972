"""Operations and bytes the algorithm requires, from shapes alone.

These count the work the round or the decode step needs, whatever
implements it: forward products of every layer, the backward only as deep
as a client trains, weight gradients only of selected layers, attention
over the keys a query may see, weights read once per decode step, and each
user's delta rows only while the user is decoding.  Recomputation,
padding and work on unselected rows are never counted, so an honest
change cannot push a share past 100%.  Each count is written over the
per-layer quantities of the configuration's family (``geometry``), so a
layer unlike the others (a leading dense layer, experts of which a token
meets only some) counts as what it is.

Parameters are counted in the configuration's dtype (2 bytes for bf16).
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from bench.harness import ROOT, family_module


def geometry(config: dict, root: Path = ROOT):
    """The configuration's ``Geometry``, from its family's
    ``bench/geometry/<family>.py``: ``n_layers``, ``param_bytes``, and per
    layer l ``matmul_flops(l)`` (a token's forward matrix FLOPs through the
    weights it meets: the active experts, not all), ``attn_flops(l,
    keys)``, ``layer_params(l)`` (what the update and the norm read),
    ``cache_bytes(l)`` (per position), ``projections(l)`` ((d_in, d_out)
    of each product ``delta_matmul`` computes); and the heads'
    ``head_params``, ``lm_head_flops`` (a token) and ``class_head_flops``
    (a pooled sequence)."""
    return family_module(config, "geometry", root).Geometry.from_config(config)


def _per_layer(g, f) -> np.ndarray:
    return np.array([f(l) for l in range(g.n_layers)], np.float64)


# ---------------------------------------------------------------------------
# the federated round
# ---------------------------------------------------------------------------

def fl_round_flops(g, masks: np.ndarray, probed: int, *,
                   tau: int, batch: int, seq: int, eval_rows: int) -> float:
    """One round: every client's τ update steps (forward over all layers,
    the activation backward down to its lowest selected layer, weight
    gradients of its selected layers), the probe's full forward and
    backward for ``probed`` clients, and the eval forward."""
    mm = _per_layer(g, g.matmul_flops)
    attn = _per_layer(g, lambda l: g.attn_flops(l, seq))
    fwd = float((mm + attn).sum())                  # a token, every layer
    act_bwd = mm + 2.0 * attn                       # a token, per layer
    head_fwd = g.class_head_flops                   # per sequence, pooled
    upd_tok = tau * batch * seq
    total = 0.0
    for m in np.asarray(masks):
        sel = np.flatnonzero(m > 0)
        total += upd_tok * fwd + tau * batch * head_fwd
        if sel.size:
            total += upd_tok * (act_bwd[sel[0]:].sum() + mm[sel].sum())
    probe_tok = batch * seq
    total += probed * (probe_tok * (fwd + act_bwd.sum() + mm.sum())
                       + batch * head_fwd)
    total += eval_rows * (seq * fwd + head_fwd)
    return float(total)


def masked_update_bytes(g, masks: np.ndarray, tau: int) -> float:
    """θ ← θ − η·g over each client's selected layers, τ times: read θ and
    g, write θ."""
    sel = np.asarray(masks, np.float64).sum(0)      # clients per layer
    return tau * float(sel @ _per_layer(g, g.layer_params)) * 3 * g.param_bytes


def layer_grad_norm_bytes(g, probed: int) -> float:
    """Σ g² per layer over every layer's gradient, once per probed client."""
    return probed * float(_per_layer(g, g.layer_params).sum()) * g.param_bytes


# ---------------------------------------------------------------------------
# the decode step
# ---------------------------------------------------------------------------

def decode_step_counts(g, active: list, delta_layers=None
                       ) -> tuple[float, float]:
    """(flops, bytes) of one decode step.  ``active``: (user, position) per
    decoding slot; ``delta_layers[user]``: that user's delta layers.
    Bytes: the layers' weights and the head's once, each active user's
    delta rows, and the cache of the positions each slot attends to
    (read) and writes."""
    pb = g.param_bytes
    mm = float(_per_layer(g, g.matmul_flops).sum())
    params = _per_layer(g, g.layer_params)
    delta = _per_layer(g, lambda l: sum(2.0 * a * b
                                        for a, b in g.projections(l)))
    cache = float(_per_layer(g, g.cache_bytes).sum())
    flops = 0.0
    nbytes = (float(params.sum()) + g.head_params) * pb
    for user, pos in active:
        keys = pos + 1
        flops += mm + sum(g.attn_flops(l, keys) for l in range(g.n_layers))
        flops += g.lm_head_flops
        nbytes += cache * (keys + 1)
        if delta_layers is not None and user >= 0:
            ls = np.asarray(delta_layers[user])
            flops += delta[ls].sum()
            nbytes += params[ls].sum() * pb
    return float(flops), float(nbytes)


def delta_matmul_counts(g, active: list, delta_layers
                        ) -> list[tuple[float, float]]:
    """(flops, bytes) of each ``base + delta`` product of one decode step:
    per layer and projection, the batch through the base weight plus each
    active slot's delta entry at that layer.  Bytes: the activations in,
    the base weight, the active delta rows, the outputs."""
    pb = g.param_bytes
    B = len(active)
    per_layer = np.zeros(g.n_layers, np.int64)
    for user, _ in active:
        if user >= 0:
            per_layer[np.asarray(delta_layers[user])] += 1
    out = []
    for l, n in enumerate(per_layer):
        for a, b in g.projections(l):
            flops = 2.0 * B * a * b + 2.0 * n * a * b
            nbytes = (B * a + a * b + n * a * b + B * b) * pb
            out.append((flops, nbytes))
    return out
