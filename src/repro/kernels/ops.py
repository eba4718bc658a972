"""Public jit'd wrappers over the Pallas kernels.

These adapt model-level pytrees / shapes to the kernels' flat layouts and
pick the implementation by platform (:func:`_resolve_mode`): the Pallas
kernel on a TPU, the pure-jnp fallback elsewhere.  Tests force either
with ``mode``; a forced kernel off-TPU runs in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import delta_matmul as _dmm
from repro.kernels import flash_attention as _fa
from repro.kernels import layer_grad_norm as _lgn
from repro.kernels import masked_update as _mu
from repro.kernels import ssd_scan as _ssd

PyTree = Any


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _resolve_mode(mode: Optional[str], interpret: Optional[bool]) -> str:
    """Kernel dispatch for the FL hot paths: ``"pallas"`` (the TPU kernel;
    interpret mode off-TPU) or ``"jnp"`` (the pure-jnp fallback, pinned
    bit-identical to the kernel).  ``None`` auto-selects: the real kernel
    on TPU, the fallback elsewhere — unless ``interpret`` was passed
    explicitly, which forces the kernel (the kernel-test path)."""
    if mode is not None:
        if mode not in ("pallas", "jnp"):
            raise ValueError(f"mode must be 'pallas' or 'jnp', got {mode!r}")
        return mode
    if interpret is not None:
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# flash attention (model layout: q/k/v (B, S, H, D))
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    mode: Optional[str] = None):
    """Model-layout wrapper: q (B,S,H,D), k/v (B,S,K,D) → (B,S,H,D).

    The Pallas kernel on TPU, the bit-identical blocked jnp fallback
    elsewhere (``mode`` forces either; tests/test_kernels.py pins the
    parity)."""
    m = _resolve_mode(mode, interpret)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if m == "jnp":
        out = _fa.flash_attention_jnp(qt, kt, vt, causal=causal,
                                      window=window, block_q=block_q,
                                      block_k=block_k)
    else:
        out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_auto_interpret(interpret))
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# SSD (model layout: x (B,S,H,P), dt (B,S,H), A_log (H,), B/C (B,S,G,N))
# ---------------------------------------------------------------------------

def ssd(x, dt, A_log, Bmat, Cmat, D, *, chunk: int = 128,
        interpret: Optional[bool] = None, mode: Optional[str] = None):
    """Model-layout SSD wrapper; the Pallas kernel on TPU, the
    bit-identical chunked jnp fallback elsewhere (``mode`` forces
    either)."""
    m = _resolve_mode(mode, interpret)
    b, s, h, p = x.shape
    g, n = Bmat.shape[2], Bmat.shape[3]
    rep = h // g
    xbh = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtbh = dt.transpose(0, 2, 1).reshape(b * h, s)
    A = -jnp.exp(A_log.astype(jnp.float32))
    Abh = jnp.tile(A, b)
    Dbh = jnp.tile(D.astype(jnp.float32), b)
    Bh = jnp.repeat(Bmat, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Ch = jnp.repeat(Cmat, rep, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, n)
    # kernel applies y += x*D with *undiscretised* x
    if m == "jnp":
        y = _ssd.ssd_scan_jnp(xbh, dtbh, Abh, Bh, Ch, Dbh, chunk=chunk)
    else:
        y = _ssd.ssd_scan(xbh, dtbh, Abh, Bh, Ch, Dbh, chunk=chunk,
                          interpret=_auto_interpret(interpret))
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# per-layer gradient norms over a stacked pytree
# ---------------------------------------------------------------------------

def layer_grad_norms(stacked_grads: PyTree, *, block_rows: int = 512,
                     interpret: Optional[bool] = None,
                     mode: Optional[str] = None) -> jax.Array:
    """Σ over leaves of row-wise ‖·‖² for (L, …) stacked leaves → (L,).

    The probe reduction of the mask-aware engine (core/masks.py routes
    ``per_layer_sq_norms`` here): the Pallas kernel on TPU, the
    bit-identical pure-jnp fallback elsewhere (``mode`` forces either).
    """
    m = _resolve_mode(mode, interpret)
    it = _auto_interpret(interpret)
    total = None
    for leaf in jax.tree.leaves(stacked_grads):
        L = leaf.shape[0]
        flat = leaf.reshape(L, -1)
        if m == "jnp":
            sq = _lgn.layer_sq_norms_2d_jnp(flat, block_rows=block_rows)
        else:
            sq = _lgn.layer_sq_norms_2d(flat, block_rows=block_rows,
                                        interpret=it)
        total = sq if total is None else total + sq
    return total


# ---------------------------------------------------------------------------
# fused masked SGD update over a stacked pytree
# ---------------------------------------------------------------------------

def masked_sgd_update(stacked_params: PyTree, stacked_grads: PyTree,
                      mask: jax.Array, lr, *, block_rows: int = 512,
                      interpret: Optional[bool] = None,
                      mode: Optional[str] = None) -> PyTree:
    """Fused Eq.(3) apply θ_l ← θ_l − η·m(l)·g_l over a stacked pytree.

    The apply step of the mask-aware engine's τ-scan (core/client.py):
    the Pallas kernel on TPU, the bit-identical pure-jnp fallback
    elsewhere (``mode`` forces either).
    """
    m = _resolve_mode(mode, interpret)
    it = _auto_interpret(interpret)

    def upd(p, g):
        if m == "jnp":
            return _mu.masked_sgd_update_2d_jnp(p, g, mask, lr)
        L = p.shape[0]
        out = _mu.masked_sgd_update_2d(p.reshape(L, -1), g.reshape(L, -1),
                                       mask, lr, block_rows=block_rows,
                                       interpret=it)
        return out.reshape(p.shape)

    return jax.tree.map(upd, stacked_params, stacked_grads)


# ---------------------------------------------------------------------------
# fused base + per-slot delta matmul (personalized-delta serving)
# ---------------------------------------------------------------------------

def delta_live_entries(slots, *, interpret: Optional[bool] = None,
                       mode: Optional[str] = None):
    """The kernel's live-first entry table of an (L, C) overlay slot table
    (``delta_matmul.live_first``), or None where the jnp fallback runs:
    the delta decode computes it once a step, outside its layer scan, and
    hands it to every :func:`base_delta_matmul`."""
    if _resolve_mode(mode, interpret) == "jnp":
        return None
    return _dmm.live_first(slots.astype(jnp.int32))


def base_delta_matmul(x, w, dw, slots, layer, *, live=None,
                      transposed: bool = False, block_f: int = 512,
                      interpret: Optional[bool] = None,
                      mode: Optional[str] = None):
    """``y[b] = x[b] @ w[l] + Σ_{e: slots[l, e]==b} x[b] @ dw[l, e]`` at
    ``l = layer`` — the serving decode projection with per-slot
    selected-layer deltas (DESIGN.md §9).

    x: (B, 1, d) decode activations (or (B, d)); w: (L, d, f) the stacked
    shared base weight; dw: (L, C, d, f) the stacked capacity-C overlay
    leaf; slots: (L, C) int32 slot owner per entry, -1 = free; layer:
    int32 scalar; live: :func:`delta_live_entries` of ``slots``, else the
    kernel computes it; transposed: ``w`` and ``dw`` hold each matrix as
    (f, d).  The stacks are read in place at ``layer``.  The
    Pallas kernel on TPU, the pure-jnp fallback elsewhere (``mode`` forces
    either; the two agree to f32 accumulation order).
    """
    m = _resolve_mode(mode, interpret)
    squeeze = x.ndim == 3
    if squeeze:
        assert x.shape[1] == 1, "delta decode projections are single-token"
        x2 = x[:, 0]
    else:
        x2 = x
    with jax.named_scope("delta_matmul"):
        if m == "jnp":
            out = _dmm.base_delta_matmul_2d_jnp(x2, w, dw, slots, layer,
                                                transposed=transposed)
        else:
            out = _dmm.base_delta_matmul_2d(
                x2, w, dw, slots, layer, live, transposed=transposed,
                block_f=block_f, interpret=_auto_interpret(interpret))
    return out[:, None] if squeeze else out
