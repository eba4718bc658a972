"""Transformer building blocks: norms, RoPE, GQA/MQA attention, gated MLPs.

All block parameters live in *stacked* pytrees with a leading ``(L, ...)``
axis and are consumed through ``lax.scan`` (see ``models/model.py``).  That
keeps HLO size depth-independent and makes the paper's per-layer masking a
single ``(L,)`` broadcast on gradients.

Attention has two execution paths:

* ``full``   — plain einsum softmax, used for short sequences;
* ``chunked``— lax.scan over query chunks (memory O(chunk·S) instead of
  O(S²)); this is the XLA-native "flash" path used for prefill_32k.  The
  Pallas kernel in :mod:`repro.kernels.flash_attention` is not called from
  the models: both paths above run on every platform.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig

Array = jax.Array

# Large-negative constant for masking (safe in bf16/f32).
NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "gelu_plain": jax.nn.gelu}[name]


def softcap(logits: Array, cap: float) -> Array:
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------

def rope_tables(positions: Array, head_dim: int, theta: float):
    """cos/sin tables for rotary embedding at given integer positions."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., S, H, hd); cos/sin: (S, hd/2) (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # cos/sin need a heads axis: (S, 1, half)
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(positions: Array, d_model: int,
                       scale: float = 0.02) -> Array:
    """Sinusoidal position encodings computed on the fly (whisper/XLM stand-in).

    Scaled to the token-embedding init scale (0.02) so position signal does
    not swamp token signal at initialisation (learned position tables in the
    original models are initialised at the same scale).
    """
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return scale * jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------

def _mask_bias(q_pos: Array, k_pos: Array, *, causal: bool, window: int,
               prefix_len: int = 0, k_valid: Optional[Array] = None) -> Array:
    """Additive mask bias (Q, K) from positions.

    ``prefix_len``: positions < prefix_len see each other bidirectionally
    (PaliGemma prefix-LM).  ``window``: sliding window (0 = unlimited).
    ``k_valid``: optional bool (K,) marking populated cache slots.
    """
    q = q_pos[:, None]
    k = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        vis = k <= q
        if prefix_len:
            vis = vis | ((k < prefix_len) & (q < prefix_len))
        ok &= vis
    if window:
        ok &= (q - k) < window
    if k_valid is not None:
        ok &= k_valid[None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _mask_bias_per_slot(q_pos: Array, k_pos: Array, *, causal: bool,
                        window: int, k_valid: Array) -> Array:
    """Batched :func:`_mask_bias`: q_pos (B,Sq), k_pos/k_valid (B,Sk) →
    (B,Sq,Sk).  The serving decode path, where every slot sits at its own
    position in its own cache row."""
    return jax.vmap(lambda qp, kp, kv: _mask_bias(
        qp, kp, causal=causal, window=window, k_valid=kv)
    )(q_pos, k_pos, k_valid)


# ---------------------------------------------------------------------------
# Per-slot delta overlays (personalized-delta serving, DESIGN.md §9)
# ---------------------------------------------------------------------------

def per_slot_param(base: Array, drows: Array, slots: Array, B: int) -> Array:
    """Effective small parameter (norm scale / bias) per slot.

    base: (*shape,); drows: (C, *shape) capacity-C delta entries; slots:
    (C,) int32 owner per entry (-1 = empty).  Returns (B, 1, *shape) f32 —
    base + the slot's delta row (at most one entry per slot), broadcastable
    over the decode seq axis.
    """
    safe = jnp.maximum(slots, 0)
    m = (slots >= 0).astype(jnp.float32).reshape((-1,) + (1,) * base.ndim)
    add = jnp.zeros((B,) + base.shape, jnp.float32)
    add = add.at[safe].add(m * drows.astype(jnp.float32))
    return (base.astype(jnp.float32)[None] + add)[:, None].astype(base.dtype)


@dataclass(frozen=True)
class LayerDelta:
    """One scanned layer's view of the per-slot serving overlay.

    The stacks are closed over by the decode scan and read in place at
    ``layer`` (an int32 scalar): ``slots`` (L, C) owner ids (-1 = free),
    ``leaves`` {name: (L, C, *shape)} the delta entries, ``weights``
    {name: (L, d, f)} the base projections that route through
    :func:`repro.kernels.ops.base_delta_matmul`, ``live`` the kernel's
    entry table of ``slots`` (:func:`repro.kernels.ops.delta_live_entries`,
    computed once a step).  Leaf names are the sub-block's own once
    :meth:`sub_block` strips its prefix.
    """
    layer: Array
    slots: Array
    leaves: dict
    weights: dict
    live: Optional[tuple] = None

    def sub_block(self, prefix: str) -> "LayerDelta":
        n = len(prefix)
        return LayerDelta(
            self.layer, self.slots,
            {k[n:]: v for k, v in self.leaves.items() if k.startswith(prefix)},
            {k[n:]: v for k, v in self.weights.items()
             if k.startswith(prefix)}, self.live)

    def proj(self, h: Array, name: str) -> Array:
        from repro.kernels import ops as _kops
        w, dw = self.weights[name], self.leaves[name]
        # the overlay stores a matrix the TPU lays out d-minor as (f, d):
        # the base weight is then read as its transpose, which is the
        # same bytes
        transposed = dw.shape[-2:] != w.shape[-2:]
        if transposed:
            w = jnp.swapaxes(w, 1, 2)
        return _kops.base_delta_matmul(h, w, dw, self.slots, self.layer,
                                       live=self.live, transposed=transposed)

    def param(self, base: Array, name: str, B: int) -> Array:
        """:func:`per_slot_param` of a small leaf at this layer: a gather of
        row ``layer`` of its (L, C, *shape) entries."""
        def row(a):
            return lax.dynamic_index_in_dim(a, self.layer, keepdims=False)
        return per_slot_param(base, row(self.leaves[name]), row(self.slots),
                              B)


def attend_full(q: Array, k: Array, v: Array, bias: Array, scale: float) -> Array:
    """q: (B,Sq,H,hd)  k/v: (B,Sk,K,hd)  bias: (Sq,Sk) shared, or
    (B,Sq,Sk) per-slot (the serving decode path). GQA via reshape."""
    B, Sq, H, hd = q.shape
    Kh = k.shape[2]
    g = H // Kh
    qg = q.reshape(B, Sq, Kh, g, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32) * scale
    logits = logits + (bias[:, None, None] if bias.ndim == 3
                       else bias[None, None, None])
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def attend_chunked(q: Array, k: Array, v: Array, *, q_positions: Array,
                   k_positions: Array, causal: bool, window: int,
                   prefix_len: int, chunk: int, scale: float,
                   remat_chunk: bool = False) -> Array:
    """Query-chunked attention: peak memory O(chunk × Sk) per head.

    Scans over query chunks; each chunk attends to the full key range with a
    position-derived mask.  Equivalent to attend_full (tested), usable at
    32k+ sequence lengths.

    ``remat_chunk`` checkpoints each chunk step so the backward pass
    recomputes per-chunk scores one at a time instead of materialising every
    chunk's (chunk × Sk) softmax simultaneously — the §Perf memory lever.
    """
    B, Sq, H, hd = q.shape
    nchunks = Sq // chunk
    assert Sq % chunk == 0, f"seq {Sq} not divisible by chunk {chunk}"
    qc = q.reshape(B, nchunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qpos = q_positions.reshape(nchunks, chunk)

    def step(_, inp):
        qi, pi = inp
        bias = _mask_bias(pi, k_positions, causal=causal, window=window,
                          prefix_len=prefix_len)
        oi = attend_full(qi, k, v, bias, scale)
        return None, oi

    if remat_chunk:
        step = jax.checkpoint(step)
    _, out = lax.scan(step, None, (qc, qpos))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Attention block (params + forward)
# ---------------------------------------------------------------------------

def attn_param_shapes(cfg: ArchConfig) -> dict:
    d, H, Kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    shapes = {
        "ln": (d,),
        "wq": (d, H * hd),
        "wk": (d, Kh * hd),
        "wv": (d, Kh * hd),
        "wo": (H * hd, d),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (H * hd,), "bk": (Kh * hd,), "bv": (Kh * hd,)})
    return shapes


def init_stacked(rng, shapes: dict, n: int, dtype, scale: float = 0.02) -> dict:
    """Initialise a stack of ``n`` layers of the given param shapes."""
    params = {}
    keys = jax.random.split(rng, len(shapes))
    for key, (name, shp) in zip(keys, sorted(shapes.items())):
        full = (n, *shp) if n else shp
        if name.startswith("b") or name == "ln" or name.endswith("_bias"):
            params[name] = jnp.zeros(full, dtype)
        elif name == "A_log":   # mamba2 A init: log of [1, 16)
            params[name] = jnp.log(
                jax.random.uniform(key, full, jnp.float32, 1.0, 16.0)).astype(dtype)
        elif name == "D":
            params[name] = jnp.ones(full, dtype)
        else:
            params[name] = (jax.random.normal(key, full, jnp.float32) * scale).astype(dtype)
    return params


def attention_fwd(p: dict, x: Array, cfg: ArchConfig, *,
                  positions: Array, cache: Optional[dict] = None,
                  cache_pos: Optional[Array] = None,
                  causal: bool = True, window: int = 0, prefix_len: int = 0,
                  cross_kv: Optional[tuple] = None, seq_chunk: int = 1024,
                  remat_chunk: bool = False,
                  delta: Optional[LayerDelta] = None):
    """One attention sub-block (pre-norm, residual added by caller).

    cache: {"k": (B,W,Kh,hd), "v": ..., "pos": (W,) int32} — decode mode
    writes the current token at slot ``cache_pos % W`` and attends over the
    cache.  With a per-slot serving cache (``pos`` shaped (B, W),
    ``cache_pos``/``positions`` batched) every batch row sits at its own
    stream position.  cross_kv: precomputed (k, v) for encoder-decoder
    cross-attention.

    delta: this layer's view of the personalized-delta serving overlay
    (:class:`LayerDelta`, DESIGN.md §9); its projections route through
    :func:`repro.kernels.ops.base_delta_matmul` and are absent from ``p``.
    """
    B, S, d = x.shape
    H, Kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)

    def proj(h_, name):
        if delta is not None and name in delta.weights:
            return delta.proj(h_, name)
        return h_ @ p[name]

    ln = p["ln"]
    if delta is not None and "ln" in delta.leaves:
        ln = delta.param(ln, "ln", B)
    h = rms_norm(x, ln, cfg.norm_eps)
    q = proj(h, "wq").reshape(B, S, H, hd)
    if cross_kv is None:
        k = proj(h, "wk").reshape(B, S, Kh, hd)
        v = proj(h, "wv").reshape(B, S, Kh, hd)
    else:
        k, v = cross_kv
    if cfg.qkv_bias:
        def bias_term(name, nh):
            if delta is not None and name in delta.leaves:
                return delta.param(p[name], name, B).reshape(B, 1, nh, hd)
            return p[name].reshape(nh, hd)
        q = q + bias_term("bq", H)
        if cross_kv is None:
            k = k + bias_term("bk", Kh)
            v = v + bias_term("bv", Kh)

    if cfg.rope_theta and cross_kv is None:
        cos_q, sin_q = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_q, sin_q)
    elif cfg.rope_theta and cross_kv is not None:
        cos_q, sin_q = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)

    new_cache = None
    if cache is not None and cache["pos"].ndim == 2:
        # Per-slot serving decode: S == 1, cache_pos (B,), pos rows (B, W).
        # Each slot writes its token at its own ring index and attends only
        # over its own populated positions — refills never align the batch.
        W = cache["k"].shape[1]
        slot = (cache_pos % W).astype(jnp.int32)
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
        cpos = cache["pos"].at[bidx, slot].set(cache_pos.astype(jnp.int32))
        k_valid = cpos <= cache_pos[:, None]
        bias = _mask_bias_per_slot(positions, cpos, causal=causal,
                                   window=window, k_valid=k_valid)
        out = attend_full(q, ck, cv, bias, scale)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    elif cache is not None:
        # Decode: S == 1. Write k/v at slot cache_pos % W, attend over cache.
        W = cache["k"].shape[1]
        slot = (cache_pos % W).astype(jnp.int32)
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
        cpos = lax.dynamic_update_slice(cache["pos"],
                                        cache_pos[None].astype(jnp.int32), (slot,))
        k_valid = cpos <= cache_pos          # populated & not future
        bias = _mask_bias(positions, cpos, causal=causal, window=window,
                          k_valid=k_valid)
        out = attend_full(q, ck, cv, bias, scale)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    else:
        k_positions = positions if cross_kv is None else \
            jnp.arange(k.shape[1], dtype=jnp.int32)
        use_causal = causal and cross_kv is None
        if S > seq_chunk and S % seq_chunk == 0:
            out = attend_chunked(q, k, v, q_positions=positions,
                                 k_positions=k_positions, causal=use_causal,
                                 window=window, prefix_len=prefix_len,
                                 chunk=seq_chunk, scale=scale,
                                 remat_chunk=remat_chunk)
        else:
            bias = _mask_bias(positions, k_positions, causal=use_causal,
                              window=window, prefix_len=prefix_len)
            out = attend_full(q, k, v, bias, scale)

    out = proj(out.reshape(B, S, H * hd), "wo")
    return out, new_cache


def make_cross_kv(p: dict, enc_out: Array, cfg: ArchConfig):
    """Precompute cross-attention k/v from encoder output (whisper prefill)."""
    B, Se, d = enc_out.shape
    Kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(B, Se, Kh, hd)
    v = (enc_out @ p["wv"]).reshape(B, Se, Kh, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].reshape(Kh, hd)
        v = v + p["bv"].reshape(Kh, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLP block
# ---------------------------------------------------------------------------

def mlp_param_shapes(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_act == "gelu_plain":           # non-gated (whisper, vit, roberta)
        return {"ln": (d,), "wi": (d, ff), "wo": (ff, d)}
    return {"ln": (d,), "wi": (d, 2 * ff), "wo": (ff, d)}   # gated: [gate|up]


def mlp_fwd(p: dict, x: Array, cfg: ArchConfig, *,
            delta: Optional[LayerDelta] = None) -> Array:
    def proj(h_, name):
        if delta is not None and name in delta.weights:
            return delta.proj(h_, name)
        return h_ @ p[name]

    ln = p["ln"]
    if delta is not None and "ln" in delta.leaves:
        ln = delta.param(ln, "ln", x.shape[0])
    h = rms_norm(x, ln, cfg.norm_eps)
    act = act_fn(cfg.mlp_act)
    if cfg.mlp_act == "gelu_plain":
        return proj(act(proj(h, "wi")), "wo")
    gu = proj(h, "wi")
    ff = gu.shape[-1] // 2
    gate, up = gu[..., :ff], gu[..., ff:]
    return proj(act(gate) * up, "wo")
