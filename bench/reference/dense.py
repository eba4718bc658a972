"""Plain float32 forward passes of the program's dense transformer family,
written from the architecture's description alone (nothing of the program
is imported).

Two heads: a sequence classifier (mean-pooled hidden state, XLM-R's use in
the paper) and a causal LM with tied embeddings (SmolLM).  Both follow the
program's block as it states it, which departs from the published
XLM-RoBERTa in the same ways the program does: pre-norm RMSNorm with a
``1 + scale`` gain in place of post-norm LayerNorm, sinusoidal positions
scaled by 0.02 in place of learned ones, no biases, tanh-approximated GELU.
SmolLM's block (pre-norm RMSNorm, RoPE, GQA, SwiGLU) is as published.

Matrix products go through :func:`mm`, at ``precision="highest"`` in f32;
``lowp`` (a dtype such as ``jnp.float8_e4m3fn``) rounds both operands of
every product to it first: the control that a lower precision must fail.
Parameters are a flat dict keyed by the program's leaf paths
(``embed/tok``, ``blocks/attn_wq``, ...), stacked per layer in one
segment, ``blocks`` (``segments``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str                 # "gelu" (plain MLP) | "silu" (SwiGLU)
    rope_theta: float        # 0: sinusoidal absolute positions
    eps: float
    causal: bool
    n_classes: int = 0

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        d, h = c["hidden_size"], c["num_attention_heads"]
        prog = c.get("program", {})
        return cls(n_layers=c["num_hidden_layers"], d_model=d, n_heads=h,
                   n_kv_heads=c.get("num_key_value_heads", h),
                   head_dim=c.get("head_dim", d // h),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   act=c["hidden_act"], rope_theta=float(c.get("rope_theta", 0.0)),
                   eps=float(c.get("rms_norm_eps", c.get("layer_norm_eps", 1e-5))),
                   causal=prog.get("task", "lm") == "lm",
                   n_classes=int(prog.get("n_classes", 0)))


def segments(c: dict) -> list:
    """The stacked parameter segments in layer order: (name, layers)."""
    return [("blocks", c["num_hidden_layers"])]


def mm(a, b, lowp=None):
    """a @ b in f32 at full precision; operands rounded to ``lowp`` first."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if lowp is not None:
        a = a.astype(lowp).astype(jnp.float32)
        b = b.astype(lowp).astype(jnp.float32)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def sinusoid(pos, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    return 0.02 * jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def rope(x, pos, theta):
    """Rotate the two halves of each head (x: (B, S, H, hd), pos: (S,))."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(p: dict, x, a: Arch, lowp=None):
    """One block: x + attn(norm(x)), then + mlp(norm(.)).  p holds this
    layer's leaves without the stacking axis."""
    B, S, _ = x.shape
    H, K, hd = a.n_heads, a.n_kv_heads, a.head_dim
    pos = jnp.arange(S)
    h = rms_norm(x, p["attn_ln"], a.eps)
    q = mm(h, p["attn_wq"], lowp).reshape(B, S, H, hd)
    k = mm(h, p["attn_wk"], lowp).reshape(B, S, K, hd)
    v = mm(h, p["attn_wv"], lowp).reshape(B, S, K, hd)
    if a.rope_theta:
        q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))   # (B,H,S,hd)
    s = mm(qh, kh.transpose(0, 1, 3, 2), lowp) / math.sqrt(hd)
    if a.causal:
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = mm(jax.nn.softmax(s, -1), vh, lowp).transpose(0, 2, 1, 3)
    x = x + mm(o.reshape(B, S, H * hd), p["attn_wo"], lowp)
    h = rms_norm(x, p["mlp_ln"], a.eps)
    if a.act == "gelu":
        u = gelu_tanh(mm(h, p["mlp_wi"], lowp))
    else:
        gu = mm(h, p["mlp_wi"], lowp)
        u = jax.nn.silu(gu[..., :a.d_ff]) * gu[..., a.d_ff:]
    return x + mm(u, p["mlp_wo"], lowp)


def layer_params(params: dict, l: int) -> dict:
    return {k.split("/", 1)[1]: v[l] for k, v in params.items()
            if k.startswith("blocks/")}


def embed(params: dict, tokens, a: Arch):
    x = params["embed/tok"][tokens].astype(jnp.float32)
    if not a.rope_theta:
        x = x + sinusoid(jnp.arange(tokens.shape[1]), a.d_model)
    return x


def hidden(params: dict, tokens, a: Arch, lowp=None):
    x = embed(params, tokens, a)
    for l in range(a.n_layers):
        x = layer(layer_params(params, l), x, a, lowp)
    return x


def class_logits(params: dict, h, a: Arch, lowp=None):
    pooled = rms_norm(jnp.mean(h, 1), params["final_norm"], a.eps)
    return mm(pooled, params["head"], lowp)


def class_loss(params: dict, batch: dict, a: Arch, lowp=None):
    """Mean cross-entropy of the pooled classifier."""
    logits = class_logits(params, hidden(params, batch["tokens"], a, lowp), a,
                          lowp)
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(lp, batch["label"][:, None], -1))


def lm_logits(params: dict, tokens, a: Arch, lowp=None,
              blocks: Optional[dict] = None):
    """(B, S, V) next-token logits; ``blocks`` overrides the stacked block
    leaves (a user's private params)."""
    p = dict(params)
    if blocks is not None:
        p.update(blocks)
    h = rms_norm(hidden(p, tokens, a, lowp), p["final_norm"], a.eps)
    return mm(h, p["embed/tok"].T, lowp)
