"""Model facade: init / loss / prefill / decode for every assigned family.

Families and their block structure (all block stacks are scanned):

* ``dense``  — [attn, mlp] × L
* ``moe``    — [attn, moe] × L (optionally ``first_dense`` leading dense
  blocks — deepseek-v2); MLA attention when ``use_mla``
* ``ssm``    — [mamba2] × L
* ``hybrid`` — [mamba2] × L with ONE parameter-shared attention block applied
  after every ``attn_every`` SSM blocks (zamba2); the shared block has a
  distinct KV cache per application site
* ``vlm``    — stub patch embeddings prepended to token embeddings,
  prefix-LM masking (paligemma) or pooled classification (clip-vit)
* ``audio``  — whisper-style encoder-decoder with cross-attention; stub
  frame embeddings

The *selectable layer* set (the paper's ``m ∈ {0,1}^L``) is described by
:func:`layer_layout` — embedding / head / final norms are outside it
(paper §B.2 freezes them).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, RuntimeConfig
from repro.models import blocks as B
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssd as SSD

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# Layer layout (mask segments)
# ---------------------------------------------------------------------------

class Segment(NamedTuple):
    path: str      # top-level key in params
    count: int     # number of mask entries (stacked leading dim, or 1)


def layer_layout(cfg: ArchConfig) -> tuple[Segment, ...]:
    """Mask segments, in mask-index order. Total == cfg.n_selectable_layers()."""
    segs: list[Segment] = []
    if cfg.has_encoder:
        segs.append(Segment("enc_blocks", cfg.n_enc_layers))
    if cfg.first_dense:
        segs.append(Segment("dense0", cfg.first_dense))
    segs.append(Segment("blocks", cfg.n_layers - cfg.first_dense))
    if cfg.family == "hybrid":
        segs.append(Segment("shared_attn", 1))
    assert sum(s.count for s in segs) == cfg.n_selectable_layers()
    return tuple(segs)


def supports_prefix_cut(cfg: ArchConfig) -> bool:
    """Whether the mask-aware compute engine can split this family's forward
    at a frozen-prefix layer index.

    Requires the selectable-layer mask order to be a *prefix* of the compute
    graph: true for the scanned stacks (dense/vlm/ssm/moe/audio).  False for
    ``hybrid`` — zamba2's shared attention block is applied interleaved
    through the whole depth, so layers below any cut still need gradients
    whenever the shared block is trainable.
    """
    return cfg.family != "hybrid"


def supports_delta_decode(cfg: ArchConfig) -> bool:
    """Whether :meth:`Model.decode_step` accepts a per-slot delta overlay.

    The overlay rides the ``blocks`` scan as capacity-C per-layer entries
    (DESIGN.md §9), which requires the plain scanned dense stack: attention
    + MLP blocks whose projections route through ``ops.base_delta_matmul``.
    MoE routing is cross-batch (capacity dropping couples slots) and
    ssm/hybrid blocks have no delta-aware projections yet.
    """
    return cfg.family in ("dense", "vlm")


def delta_projections(blocks: dict, leaves: dict) -> dict:
    """The stacked (L, d, f) base projections of ``blocks`` that the delta
    decode reads in place through ``ops.base_delta_matmul``: those the
    overlay ``leaves`` carry entries for."""
    return {n: v for n, v in blocks.items()
            if n in leaves and len(v.shape) == 3}


def segment_cuts(cut: int, cfg: ArchConfig) -> dict[str, int]:
    """Per-segment frozen-prefix lengths for a global mask-index ``cut``.

    ``cut`` is in mask-index order (:func:`layer_layout`): segments entirely
    below it are fully frozen (cut == count), the segment containing it is
    split, segments above are fully trainable (cut == 0).
    """
    out, off = {}, 0
    for seg in layer_layout(cfg):
        out[seg.path] = min(max(int(cut) - off, 0), seg.count)
        off += seg.count
    return out


def trainable_slice(params: PyTree, cut: int, cfg: ArchConfig) -> PyTree:
    """Rows ``[cut_k:]`` of every selectable segment with trainable layers.

    This is the pytree the mask-aware τ-step scan carries — frozen prefix
    rows and the non-selectable groups (embed/head/norms) are excluded, so
    they are closed over as constants and get neither backward passes nor
    scan-carry traffic.  Fully frozen segments are omitted entirely.
    """
    cuts = segment_cuts(cut, cfg)
    out = {}
    for seg in layer_layout(cfg):
        c = cuts[seg.path]
        if c < seg.count:
            out[seg.path] = jax.tree.map(lambda a, c=c: a[c:],
                                         params[seg.path])
    return out


def split_mask(mask: Array, cfg: ArchConfig) -> dict[str, Array]:
    """Split an (L,)-mask into per-segment arrays keyed by param path."""
    out, off = {}, 0
    for seg in layer_layout(cfg):
        out[seg.path] = mask[off:off + seg.count]
        off += seg.count
    return out


def split_mask_matrix(mask_matrix: Array, cfg: ArchConfig) -> dict[str, Array]:
    """Split an (n, L) cohort mask/weight matrix into (n, count) segments.

    Column-axis analogue of :func:`split_mask`, used by the vectorized
    cohort engine to fuse Eq.(7) weighting over stacked delta pytrees.
    """
    out, off = {}, 0
    for seg in layer_layout(cfg):
        out[seg.path] = mask_matrix[:, off:off + seg.count]
        off += seg.count
    return out


def apply_layer_mask(tree: PyTree, mask: Array, cfg: ArchConfig,
                     frozen_zero: bool = True) -> PyTree:
    """Multiply per-layer subtrees of ``tree`` (grads/updates) by the mask.

    Non-selectable groups (embed, head, norms) are zeroed when
    ``frozen_zero`` (paper freezes them).
    """
    parts = split_mask(mask, cfg)
    out = {}
    for key, sub in tree.items():
        if key in parts:
            m = parts[key]
            if m.shape[0] == 1 and key == "shared_attn":
                out[key] = jax.tree.map(lambda x: x * m[0].astype(x.dtype), sub)
            else:
                out[key] = jax.tree.map(
                    lambda x: x * m.astype(x.dtype).reshape(
                        (m.shape[0],) + (1,) * (x.ndim - 1)), sub)
        else:
            if frozen_zero:
                out[key] = jax.tree.map(jnp.zeros_like, sub)
            else:
                out[key] = sub
    return out


# ---------------------------------------------------------------------------
# Parameter initialisation
# ---------------------------------------------------------------------------

def _block_shapes(cfg: ArchConfig, kind: str) -> dict:
    """Per-layer parameter shapes for one block of the given kind."""
    if kind == "dense":
        return {**_prefixed("attn_", B.attn_param_shapes(cfg)),
                **_prefixed("mlp_", B.mlp_param_shapes(cfg))}
    if kind == "moe":
        attn = MLA.mla_param_shapes(cfg) if cfg.use_mla else B.attn_param_shapes(cfg)
        return {**_prefixed("attn_", attn), **_prefixed("moe_", MOE.moe_param_shapes(cfg))}
    if kind == "moe_dense0":   # deepseek's first dense block: plain MLP sized 4x? use d_ff of shared? use 4*d
        attn = MLA.mla_param_shapes(cfg) if cfg.use_mla else B.attn_param_shapes(cfg)
        mlp = B.mlp_param_shapes(cfg, d_ff=cfg.d_ff * max(cfg.top_k + cfg.n_shared_experts, 1))
        return {**_prefixed("attn_", attn), **_prefixed("mlp_", mlp)}
    if kind == "ssm":
        return _prefixed("ssm_", SSD.mamba2_param_shapes(cfg))
    if kind == "attn_mlp_shared":  # zamba2 shared block
        return {**_prefixed("attn_", B.attn_param_shapes(cfg)),
                **_prefixed("mlp_", B.mlp_param_shapes(cfg))}
    if kind == "encdec":          # whisper decoder block
        return {**_prefixed("attn_", B.attn_param_shapes(cfg)),
                **_prefixed("xattn_", B.attn_param_shapes(cfg)),
                **_prefixed("mlp_", B.mlp_param_shapes(cfg))}
    raise ValueError(kind)


def _prefixed(prefix: str, shapes: dict) -> dict:
    return {prefix + k: v for k, v in shapes.items()}


def _take(p: dict, prefix: str) -> dict:
    n = len(prefix)
    return {k[n:]: v for k, v in p.items() if k.startswith(prefix)}


def init_params(cfg: ArchConfig, rng: Array) -> PyTree:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(rng, 8)
    d = cfg.d_model
    params: dict = {}

    # --- embeddings -------------------------------------------------------
    embed: dict = {}
    if cfg.task == "lm" or cfg.family != "vlm" or cfg.vocab_size:
        embed["tok"] = (jax.random.normal(keys[0], (cfg.vocab_size, d), jnp.float32)
                        * 0.02).astype(dtype)
    if cfg.family == "vlm":
        embed["patch_proj"] = (jax.random.normal(keys[1], (d, d), jnp.float32)
                               * 0.02).astype(dtype)
    if cfg.family == "audio":
        embed["frame_proj"] = (jax.random.normal(keys[1], (d, d), jnp.float32)
                               * 0.02).astype(dtype)
    params["embed"] = embed

    # --- block stacks ------------------------------------------------------
    if cfg.family in ("dense", "vlm"):
        params["blocks"] = B.init_stacked(keys[2], _block_shapes(cfg, "dense"),
                                          cfg.n_layers, dtype)
    elif cfg.family == "moe":
        if cfg.first_dense:
            params["dense0"] = B.init_stacked(
                keys[3], _block_shapes(cfg, "moe_dense0"), cfg.first_dense, dtype)
        params["blocks"] = B.init_stacked(
            keys[2], _block_shapes(cfg, "moe"), cfg.n_layers - cfg.first_dense, dtype)
    elif cfg.family == "ssm":
        params["blocks"] = B.init_stacked(keys[2], _block_shapes(cfg, "ssm"),
                                          cfg.n_layers, dtype)
    elif cfg.family == "hybrid":
        params["blocks"] = B.init_stacked(keys[2], _block_shapes(cfg, "ssm"),
                                          cfg.n_layers, dtype)
        params["shared_attn"] = B.init_stacked(
            keys[3], _block_shapes(cfg, "attn_mlp_shared"), 0, dtype)
    elif cfg.family == "audio":
        params["enc_blocks"] = B.init_stacked(keys[4], _block_shapes(cfg, "dense"),
                                              cfg.n_enc_layers, dtype)
        params["blocks"] = B.init_stacked(keys[2], _block_shapes(cfg, "encdec"),
                                          cfg.n_layers, dtype)
        params["enc_norm"] = jnp.zeros((d,), dtype)
    else:
        raise ValueError(cfg.family)

    params["final_norm"] = jnp.zeros((d,), dtype)

    # --- head --------------------------------------------------------------
    if cfg.task == "classification":
        params["head"] = (jax.random.normal(keys[5], (d, cfg.n_classes), jnp.float32)
                          * 0.02).astype(dtype)
    elif not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(keys[5], (d, cfg.vocab_size), jnp.float32)
                          * 0.02).astype(dtype)
    return params


def count_params(params: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def count_active_params(cfg: ArchConfig, params: PyTree) -> int:
    """Active parameters per token (MoE: top_k of n_experts routed)."""
    total = count_params(params)
    if not cfg.n_experts:
        return total
    routed = sum(params_size
                 for name, params_size in _moe_expert_sizes(params).items())
    active_frac = cfg.top_k / cfg.n_experts
    return int(total - routed + routed * active_frac)


def _moe_expert_sizes(params: PyTree) -> dict[str, int]:
    out = {}
    blocks = params.get("blocks", {})
    for name in ("moe_wi_e", "moe_wo_e"):
        if name in blocks:
            out[name] = blocks[name].size
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _maybe_remat(fn, runtime: RuntimeConfig):
    return jax.checkpoint(fn) if runtime.remat else fn


def _dense_block_fwd(p: dict, x: Array, cfg: ArchConfig, *, positions,
                     causal, window, prefix_len, seq_chunk,
                     cache=None, cache_pos=None, cross_kv=None,
                     remat_chunk=False, delta=None):
    # delta: this layer's B.LayerDelta view of the per-slot serving
    # overlay; its leaf names are split by sub-block prefix
    dattn = dmlp = None
    if delta is not None:
        dattn, dmlp = delta.sub_block("attn_"), delta.sub_block("mlp_")
    attn_out, new_kv = B.attention_fwd(
        _take(p, "attn_"), x, cfg, positions=positions, cache=cache,
        cache_pos=cache_pos, causal=causal, window=window,
        prefix_len=prefix_len, seq_chunk=seq_chunk, remat_chunk=remat_chunk,
        delta=dattn)
    x = x + attn_out
    if "xattn_ln" in p:   # whisper decoder cross-attention
        xo, _ = B.attention_fwd(_take(p, "xattn_"), x, cfg, positions=positions,
                                cross_kv=cross_kv, causal=False,
                                seq_chunk=seq_chunk)
        x = x + xo
    x = x + B.mlp_fwd(_take(p, "mlp_"), x, cfg, delta=dmlp)
    return x, new_kv


def _moe_block_fwd(p: dict, x: Array, cfg: ArchConfig, *, positions, window,
                   seq_chunk, cache=None, cache_pos=None, shard=None,
                   remat_chunk=False, moe_local=False):
    if cfg.use_mla:
        attn_out, new_kv = MLA.mla_fwd(_take(p, "attn_"), x, cfg,
                                       positions=positions, cache=cache,
                                       cache_pos=cache_pos, window=window,
                                       seq_chunk=seq_chunk)
    else:
        attn_out, new_kv = B.attention_fwd(_take(p, "attn_"), x, cfg,
                                           positions=positions, cache=cache,
                                           cache_pos=cache_pos, causal=True,
                                           window=window, seq_chunk=seq_chunk,
                                           remat_chunk=remat_chunk)
    x = x + attn_out
    moe_out, stats = MOE.moe_fwd(_take(p, "moe_"), x, cfg, shard=shard,
                                 local_dispatch=moe_local)
    return x + moe_out, new_kv, stats.aux_loss


class Model:
    """Facade over one architecture: init, loss, prefill, decode."""

    def __init__(self, cfg: ArchConfig, runtime: RuntimeConfig = RuntimeConfig(),
                 shard: Optional[Callable] = None):
        cfg.validate()
        self.cfg = cfg
        self.runtime = runtime
        # custom shard callables change the lowering; the shared jit suite
        # cache (core/client.py) only serves default-sharded models
        self.custom_shard = shard is not None
        self.shard = shard or (lambda x, kind=None: x)

    # -- params ------------------------------------------------------------
    def init(self, rng: Array) -> PyTree:
        return init_params(self.cfg, rng)

    @property
    def n_selectable(self) -> int:
        return self.cfg.n_selectable_layers()

    # -- embedding ---------------------------------------------------------
    def _embed_tokens(self, params, tokens, pos_offset=0):
        cfg = self.cfg
        x = params["embed"]["tok"][tokens]
        if cfg.rope_theta == 0.0:
            # no RoPE (whisper / xlm-r / clip): sinusoidal absolute positions
            S = tokens.shape[1]
            pos = jnp.arange(S, dtype=jnp.int32) + pos_offset
            x = x + B.sinusoid_positions(pos, cfg.d_model).astype(x.dtype)
        return x * (cfg.d_model ** 0.5 if cfg.name.startswith(("gemma", "paligemma")) else 1.0)

    def _head(self, params, h):
        cfg = self.cfg
        h = B.rms_norm(h, params["final_norm"], cfg.norm_eps)
        if cfg.task == "classification":
            return h @ params["head"]
        w = params["embed"]["tok"].T if cfg.tie_embeddings else params["head"]
        logits = h @ w
        return B.softcap(logits, cfg.logit_softcap)

    # -- sequence forward (train / prefill) ---------------------------------
    def _split_scan(self, step, carry, full, idx, trainable, cut: int, rt):
        """Scan ``step`` over a stacked segment, split at frozen-prefix ``cut``.

        Dense path (``trainable is None``): one scan over ``full`` — exactly
        the pre-split program.  Mask-aware path: rows ``[:cut]`` come from
        ``full`` (constants w.r.t. the differentiated arguments, so AD saves
        no residuals and emits no backward for them) and rows ``[cut:]``
        come from ``trainable`` — the slice the τ-step scan carries.
        """
        f = _maybe_remat(step, rt)
        if trainable is None:
            carry, _ = lax.scan(f, carry, (full, idx))
            return carry
        if cut > 0:
            prefix = jax.tree.map(lambda a: lax.stop_gradient(a[:cut]), full)
            carry, _ = lax.scan(f, carry, (prefix, idx[:cut]))
        if cut < idx.shape[0]:
            carry, _ = lax.scan(f, carry, (trainable, idx[cut:]))
        return carry

    def forward_seq(self, params: PyTree, batch: dict, *,
                    window_override: Optional[int] = None,
                    layer_hook: Optional[Callable] = None,
                    trainable: Optional[PyTree] = None, cut: int = 0):
        """Full-sequence forward. Returns (hidden, aux_loss, prefix_len).

        ``layer_hook(per_layer_params, idx, segment)`` is applied to each
        scanned layer's (sliced) params — the distributed FL step uses it to
        ZeRO-gather each layer inside the scan and apply the Eq.(7)
        grad-scale, so no more than one layer's full weights ever
        materialise per device (DESIGN.md §4).

        ``trainable``/``cut`` select the mask-aware compute path (DESIGN.md
        §7): each selectable segment's scan is split at the static mask
        index ``cut`` — rows below it are read from ``params`` (frozen
        constants), rows at or above it from ``trainable`` (the
        :func:`trainable_slice` pytree the caller differentiates).  Only
        families with ``supports_prefix_cut(cfg)`` accept a trainable slice.
        """
        cfg, rt = self.cfg, self.runtime
        if trainable is not None and not supports_prefix_cut(cfg):
            raise ValueError(f"family {cfg.family!r} has no prefix-cut path")
        cuts = segment_cuts(cut, cfg) if trainable is not None else {}
        hook = layer_hook if layer_hook is not None else (lambda p, i, s: p)
        window = cfg.sliding_window if window_override is None else window_override
        aux = jnp.zeros((), jnp.float32)
        prefix_len = 0

        if cfg.family == "audio":
            return self._whisper_seq(params, batch, window,
                                     layer_hook if layer_hook is not None
                                     else (lambda p, i, s: p),
                                     trainable=trainable, cuts=cuts)

        if cfg.family == "vlm":
            patches = batch["patches"].astype(params["embed"]["patch_proj"].dtype)
            px = patches @ params["embed"]["patch_proj"]
            prefix_len = px.shape[1]
            if cfg.task == "classification":
                x = px
            else:
                tx = self._embed_tokens(params, batch["tokens"])
                x = jnp.concatenate([px, tx], axis=1)
        else:
            x = self._embed_tokens(params, batch["tokens"])

        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        causal = cfg.task == "lm"
        x = self.shard(x, "act_bsd")

        if cfg.family in ("dense", "vlm"):
            def step(carry, inp):
                p, idx = inp
                p = hook(p, idx, "blocks")
                h, _ = _dense_block_fwd(p, carry, cfg, positions=positions,
                                        causal=causal, window=window,
                                        prefix_len=prefix_len,
                                        seq_chunk=rt.seq_chunk,
                                        remat_chunk=rt.remat_scores)
                return self.shard(h, "act_bsd"), None
            x = self._split_scan(step, x, params["blocks"],
                                 jnp.arange(cfg.n_layers, dtype=jnp.int32),
                                 None if trainable is None
                                 else trainable.get("blocks"),
                                 cuts.get("blocks", 0), rt)

        elif cfg.family == "moe":
            if cfg.first_dense:
                def step0(carry, inp):
                    p, _ = inp
                    if cfg.use_mla:
                        ao, _ = MLA.mla_fwd(_take(p, "attn_"), carry, cfg,
                                            positions=positions, window=window,
                                            seq_chunk=rt.seq_chunk)
                    else:
                        ao, _ = B.attention_fwd(_take(p, "attn_"), carry, cfg,
                                                positions=positions, causal=True,
                                                window=window, seq_chunk=rt.seq_chunk)
                    h = carry + ao
                    h = h + B.mlp_fwd(_take(p, "mlp_"), h, cfg)
                    return self.shard(h, "act_bsd"), None
                x = self._split_scan(step0, x, params["dense0"],
                                     jnp.arange(cfg.first_dense,
                                                dtype=jnp.int32),
                                     None if trainable is None
                                     else trainable.get("dense0"),
                                     cuts.get("dense0", 0), rt)

            def step(carry, inp):
                p, idx = inp
                p = hook(p, idx, "blocks")
                h, a = carry
                h, _, aux_l = _moe_block_fwd(p, h, cfg, positions=positions,
                                             window=window, seq_chunk=rt.seq_chunk,
                                             shard=self.shard,
                                             remat_chunk=rt.remat_scores,
                                             moe_local=rt.moe_local_dispatch)
                return (self.shard(h, "act_bsd"), a + aux_l), None
            nb = cfg.n_layers - cfg.first_dense
            (x, aux) = self._split_scan(step, (x, aux), params["blocks"],
                                        jnp.arange(nb, dtype=jnp.int32),
                                        None if trainable is None
                                        else trainable.get("blocks"),
                                        cuts.get("blocks", 0), rt)

        elif cfg.family == "ssm":
            def step(carry, inp):
                p, idx = inp
                p = hook(p, idx, "blocks")
                out, _ = SSD.mamba2_fwd(_take(p, "ssm_"), carry, cfg)
                return self.shard(carry + out, "act_bsd"), None
            x = self._split_scan(step, x, params["blocks"],
                                 jnp.arange(cfg.n_layers, dtype=jnp.int32),
                                 None if trainable is None
                                 else trainable.get("blocks"),
                                 cuts.get("blocks", 0), rt)

        elif cfg.family == "hybrid":
            x = self._zamba_seq(params, x, positions, window, hook)

        return x, aux, prefix_len

    def _zamba_seq(self, params, x, positions, window, hook=lambda p, i, s: p):
        cfg, rt = self.cfg, self.runtime
        k = cfg.attn_every
        n_groups, rem = divmod(cfg.n_layers, k)
        blocks = params["blocks"]
        grouped = jax.tree.map(
            lambda a: a[:n_groups * k].reshape((n_groups, k) + a.shape[1:]), blocks)
        tail = jax.tree.map(lambda a: a[n_groups * k:], blocks)
        idx_g = jnp.arange(n_groups * k, dtype=jnp.int32).reshape(n_groups, k)
        idx_t = jnp.arange(n_groups * k, cfg.n_layers, dtype=jnp.int32)
        shared = params["shared_attn"]

        def mamba_step(carry, inp):
            p, idx = inp
            p = hook(p, idx, "blocks")
            out, _ = SSD.mamba2_fwd(_take(p, "ssm_"), carry, cfg)
            return self.shard(carry + out, "act_bsd"), None

        def group_step(carry, inp):
            pg, ig = inp
            h, _ = lax.scan(_maybe_remat(mamba_step, rt), carry, (pg, ig))
            h2, _ = _dense_block_fwd(shared, h, cfg, positions=positions,
                                     causal=True, window=window, prefix_len=0,
                                     seq_chunk=rt.seq_chunk,
                                     remat_chunk=rt.remat_scores)
            return self.shard(h2, "act_bsd"), None

        x, _ = lax.scan(group_step, x, (grouped, idx_g))
        if rem:
            x, _ = lax.scan(_maybe_remat(mamba_step, rt), x, (tail, idx_t))
        return x

    def _whisper_seq(self, params, batch, window, hook=lambda p, i, s: p,
                     trainable: Optional[PyTree] = None,
                     cuts: Optional[dict] = None):
        cfg, rt = self.cfg, self.runtime
        cuts = cuts if cuts is not None else {}
        frames = batch["frames"].astype(params["embed"]["frame_proj"].dtype)
        e = frames @ params["embed"]["frame_proj"]
        Se = e.shape[1]
        e = e + B.sinusoid_positions(jnp.arange(Se, dtype=jnp.int32),
                                     cfg.d_model).astype(e.dtype)
        enc_pos = jnp.arange(Se, dtype=jnp.int32)

        def enc_step(carry, inp):
            p, idx = inp
            p = hook(p, idx, "enc_blocks")
            h, _ = _dense_block_fwd(p, carry, cfg, positions=enc_pos,
                                    causal=False, window=0, prefix_len=0,
                                    seq_chunk=rt.seq_chunk,
                                    remat_chunk=rt.remat_scores)
            return self.shard(h, "act_bsd"), None
        e = self._split_scan(enc_step, e, params["enc_blocks"],
                             jnp.arange(cfg.n_enc_layers, dtype=jnp.int32),
                             None if trainable is None
                             else trainable.get("enc_blocks"),
                             cuts.get("enc_blocks", 0), rt)
        enc_out = B.rms_norm(e, params["enc_norm"], cfg.norm_eps)

        x = self._embed_tokens(params, batch["tokens"])
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)

        def dec_step(carry, inp):
            p, idx = inp
            p = hook(p, idx, "blocks")
            cross_kv = B.make_cross_kv(_take(p, "xattn_"), enc_out, cfg)
            h, _ = _dense_block_fwd(p, carry, cfg, positions=positions,
                                    causal=True, window=window, prefix_len=0,
                                    seq_chunk=rt.seq_chunk, cross_kv=cross_kv,
                                    remat_chunk=rt.remat_scores)
            return self.shard(h, "act_bsd"), None
        x = self._split_scan(dec_step, x, params["blocks"],
                             jnp.arange(cfg.n_layers, dtype=jnp.int32),
                             None if trainable is None
                             else trainable.get("blocks"),
                             cuts.get("blocks", 0), rt)
        return x, jnp.zeros((), jnp.float32), 0

    # -- losses --------------------------------------------------------------
    def loss(self, params: PyTree, batch: dict, *,
             window_override: Optional[int] = None,
             layer_hook: Optional[Callable] = None,
             trainable: Optional[PyTree] = None, cut: int = 0) -> Array:
        h, aux, prefix_len = self.forward_seq(params, batch,
                                              window_override=window_override,
                                              layer_hook=layer_hook,
                                              trainable=trainable, cut=cut)
        return self.loss_from_hidden(params, h, aux, prefix_len, batch)

    def loss_from_hidden(self, params: PyTree, h: Array, aux: Array,
                         prefix_len: int, batch: dict) -> Array:
        """The loss tail on an already-computed hidden state — shared by
        :meth:`loss` and the single-forward eval (core/client.py), so eval
        loss and accuracy come from one ``forward_seq`` call."""
        cfg = self.cfg
        if cfg.task == "classification":
            pooled = jnp.mean(h, axis=1)
            logits = self._head(params, pooled[:, None])[:, 0].astype(jnp.float32)
            ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                      batch["label"][:, None], axis=-1)
            return jnp.mean(ce) + aux

        tokens = batch["tokens"]
        text_h = h[:, prefix_len:] if prefix_len else h
        ce = self._lm_ce(params, text_h[:, :-1], tokens[:, 1:])
        return ce + aux

    def _lm_ce(self, params, h, targets, chunk: int = 1024) -> Array:
        """Chunked next-token cross-entropy (never materialises (B,S,V) f32)."""
        cfg = self.cfg
        h = B.rms_norm(h, params["final_norm"], cfg.norm_eps)
        w = params["embed"]["tok"].T if cfg.tie_embeddings and cfg.task == "lm" \
            else params["head"]
        S = h.shape[1]
        if S <= chunk or S % chunk != 0:
            logits = B.softcap(h @ w, cfg.logit_softcap).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            return jnp.mean(lse - gold)

        nck = S // chunk
        hc = h.reshape(h.shape[0], nck, chunk, -1).transpose(1, 0, 2, 3)
        tc = targets.reshape(targets.shape[0], nck, chunk).transpose(1, 0, 2)

        def step(acc, inp):
            hi, ti = inp
            logits = B.softcap(hi @ w, cfg.logit_softcap).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, ti[..., None], -1)[..., 0]
            return acc + jnp.sum(lse - gold), None

        tot, _ = lax.scan(step, jnp.zeros((), jnp.float32), (hc, tc))
        return tot / (targets.shape[0] * S)

    def logits_seq(self, params: PyTree, batch: dict) -> Array:
        """Full-sequence logits (prefill_32k lowers this)."""
        h, _, prefix_len = self.forward_seq(params, batch)
        if self.cfg.task == "classification":
            return self._head(params, jnp.mean(h, axis=1)[:, None])[:, 0]
        return self._head(params, h[:, -1:])[:, 0]   # last-position logits

    # -- decode ---------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, *,
                   window: int = 0, dtype=None,
                   per_slot: bool = False) -> PyTree:
        """KV/state caches for decode. ``window`` caps attention cache size.

        ``per_slot=True`` builds the serving layout: ``pos`` gains a batch
        axis ((L, B, W) instead of (L, W)) so every slot tracks its own
        stream position — decode_step then takes a (B,) position vector and
        refills never have to align the batch (DESIGN.md §9).
        """
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        W = min(window or max_seq, max_seq)
        Kh = cfg.n_kv_heads
        hd = cfg.resolved_head_dim if cfg.n_heads else 0

        def pos_full(*lead):
            shp = lead + ((batch, W) if per_slot else (W,))
            return jnp.full(shp, jnp.iinfo(jnp.int32).max, jnp.int32)

        def kv(n_layers):
            shp = (n_layers, batch, W, Kh, hd) if n_layers else (batch, W, Kh, hd)
            return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt),
                    "pos": pos_full(n_layers) if n_layers else pos_full()}

        if cfg.family in ("dense", "vlm"):
            return {"blocks": kv(cfg.n_layers)}
        if cfg.family == "moe":
            if cfg.use_mla:
                def mla_cache(n):
                    return {"ckv": jnp.zeros((n, batch, W, cfg.kv_lora_rank), dt),
                            "krope": jnp.zeros((n, batch, W, cfg.qk_rope_dim), dt),
                            "pos": pos_full(n)}
                c = {"blocks": mla_cache(cfg.n_layers - cfg.first_dense)}
                if cfg.first_dense:
                    c["dense0"] = mla_cache(cfg.first_dense)
                return c
            c = {"blocks": kv(cfg.n_layers - cfg.first_dense)}
            if cfg.first_dense:
                c["dense0"] = kv(cfg.first_dense)
            return c
        if cfg.family == "ssm":
            shp = SSD.mamba2_cache_shapes(cfg, batch)
            return {"blocks": {
                "conv": jnp.zeros((cfg.n_layers,) + shp["conv"], dt),
                "state": jnp.zeros((cfg.n_layers,) + shp["state"], dt)}}
        if cfg.family == "hybrid":
            shp = SSD.mamba2_cache_shapes(cfg, batch)
            n_groups = cfg.n_layers // cfg.attn_every
            return {"blocks": {
                        "conv": jnp.zeros((cfg.n_layers,) + shp["conv"], dt),
                        "state": jnp.zeros((cfg.n_layers,) + shp["state"], dt)},
                    "shared_attn": {
                        "k": jnp.zeros((n_groups, batch, W, Kh, hd), dt),
                        "v": jnp.zeros((n_groups, batch, W, Kh, hd), dt),
                        "pos": pos_full(n_groups)}}
        if cfg.family == "audio":
            return {"blocks": kv(cfg.n_layers),
                    "cross_kv": {
                        "k": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, Kh, hd), dt),
                        "v": jnp.zeros((cfg.n_layers, batch, cfg.enc_seq, Kh, hd), dt)}}
        raise ValueError(cfg.family)

    def reset_slot(self, cache: PyTree, slot, *, stacked: bool = False) -> PyTree:
        """Invalidate one batch slot of a decode cache (request refill).

        Position rows become int32-max (= "empty": ``k_valid`` masks every
        cached entry) and SSM conv/state rows are zeroed; k/v slabs are left
        in place — they are unreachable until overwritten.  ``stacked=True``
        addresses the dense per-user layout (leading batch axis from a
        vmapped decode) instead of the per-slot layout (batch axis second,
        after the layer axis).
        """
        imax = jnp.iinfo(jnp.int32).max

        def walk(tree):
            out = {}
            for key, val in tree.items():
                if isinstance(val, dict):
                    out[key] = walk(val)
                elif key == "pos":
                    out[key] = (val.at[slot].set(imax) if stacked
                                else val.at[:, slot].set(imax))
                elif key in ("conv", "state"):
                    out[key] = (val.at[slot].set(0) if stacked
                                else val.at[:, slot].set(0))
                else:
                    out[key] = val
            return out

        return walk(cache)

    def decode_step(self, params: PyTree, tokens: Array, pos: Array,
                    cache: PyTree, *, window: int = 0,
                    delta: Optional[dict] = None) -> tuple[Array, PyTree]:
        """One decode step. tokens: (B,) int32; pos: scalar int32, or a
        (B,) per-slot position vector over a ``per_slot`` cache (the
        serving layout — each slot advances independently).

        ``delta``: per-slot selected-layer overlay for the serving path
        (families with :func:`supports_delta_decode`): ``{"slots": (L, C)
        int32 owner ids (-1 = empty), "leaves": {name: (L, C, *shape)}}``
        — capacity-C delta entries per scanned layer, consumed inside the
        one jitted program so slots with *different* deltas batch together
        (DESIGN.md §9).  The scan closes over the overlay and the base
        projections and carries the layer index instead.

        Returns (logits (B,V), new_cache).
        """
        cfg, rt = self.cfg, self.runtime
        per_slot = jnp.ndim(pos) == 1
        if delta is not None and not supports_delta_decode(cfg):
            raise ValueError(f"family {cfg.family!r} has no delta-decode path")
        x = self._embed_tokens(params, tokens[:, None], pos_offset=0)
        if cfg.rope_theta == 0.0 or cfg.family == "audio":
            # sinusoidal position of the *current* slot
            sp = (B.sinusoid_positions(pos[:, None], cfg.d_model) if per_slot
                  else B.sinusoid_positions(pos[None], cfg.d_model)[None])
            x = params["embed"]["tok"][tokens[:, None]] + sp.astype(x.dtype)
        positions = (pos[:, None] if per_slot else pos[None]).astype(jnp.int32)
        w = window or cfg.sliding_window
        with jax.named_scope("decode_blocks"):
            if cfg.family in ("dense", "vlm"):
                blocks, stacked, live = params["blocks"], {}, None
                if delta is not None:
                    from repro.kernels import ops as _kops
                    # the overlay and the base projections it corrects are
                    # closed over and read in place at the scanned layer
                    # index: the scan slices neither
                    stacked = delta_projections(blocks, delta["leaves"])
                    blocks = {n: v for n, v in blocks.items()
                              if n not in stacked}
                    live = _kops.delta_live_entries(delta["slots"])

                def step(carry, inp):
                    p, kv, l = inp
                    dl = (B.LayerDelta(l, delta["slots"], delta["leaves"],
                                       stacked, live)
                          if delta is not None else None)
                    h, new_kv = _dense_block_fwd(p, carry, cfg, positions=positions,
                                                 causal=True, window=w, prefix_len=0,
                                                 seq_chunk=rt.seq_chunk, cache=kv,
                                                 cache_pos=pos, delta=dl)
                    return h, new_kv
                xs = (blocks, cache["blocks"],
                      jnp.arange(cfg.n_layers, dtype=jnp.int32))
                x, new_kv = lax.scan(step, x, xs)
                new_cache = {"blocks": new_kv}

            elif cfg.family == "moe":
                new_cache = {}
                if cfg.first_dense:
                    def step0(carry, inp):
                        p, kv = inp
                        if cfg.use_mla:
                            ao, nkv = MLA.mla_fwd(_take(p, "attn_"), carry, cfg,
                                                  positions=positions, cache=kv,
                                                  cache_pos=pos, window=w,
                                                  seq_chunk=rt.seq_chunk)
                        else:
                            ao, nkv = B.attention_fwd(_take(p, "attn_"), carry, cfg,
                                                      positions=positions, cache=kv,
                                                      cache_pos=pos, causal=True,
                                                      window=w, seq_chunk=rt.seq_chunk)
                        h = carry + ao
                        h = h + B.mlp_fwd(_take(p, "mlp_"), h, cfg)
                        return h, nkv
                    x, nkv0 = lax.scan(step0, x, (params["dense0"], cache["dense0"]))
                    new_cache["dense0"] = nkv0

                def step(carry, inp):
                    p, kv = inp
                    h, nkv, _ = _moe_block_fwd(p, carry, cfg, positions=positions,
                                               window=w, seq_chunk=rt.seq_chunk,
                                               cache=kv, cache_pos=pos,
                                               shard=self.shard,
                                               moe_local=rt.moe_local_dispatch)
                    return h, nkv
                x, nkv = lax.scan(step, x, (params["blocks"], cache["blocks"]))
                new_cache["blocks"] = nkv

            elif cfg.family == "ssm":
                def step(carry, inp):
                    p, c = inp
                    out, nc = SSD.mamba2_fwd(_take(p, "ssm_"), carry, cfg, cache=c)
                    return carry + out, nc
                x, nc = lax.scan(step, x, (params["blocks"], cache["blocks"]))
                new_cache = {"blocks": nc}

            elif cfg.family == "hybrid":
                x, new_cache = self._zamba_decode(params, x, positions, pos, cache, w)

            elif cfg.family == "audio":
                def step(carry, inp):
                    p, kv, xkv = inp
                    h, nkv = _dense_block_fwd(p, carry, cfg, positions=positions,
                                              causal=True, window=w, prefix_len=0,
                                              seq_chunk=rt.seq_chunk, cache=kv,
                                              cache_pos=pos,
                                              cross_kv=(xkv["k"], xkv["v"]))
                    return h, nkv
                x, nkv = lax.scan(step, x, (params["blocks"], cache["blocks"],
                                            cache["cross_kv"]))
                new_cache = {"blocks": nkv, "cross_kv": cache["cross_kv"]}
            else:
                raise ValueError(cfg.family)

        with jax.named_scope("lm_head"):
            logits = self._head(params, x)[:, 0]
        return logits, new_cache

    def _zamba_decode(self, params, x, positions, pos, cache, w):
        cfg = self.cfg
        k = cfg.attn_every
        n_groups, rem = divmod(cfg.n_layers, k)
        blocks = params["blocks"]
        grouped = jax.tree.map(
            lambda a: a[:n_groups * k].reshape((n_groups, k) + a.shape[1:]), blocks)
        tail = jax.tree.map(lambda a: a[n_groups * k:], blocks)
        mcache = cache["blocks"]
        gcache = jax.tree.map(
            lambda a: a[:n_groups * k].reshape((n_groups, k) + a.shape[1:]), mcache)
        tcache = jax.tree.map(lambda a: a[n_groups * k:], mcache)
        shared = params["shared_attn"]

        def mamba_step(carry, inp):
            p, c = inp
            out, nc = SSD.mamba2_fwd(_take(p, "ssm_"), carry, cfg, cache=c)
            return carry + out, nc

        def group_step(carry, inp):
            pg, cg, kvg = inp
            h, ncg = lax.scan(mamba_step, carry, (pg, cg))
            h2, nkv = _dense_block_fwd(shared, h, cfg, positions=positions,
                                       causal=True, window=w, prefix_len=0,
                                       seq_chunk=self.runtime.seq_chunk,
                                       cache=kvg, cache_pos=pos)
            return h2, (ncg, nkv)

        x, (new_g, new_kv) = lax.scan(group_step, x,
                                      (grouped, gcache, cache["shared_attn"]))
        new_m = jax.tree.map(
            lambda a: a.reshape((n_groups * k,) + a.shape[2:]), new_g)
        if rem:
            x, new_t = lax.scan(mamba_step, x, (tail, tcache))
            new_m = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                                 new_m, new_t)
        return x, {"blocks": new_m, "shared_attn": new_kv}
