"""The distributed FL round step: Algorithm 1 on the production mesh.

Mapping (DESIGN.md §4): one cohort client per (pod×data) mesh coordinate;
``model`` axis = tensor parallelism (left to XLA auto-sharding).  The step
is a *partial-manual* ``jax.shard_map``: manual over the client axes, auto
over ``model``.

The per-(client, layer) aggregation of Eq. (5)-(7) is fused into a single
backward pass with two tricks validated in isolation:

1. **grad-scale**: ``gscale(x, c) = x·c + stop_grad(x·(1−c))`` has value
   ``x`` and gradient scaled by ``c``.  Applying it per layer to the
   (gathered) parameters with ``c = w_{i,l}`` makes client i's weight-
   gradient contribution exactly ``w_{i,l}·g_{i,l}``.
2. **differentiable ZeRO-3 gather**: the frozen base is stored sharded over
   the client axes; ``all_gather`` inside the loss is differentiated to a
   ``psum_scatter`` — which *is* the Eq. (5) sum over clients, landing the
   aggregated update already in storage layout.

Selective-layer savings appear structurally: with ``upload_selected_only``
the backward collective runs over the selected sub-stack only (R/L of the
bytes — the paper's communication claim, visible in §Roofline).

τ > 1 local steps keep per-client copies of the *selected sub-stack only*
(the union set is static per selection period) — the frozen base stays
shared/sharded, which is what makes a 314B cohort member fit one v5e chip.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.model import Model, layer_layout, split_mask
from repro.sharding import rules

PyTree = Any


def shard_cohort_rows(mesh, rows: PyTree) -> PyTree:
    """Place per-cohort-member rows on the mesh, leading (cohort) axis
    sharded over the client axes — the DESIGN.md §4 mapping (one cohort
    member per pod×data coordinate) applied to gathered client-state rows
    (warm-start masks, probe stats) so cohort size scales with the mesh.

    Rows whose cohort axis does not divide the client-axis extent are
    replicated instead (values unchanged either way, so the single-device
    path is bit-identical to the host gather).  Accepts a single array or
    any pytree of (cohort, ...) arrays.
    """
    caxes = rules.client_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in caxes])) if caxes else 1

    def place(x):
        x = jnp.asarray(x)
        spec = P(caxes) if x.ndim and n > 1 and x.shape[0] % n == 0 \
            else P(*([None] * x.ndim))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, rows)


def gscale(x, c):
    """Value x, gradient scaled by c (c may broadcast)."""
    c = c.astype(x.dtype)
    return x * c + lax.stop_gradient(x * (1.0 - c))


def _client_mask_scales(mask_row: jnp.ndarray, d_i: jnp.ndarray,
                        caxes: Sequence[str]) -> jnp.ndarray:
    """Eq. (7): w_{i,l} for this shard's client, via a psum over the cohort."""
    dm = mask_row * d_i
    denom = lax.psum(dm, caxes)
    return jnp.where(denom > 0, dm / jnp.where(denom > 0, denom, 1.0), 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _zgather(x, ax: int):
    """ZeRO-3 all-gather whose backward psum_scatters in f32.

    Two reasons: (a) Eq.(5)'s cohort sum should accumulate in f32 even for
    bf16 params; (b) XLA:CPU's AllReducePromotion pass crashes on the
    16-bit reduce-scatter jax would otherwise emit (observed on 0.8.2),
    while f32 collectives are handled fine.
    """
    return lax.all_gather(x, rules.DATA, axis=ax, tiled=True)


def _zgather_fwd(x, ax):
    return _zgather(x, ax), jnp.zeros((0,), x.dtype)   # dtype carrier


def _zgather_bwd(ax, dtype_carrier, ct):
    g = lax.psum_scatter(ct.astype(jnp.float32), rules.DATA,
                         scatter_dimension=ax, tiled=True)
    return (g.astype(dtype_carrier.dtype),)


_zgather.defvjp(_zgather_fwd, _zgather_bwd)


def _gather_leaf(x, spec: P, caxes: Sequence[str]):
    """All-gather the ZeRO-3 ('data') axis of a param leaf (differentiable)."""
    ax = rules.zero3_gather_axis(spec)
    if ax is None:
        return x
    return _zgather(x, ax)


def _residual_psum_axes(spec: P, caxes: Sequence[str]) -> tuple[str, ...]:
    """Client axes whose Eq.(5) sum is NOT covered by the gather backward.

    The ZeRO-3 all_gather differentiates to a psum_scatter over 'data' only;
    replicated leaves (and the 'pod' axis) need an explicit psum.
    """
    covered = {rules.DATA} if rules.zero3_gather_axis(spec) is not None else set()
    return tuple(a for a in caxes if a not in covered)


def _scale_tree(tree: PyTree, w: jnp.ndarray, cfg: ArchConfig,
                freeze_rest: bool, skip: tuple[str, ...] = ()) -> PyTree:
    """Apply gscale per selectable layer; freeze (stop_grad) other groups.

    Segments in ``skip`` are left untouched (the per-layer scan hook scales
    them inside the loop)."""
    parts = split_mask(w, cfg)
    out = {}
    for key, sub in tree.items():
        if key in skip:
            out[key] = sub
        elif key in parts:
            c = parts[key]
            if key == "shared_attn":
                out[key] = jax.tree.map(lambda x: gscale(x, c[0]), sub)
            else:
                out[key] = jax.tree.map(
                    lambda x: gscale(x, c.reshape((c.shape[0],) + (1,) *
                                                  (x.ndim - 1))), sub)
        elif freeze_rest:
            out[key] = jax.tree.map(lax.stop_gradient, sub)
        else:
            out[key] = sub
    return out


# Stacked segments whose ZeRO gather + Eq.(7) scaling happen per layer
# *inside* the scan (so at most one layer's full weights exist per device).
HOOKED_SEGMENTS = ("blocks", "enc_blocks")


def _model_only(spec: P, drop_lead: int = 0) -> P:
    """Keep only 'model' members of a spec (optionally dropping lead dims)."""
    out = []
    for e in list(spec)[drop_lead:]:
        names = e if isinstance(e, tuple) else (e,)
        kept = tuple(n for n in names if n == rules.MODEL)
        out.append(kept[0] if kept else None)
    return P(*out)


def make_fl_train_step(model: Model, mesh, *, zero3: bool = True,
                       freeze_nonlayers: bool = True,
                       window_override: Optional[int] = None,
                       sel_idx: Optional[tuple[int, ...]] = None):
    """Build the jit-able FL round step (τ=1, FedSGD semantics).

    Signature of the returned fn:
        step(params, batch, masks, sizes, lr) -> (new_params, metrics)
    with batch["tokens"]: (clients, per_client, seq) etc., masks: (clients, L),
    sizes: (clients,), all sharded over the client axes.

    §Perf levers (RuntimeConfig):
    * ``tp_constraints`` — constrain gathered params to their Megatron
      'model'-axis layout inside the manual region, so XLA tensor-parallelises
      the per-client compute instead of replicating it 16×.
    * ``sel_upload`` (+ static ``sel_idx``) — only the selected sub-stack's
      rows flow through the differentiable gather, so the Eq.(5) backward
      collective carries R/L of the bytes (the paper's upload saving, made
      structural).
    """
    cfg = model.cfg
    rt = model.runtime
    caxes = rules.client_axes(mesh)
    mesh_shape = {n: mesh.shape[n] for n in mesh.axis_names}
    tp_specs_cache = {}

    def _tp_constrain(p_full, skip=()):
        """Megatron layout hints on the model axis (auto region)."""
        if not tp_specs_cache:
            tp_specs_cache["specs"] = rules.params_pytree_specs(
                cfg, p_full, zero3=False, mesh_shape=mesh_shape)
        specs = tp_specs_cache["specs"]
        out = {}
        for key, sub in p_full.items():
            if key in skip:
                out[key] = sub
                continue
            out[key] = jax.tree.map(
                lambda x, s: lax.with_sharding_constraint(
                    x, jax.sharding.NamedSharding(mesh, s)),
                sub, specs[key], is_leaf=lambda x: isinstance(x, P))
        return out

    def step(params, param_specs, batch, masks, sizes, lr):
        mask_row = masks[0]                       # (L,) this client
        d_i = sizes[0]
        w = _client_mask_scales(mask_row, d_i, caxes)       # (L,)
        w_parts = split_mask(w, cfg)
        my_batch = jax.tree.map(lambda x: x[0], batch)

        hooked = tuple(k for k in HOOKED_SEGMENTS if k in params)

        def layer_hook(pl, idx, segment):
            """Per-layer ZeRO gather + Eq.(7) grad-scale, inside the scan."""
            if segment not in hooked:
                return pl
            c = w_parts[segment][idx]
            specs = param_specs[segment]
            out = {}
            for nm, xv in pl.items():
                ax = rules.zero3_gather_axis(specs[nm])
                if ax is not None:
                    xv = _zgather(xv, ax - 1)    # stacked L dim was sliced off
                if rt.tp_constraints:
                    # re-pin the Megatron 'model' layout: the manual gather
                    # above erases auto-sharding knowledge, and without it
                    # GSPMD replicates the layer compute across 'model'
                    mspec = _model_only(specs[nm], drop_lead=1)
                    xv = lax.with_sharding_constraint(
                        xv, jax.sharding.NamedSharding(mesh, mspec))
                out[nm] = gscale(xv, c)
            return out

        def gather_all(p, with_grad=True, skip=()):
            g = {}
            for key, sub in p.items():
                if key in skip:
                    g[key] = sub
                    continue
                g[key] = jax.tree.map(
                    lambda x, s: _gather_leaf(x, s, caxes), sub,
                    param_specs[key], is_leaf=lambda x: isinstance(x, P))
            return g if with_grad else jax.tree.map(lax.stop_gradient, g)

        if rt.sel_upload and sel_idx is not None:
            # Structural R/L upload: gradient (and its psum_scatter) flows
            # only through the selected rows of the block stack.
            sel = jnp.asarray(sel_idx, jnp.int32)

            def loss_fn(p):
                frozen_full = gather_all(p, with_grad=False)
                sel_rows = jax.tree.map(
                    lambda x, s: _gather_leaf(x, s, caxes),
                    jax.tree.map(lambda a: a[sel], p["blocks"]),
                    jax.tree.map(lambda s: s, param_specs["blocks"]),
                    is_leaf=lambda x: isinstance(x, P))
                blocks = jax.tree.map(
                    lambda full, r: full.at[sel].set(r),
                    frozen_full["blocks"], sel_rows)
                p_full = {**frozen_full, "blocks": blocks}
                if rt.tp_constraints:
                    p_full = _tp_constrain(p_full)
                p_eff = _scale_tree(p_full, w, cfg, freeze_nonlayers)
                return model.loss(p_eff, my_batch,
                                  window_override=window_override)
        else:
            def loss_fn(p):
                # stacked block segments stay sharded here — the per-layer
                # scan hook gathers + scales them one layer at a time
                p_full = gather_all(p, skip=hooked)
                if rt.tp_constraints:
                    p_full = _tp_constrain(p_full, skip=hooked)
                p_eff = _scale_tree(p_full, w, cfg, freeze_nonlayers,
                                    skip=hooked)
                return model.loss(p_eff, my_batch,
                                  window_override=window_override,
                                  layer_hook=layer_hook)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # Eq. (5) cohort sum: the ZeRO-3 gather backward psum_scatters over
        # 'data'; any remaining client axes (replicated leaves, 'pod') get an
        # explicit psum.  Contributions are already w_{i,l}-scaled.
        def _cohort_sum(g, s):
            ra = _residual_psum_axes(s, caxes)
            if not ra:
                return g
            # f32 psum: accuracy + XLA:CPU 16-bit all-reduce promotion bug
            return lax.psum(g.astype(jnp.float32), ra)

        if rt.sel_upload and sel_idx is not None:
            # replicated-storage upload saving: psum only the R selected
            # rows of the block stack (grads are zero elsewhere), the
            # paper's R/L communication claim made structural.
            sel = jnp.asarray(sel_idx, jnp.int32)

            def _sel_sum(g, s):
                ra = _residual_psum_axes(s, caxes)
                if not ra:
                    return g
                rows = lax.psum(g[sel].astype(jnp.float32), ra)
                return jnp.zeros(g.shape, rows.dtype).at[sel].set(rows)

            gb = {k: jax.tree.map(_sel_sum, grads[k], param_specs[k],
                                  is_leaf=lambda x: isinstance(x, P))
                  for k in grads if k == "blocks"}
            rest = {k: jax.tree.map(_cohort_sum, grads[k], param_specs[k],
                                    is_leaf=lambda x: isinstance(x, P))
                    for k in grads if k != "blocks"}
            grads = {**rest, **gb}
        else:
            grads = jax.tree.map(_cohort_sum, grads, param_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        # Eq. (6): θ ← θ − η Δ   (Δ = Σ_i w_il g_il, masked by construction)
        new_params = jax.tree.map(
            lambda pp, g: (pp - lr * g.astype(jnp.float32)).astype(pp.dtype),
            params, grads)
        mean_loss = lax.pmean(loss, caxes)
        union = lax.psum(mask_row, caxes) > 0
        metrics = {"loss": mean_loss,
                   "union_frac": jnp.mean(union.astype(jnp.float32))}
        return new_params, metrics

    def build(params_or_shapes):
        """Return (jitted_fn, in_shardings, out_shardings) for this arch."""
        specs = rules.params_pytree_specs(cfg, params_or_shapes,
                                          zero3=zero3, mesh_shape=mesh_shape)
        # shard_map in_specs: client axes only (model axis stays auto);
        # tuple entries like ('model','data') keep only the client member
        def manual_only(s: P) -> P:
            out = []
            for e in s:
                names = e if isinstance(e, tuple) else (e,)
                kept = tuple(n for n in names if n in caxes)
                out.append(kept[0] if kept else None)
            return P(*out)

        p_manual = jax.tree.map(manual_only, specs,
                                is_leaf=lambda x: isinstance(x, P))
        cl = P(caxes)
        b_spec = P(caxes)        # shard only the leading (clients,) dim

        smapped = jax.shard_map(
            lambda p, b, m, sz, lr_: step(p, specs, b, m, sz, lr_),
            mesh=mesh,
            in_specs=(p_manual,
                      jax.tree.map(lambda _: b_spec, _batch_template(cfg)),
                      P(caxes, None), cl, P()),
            out_specs=(p_manual, {"loss": P(), "union_frac": P()}),
            axis_names=set(caxes),
            check_vma=False,
        )
        in_sh = (rules.named(mesh, specs),
                 jax.tree.map(lambda _: NamedSharding(mesh, b_spec),
                              _batch_template(cfg)),
                 NamedSharding(mesh, P(caxes, None)),
                 NamedSharding(mesh, cl),
                 NamedSharding(mesh, P()))
        out_sh = (rules.named(mesh, specs),
                  {"loss": NamedSharding(mesh, P()),
                   "union_frac": NamedSharding(mesh, P())})
        return jax.jit(smapped, in_shardings=in_sh, out_shardings=out_sh), specs

    return build


def make_fl_train_step_tau(model: Model, mesh, *, sel_idx: tuple[int, ...],
                           tau: int, zero3: bool = True,
                           window_override: Optional[int] = None):
    """τ>1 local steps (Eq. 3-4, Theorem A.2) on the production mesh.

    Memory model = the paper's: each client holds *local copies of the
    selected sub-stack only* (R rows, gathered once per round); the frozen
    base stays ZeRO-sharded and is re-gathered per layer with stop_gradient
    — so local backward passes run **collective-free**, and the only
    cross-client traffic is the Eq.(5) upload of R rows (w-weighted
    psum_scatter back into storage layout).

    Returned fn: step(params, batch, masks, sizes, lr) with batch leaves
    shaped (clients, tau, per_client, ...), masks (clients, L).
    """
    cfg = model.cfg
    rt = model.runtime
    caxes = rules.client_axes(mesh)
    mesh_shape = {n: mesh.shape[n] for n in mesh.axis_names}
    sel_arr = np.asarray(sel_idx, np.int32)

    def step(params, param_specs, batch, masks, sizes, lr):
        mask_row = masks[0]
        d_i = sizes[0]
        w = _client_mask_scales(mask_row, d_i, caxes)           # (L,)
        w_parts = split_mask(w, cfg)
        mask_parts = split_mask(mask_row, cfg)
        my_batch = jax.tree.map(lambda x: x[0], batch)          # (tau, ...)
        sel = jnp.asarray(sel_arr)
        blocks_specs = param_specs["blocks"]

        def gather_rows(blocks):
            """Selected rows, gathered to full width (differentiable).

            Rows keep their leading (R,) dim, so the gather axis is the
            same index as in the stacked spec."""
            out = {}
            for nm, xv in blocks.items():
                ax = rules.zero3_gather_axis(blocks_specs[nm])
                rows = xv[sel]
                if ax is not None:
                    rows = _zgather(rows, ax)
                if rt.tp_constraints:
                    mspec = _model_only(blocks_specs[nm], drop_lead=0)
                    rows = lax.with_sharding_constraint(
                        rows, jax.sharding.NamedSharding(mesh, mspec))
                out[nm] = rows
            return out

        sel_rows0 = jax.tree.map(lax.stop_gradient,
                                 gather_rows(params["blocks"]))

        # frozen groups: gathered once, stop-grad
        others = {k: v for k, v in params.items() if k != "blocks"}
        others_full = {}
        for key, sub in others.items():
            others_full[key] = jax.tree.map(
                lambda x, s: lax.stop_gradient(_gather_leaf(x, s, caxes)),
                sub, param_specs[key], is_leaf=lambda x: isinstance(x, P))

        def layer_hook_for(local_rows):
            def hook(pl, idx, segment):
                if segment != "blocks":
                    return pl
                slot = jnp.clip(jnp.searchsorted(sel, idx), 0, sel.shape[0] - 1)
                is_sel = sel[slot] == idx
                out = {}
                for nm, xv in pl.items():
                    ax = rules.zero3_gather_axis(blocks_specs[nm])
                    stale = xv
                    if ax is not None:
                        stale = _zgather(stale, ax - 1)
                    stale = lax.stop_gradient(stale)
                    if rt.tp_constraints:
                        mspec = _model_only(blocks_specs[nm], drop_lead=1)
                        stale = lax.with_sharding_constraint(
                            stale, jax.sharding.NamedSharding(mesh, mspec))
                    out[nm] = jnp.where(is_sel, local_rows[nm][slot], stale)
                return out
            return hook

        m_sel = mask_parts["blocks"][sel]                        # (R,)

        def local_step(rows, microbatch):
            def loss_fn(r):
                return model.loss(others_full | {"blocks": params["blocks"]},
                                  microbatch,
                                  window_override=window_override,
                                  layer_hook=layer_hook_for(r))
            loss, g = jax.value_and_grad(loss_fn)(rows)
            # Eq.(3): client updates only ITS selected layers
            new_rows = jax.tree.map(
                lambda r, gg: (r.astype(jnp.float32) - lr
                               * gg.astype(jnp.float32)
                               * m_sel.reshape((-1,) + (1,) * (r.ndim - 1))
                               ).astype(r.dtype),
                rows, g)
            return new_rows, loss

        rows_final, losses = lax.scan(local_step, sel_rows0, my_batch)

        # Eq.(4)/(5): Δ_i rows, w-weighted, psum_scattered back to storage
        w_sel = w_parts["blocks"][sel]
        new_blocks = {}
        for nm, xv in params["blocks"].items():
            delta = ((sel_rows0[nm] - rows_final[nm]).astype(jnp.float32)
                     / lr)                                        # Σ_k g
            delta = delta * w_sel.reshape((-1,) + (1,) * (delta.ndim - 1))
            ax = rules.zero3_gather_axis(blocks_specs[nm])
            if ax is not None:
                agg = lax.psum_scatter(delta, rules.DATA,
                                       scatter_dimension=ax, tiled=True)
            else:
                agg = lax.psum(delta, caxes)
            if ax is not None and len(caxes) > 1:   # 'pod' residual
                agg = lax.psum(agg, tuple(a for a in caxes if a != rules.DATA))
            new_blocks[nm] = xv.at[sel].add(
                (-lr * agg).astype(xv.dtype))

        new_params = {**params, "blocks": new_blocks}
        metrics = {"loss": lax.pmean(jnp.mean(losses), caxes),
                   "union_frac": jnp.mean(
                       (lax.psum(mask_row, caxes) > 0).astype(jnp.float32))}
        return new_params, metrics

    def build(params_or_shapes):
        specs = rules.params_pytree_specs(cfg, params_or_shapes,
                                          zero3=zero3, mesh_shape=mesh_shape)

        def manual_only(s: P) -> P:
            out = []
            for e in s:
                names = e if isinstance(e, tuple) else (e,)
                kept = tuple(n for n in names if n in caxes)
                out.append(kept[0] if kept else None)
            return P(*out)

        p_manual = jax.tree.map(manual_only, specs,
                                is_leaf=lambda x: isinstance(x, P))
        cl = P(caxes)
        b_spec = P(caxes)
        smapped = jax.shard_map(
            lambda p, b, m, sz, lr_: step(p, specs, b, m, sz, lr_),
            mesh=mesh,
            in_specs=(p_manual,
                      jax.tree.map(lambda _: b_spec, _batch_template(cfg)),
                      P(caxes, None), cl, P()),
            out_specs=(p_manual, {"loss": P(), "union_frac": P()}),
            axis_names=set(caxes),
            check_vma=False,
        )
        in_sh = (rules.named(mesh, specs),
                 jax.tree.map(lambda _: NamedSharding(mesh, b_spec),
                              _batch_template(cfg)),
                 NamedSharding(mesh, P(caxes, None)),
                 NamedSharding(mesh, cl),
                 NamedSharding(mesh, P()))
        out_sh = (rules.named(mesh, specs),
                  {"loss": NamedSharding(mesh, P()),
                   "union_frac": NamedSharding(mesh, P())})
        return jax.jit(smapped, in_shardings=in_sh, out_shardings=out_sh), specs

    return build


def _batch_template(cfg: ArchConfig) -> dict:
    """Structure-only template of the training batch for spec mapping."""
    t = {"tokens": 0}
    if cfg.family == "vlm":
        t["patches"] = 0
        if cfg.task == "classification":
            t = {"patches": 0, "label": 0}
    elif cfg.family == "audio":
        t["frames"] = 0
    elif cfg.task == "classification":
        t["label"] = 0
    return t
