"""Client-side local training (Eq. 2-4) and the selection-probe step (§4.2).

Everything is jit-compiled once per architecture and reused across rounds
and clients — masks, batches and learning rate are runtime arrays.

Two execution granularities share the same per-client math:

* per-client: :meth:`Client.local_update` / :meth:`Client.probe` — one jit
  call per cohort member (the sequential oracle).
* per-cohort: :meth:`Client.cohort_update` / :meth:`Client.probe_cohort` —
  the vectorized engine: ``jax.vmap`` over the stacked cohort axis, with the
  Eq.(5)-(7) weighted aggregation and Eq.(6) apply fused into the same XLA
  program, so one round's hot path is a single dispatch (the single-host
  analogue of the mesh step in sharding/fl_step.py).
* fused probe+update: :meth:`Client.probe_update_cohort` — one program that
  runs the cohort update *and* the next round's selection probe on the
  updated params; the streaming round pipeline (core/server.py) uses it
  when every round re-selects (``selection_period == 1``).

Probes are requirement-trimmed: every probe entry point takes a static
``reqs`` tuple (the strategy's declared ``probe_requirements``) and
computes only those stats, plus an optional static ``score_fn`` — a
strategy's device-side scoring fused into the same XLA program
(repro.api.strategy, DESIGN.md §6).

Jit caches are hoisted out of ``Client`` instances into a module-level
cache keyed on ``(ArchConfig, RuntimeConfig)`` (both frozen/hashable), so
benchmark sweeps and multi-server runs that rebuild ``FLServer``/``Client``
for the same architecture share compiled programs instead of recompiling.
Static shapes and τ are handled by jax's own per-function cache, which the
shared callables make global.  Models with a custom ``shard`` callable
bypass the cache (their lowering differs).  ``jit_cache_stats()`` exposes
hit/miss counters for tests and benchmarks.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as M
from repro.core.strategies import PROBE_KEYS
from repro.kernels import ops
from repro.models.model import (Model, apply_layer_mask, segment_cuts,
                                split_mask, trainable_slice)

Array = jax.Array
PyTree = Any


# -- module-level jit suite cache -------------------------------------------
_JIT_CACHE: dict = {}
_JIT_STATS = {"hits": 0, "misses": 0, "uncached": 0}

def jit_cache_stats() -> dict:
    """Hit/miss counters + entry count for the shared jit suite cache, plus
    per-entry-point compiled-program counts (``programs``): how many
    distinct traces each suite function holds across every cached suite.
    The mask-aware engine's entries are keyed on the static prefix cut, so
    their counts are bounded by the number of distinct cuts seen — at most
    L+1 — and must not grow round over round (tests/test_jit_cache.py).
    """
    programs: dict[str, int] = {}
    for suite in _JIT_CACHE.values():
        for name, fn in suite.items():
            programs[name] = programs.get(name, 0) + fn._cache_size()
    return dict(_JIT_STATS, entries=len(_JIT_CACHE), programs=programs)


def _dev_f32(x) -> Array:
    """Stage a host value (np array / python scalar) on device as f32 via an
    *explicit* transfer.  The raw wrappers take host-produced masks/sizes/lr
    every round; ``jnp.asarray(x, jnp.float32)`` routes python scalars
    through an implicit transfer that ``jax.transfer_guard("disallow")``
    (REPRO_STRICT=1) rejects, while ``device_put`` of a host-final np value
    is sanctioned."""
    return jax.device_put(np.asarray(x, np.float32))  # repro: allow[host-sync] -- h2d staging of host-final round inputs, not a device sync


def _dev_i32(x) -> Array:
    """Integer twin of :func:`_dev_f32` (fault-code rows et al.)."""
    return jax.device_put(np.asarray(x, np.int32))  # repro: allow[host-sync] -- h2d staging of host-final round inputs, not a device sync


def masked_suffix_sgd(trainable: PyTree, grads: PyTree, mask: Array, lr,
                      cut: int, cfg, *, mode: str | None = None) -> PyTree:
    """Fused Eq.(3) apply on the trainable suffix slice — the mask-aware
    τ-scan's hot-path call site for kernels/masked_update.py.

    Each segment's stacked leaves get one row-mask-scaled AXPY
    (θ ← θ − η·m(l)·g) through :func:`repro.kernels.ops.masked_sgd_update`:
    the Pallas kernel on TPU, its bit-identical pure-jnp fallback elsewhere
    (``mode`` forces either; tests/test_kernels.py pins the parity).
    """
    cuts = segment_cuts(cut, cfg)
    mparts = split_mask(mask, cfg)
    out = {}
    for path, sub in trainable.items():
        m = mparts[path][cuts[path]:]
        out[path] = ops.masked_sgd_update(sub, grads[path], m, lr, mode=mode)
    return out


def clear_jit_cache() -> None:
    _JIT_CACHE.clear()
    for k in _JIT_STATS:
        _JIT_STATS[k] = 0


# -- program-auditor enumeration hook ---------------------------------------

def _abstract_batch(cfg, lead: tuple, seq: int) -> dict:
    """ShapeDtypeStruct batch with leading axes ``lead`` (family-aware)."""
    SDS = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "vlm":
        batch = {"patches": SDS(lead + (cfg.n_prefix_tokens, cfg.d_model), dt)}
        if cfg.task == "lm":
            batch["tokens"] = SDS(
                lead + (max(seq - cfg.n_prefix_tokens, 4),), jnp.int32)
        else:
            batch["label"] = SDS(lead, jnp.int32)
        return batch
    if cfg.family == "audio":
        return {"frames": SDS(lead + (cfg.enc_seq, cfg.d_model), dt),
                "tokens": SDS(lead + (seq,), jnp.int32)}
    return {"tokens": SDS(lead + (seq,), jnp.int32)}


def suite_program_specs(model: "Model", *, cohort: int = 2, tau: int = 2,
                        batch: int = 2, seq: int = 16, sel_batches: int = 1,
                        cuts: "tuple | None" = None) -> list[dict]:
    """Shape-only audit specs for every training-suite program family.

    One dict per program the jit cache can hold for this (cfg, runtime):
    the dense round step, every masked-cut variant (``cuts`` defaults to
    all L+1, including the cut=L forward-only program), the cohort probe,
    and the fused probe+update (dense + one masked representative).  The
    program auditor (repro.analysis.program) lowers each entry's ``fn`` on
    its abstract ``args`` — nothing here allocates or executes.  Plain
    dicts, not analysis types: core must not import the auditor.
    """
    client = Client(model)
    cfg = model.cfg
    SDS = jax.ShapeDtypeStruct
    from repro.models.model import init_params
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            SDS((2,), jnp.uint32))
    L = model.n_selectable
    batches = _abstract_batch(cfg, (cohort, tau, batch), seq)
    pbatches = _abstract_batch(cfg, (cohort, sel_batches, batch), seq)
    masks = SDS((cohort, L), jnp.float32)
    sizes = SDS((cohort,), jnp.float32)
    lr = SDS((), jnp.float32)
    reqs = ("grad_sq_norms",)
    if cuts is None:
        cuts = tuple(range(L + 1))
    # training entries deliberately do NOT donate: params feed the probe /
    # sequential-oracle paths of the same round (meta records it so the
    # donation contract skips them)
    base = dict(static_argnums=(), donate_argnums=(), weight_argnums=(0,))
    specs = [
        dict(base, name="fl_step", fn=client._cohort_update,
             args=(params, batches, masks, sizes, lr),
             meta={"kind": "fl_step", "single_host": True}),
        dict(base, name="probe", fn=client._probe_cohort,
             args=(params, pbatches, reqs, None), static_argnums=(2, 3),
             meta={"kind": "probe", "single_host": True}),
        dict(base, name="probe_update", fn=client._probe_update_cohort,
             args=(params, batches, masks, sizes, lr, pbatches, reqs, None),
             static_argnums=(6, 7),
             meta={"kind": "probe_update", "single_host": True}),
        # the fault path's one extra variant (DESIGN.md §12): survivor
        # mask / corruption codes / guard scales are runtime arrays
        dict(base, name="fl_step_guarded",
             fn=client._cohort_update_guarded,
             args=(params, batches, masks, sizes, lr,
                   SDS((cohort,), jnp.float32), SDS((cohort,), jnp.int32),
                   SDS((), jnp.float32), SDS((), jnp.float32)),
             meta={"kind": "fl_step_guarded", "single_host": True}),
    ]
    mid = cuts[len(cuts) // 2] if cuts else 0
    for cut in cuts:
        specs.append(dict(
            base, name=f"fl_step_masked/cut{cut}",
            fn=client._cohort_update_masked,
            args=(params, batches, masks, sizes, lr, int(cut)),
            static_argnums=(5,),
            meta={"kind": "fl_step_masked", "cut": int(cut),
                  "n_selectable": L, "single_host": True}))
    specs.append(dict(
        base, name=f"probe_update_masked/cut{mid}",
        fn=client._probe_update_cohort_masked,
        args=(params, batches, masks, sizes, lr, pbatches, int(mid), reqs,
              None),
        static_argnums=(6, 7, 8),
        meta={"kind": "probe_update_masked", "cut": int(mid),
              "single_host": True}))
    return specs


def probe_stats_dict(stats) -> dict[str, np.ndarray]:
    """Materialise a probe result to host numpy.  Accepts the stat dict the
    probe impls return, or the legacy (sq, mean, var, p_sq) 4-tuple."""
    if isinstance(stats, dict):
        return {k: np.asarray(v) for k, v in stats.items()}
    sq, mean, var, p_sq = stats
    return {"grad_sq_norms": np.asarray(sq), "grad_means": np.asarray(mean),
            "grad_vars": np.asarray(var), "param_sq_norms": np.asarray(p_sq)}


class Client:
    """Stateless executor for local training; data is passed per call."""

    def __init__(self, model: Model):
        self.model = model
        self.cfg = model.cfg
        # The compiled suite depends only on (cfg, runtime): Model is a
        # stateless facade, so a suite built against the first Model seen
        # for this key serves every later instance with the same configs.
        key = (None if getattr(model, "custom_shard", False)
               else (model.cfg, model.runtime))
        suite = _JIT_CACHE.get(key) if key is not None else None
        if suite is None:
            # probe entries take static (reqs, score_fn) tail args: jax
            # caches one trace per distinct requirement set / score fn, so
            # requirement-trimmed probes and fused device scoring share the
            # same suite entry (strategy singletons keep identities stable)
            # training entries deliberately never donate params: the same
            # round's params buffer also feeds the probe and the sequential
            # oracle paths, and Δ needs θ^{t,0} after the scan — donation
            # is owned by the serve write programs
            suite = {
                "local_update": jax.jit(self._local_update_impl),  # repro: allow[donation-miss] -- params reused by the probe/oracle paths in the same round
                "probe": jax.jit(self._probe_impl, static_argnums=(2, 3)),  # repro: allow[donation-miss] -- probe is read-only over params
                "eval": jax.jit(self._eval_impl),  # repro: allow[donation-miss] -- eval is read-only over params
                "cohort_update": jax.jit(self._cohort_update_impl),  # repro: allow[donation-miss] -- Δ = θ^{t,0} − θ^{t,τ} needs the pre-round params alive
                # fault path (DESIGN.md §12): the ONE guarded variant —
                # survivors/codes/scales are runtime arrays, so every
                # fault pattern replays this single compiled program
                "cohort_update_guarded": jax.jit(  # repro: allow[donation-miss] -- Δ = θ^{t,0} − θ^{t,τ} needs the pre-round params alive
                    self._cohort_update_guarded_impl),
                # mask-aware engine: one program variant per static prefix
                # cut (≤ L+1 total; jit_cache_stats()["programs"] pins it)
                "cohort_update_masked": jax.jit(  # repro: allow[donation-miss] -- Δ = θ^{t,0} − θ^{t,τ} needs the pre-round params alive
                    self._cohort_update_masked_impl, static_argnums=(5,)),
                "probe_cohort": jax.jit(self._probe_cohort_impl,  # repro: allow[donation-miss] -- probe is read-only over params
                                        static_argnums=(2, 3)),
                "probe_update_cohort": jax.jit(self._probe_update_cohort_impl,  # repro: allow[donation-miss] -- Δ = θ^{t,0} − θ^{t,τ} needs the pre-round params alive
                                               static_argnums=(6, 7)),
                "probe_update_cohort_masked": jax.jit(  # repro: allow[donation-miss] -- Δ = θ^{t,0} − θ^{t,τ} needs the pre-round params alive
                    self._probe_update_cohort_masked_impl,
                    static_argnums=(6, 7, 8)),
            }
            if key is None:
                _JIT_STATS["uncached"] += 1
            else:
                _JIT_CACHE[key] = suite
                _JIT_STATS["misses"] += 1
        else:
            _JIT_STATS["hits"] += 1
        self._local_update = suite["local_update"]
        self._probe = suite["probe"]
        self._eval = suite["eval"]
        self._cohort_update = suite["cohort_update"]
        self._cohort_update_guarded = suite["cohort_update_guarded"]
        self._cohort_update_masked = suite["cohort_update_masked"]
        self._probe_cohort = suite["probe_cohort"]
        self._probe_update_cohort = suite["probe_update_cohort"]
        self._probe_update_cohort_masked = suite["probe_update_cohort_masked"]

    # -- Eq. (3)-(4): τ masked SGD steps, return accumulated update ---------
    def _local_update_impl(self, params: PyTree, batches: PyTree,
                           mask: Array, lr: Array):
        model, cfg = self.model, self.cfg

        def step(p, batch):
            loss, g = jax.value_and_grad(model.loss)(p, batch)
            g = apply_layer_mask(g, mask, cfg)
            new_p = jax.tree.map(
                lambda a, b: (a - lr * b.astype(a.dtype)).astype(a.dtype),
                p, g)
            return new_p, loss

        with jax.named_scope("local_steps"):
            p_final, losses = jax.lax.scan(step, params, batches)
            # Δ_i^t = (θ^{t,0} − θ^{t,τ}) / η  = Σ_k Σ_{l∈L_i} g_{i,l}
            delta = jax.tree.map(
                lambda a, b: (a - b).astype(jnp.float32) / lr,
                params, p_final)
            return delta, jnp.mean(losses)

    def local_update(self, params, batches, mask, lr) -> tuple[PyTree, float]:
        """batches: pytree stacked on axis 0 with length τ."""
        delta, loss = self._local_update(params, batches,
                                         jnp.asarray(mask, jnp.float32),
                                         jnp.asarray(lr, jnp.float32))
        return delta, float(loss)

    # -- vectorized cohort round: vmap(τ-step scan) + fused Eq.(5)-(7) ------
    def _cohort_update_impl(self, params: PyTree, batches: PyTree,
                            masks: Array, sizes: Array, lr: Array):
        from repro.core import aggregation as agg

        def one(b, m):
            return self._local_update_impl(params, b, m, lr)

        # deltas: stacked (n, ...) pytree; losses: (n,)
        deltas, losses = jax.vmap(one)(batches, masks)
        with jax.named_scope("aggregate"):
            weights = M.aggregation_weights(masks, sizes)    # (n, L), Eq. 7
            update = agg.aggregate_stacked(deltas, weights, self.cfg)
            new_params = agg.apply_update(params, update, lr)
        return new_params, losses

    # -- fault-guarded cohort round: survivor reweighting + finite guard ----
    def _cohort_update_guarded_impl(self, params: PyTree, batches: PyTree,
                                    masks: Array, sizes: Array, lr: Array,
                                    survivors: Array, codes: Array,
                                    explode_scale: Array, max_delta_sq: Array):
        """The ONE masked round-step variant the fault path adds
        (DESIGN.md §12): identical local math to ``_cohort_update_impl``,
        then injected corruption (``codes``), the device-side finite
        guard, and survivor-reweighted Eq.(5)-(7) aggregation — all of it
        runtime data, so one compiled program serves every fault pattern
        and a no-fault call (survivors=1, codes=0) computes exactly the
        dense step's params.

        Returns ``(new_params, losses, ok)``: ``ok`` (n,) f32 marks the
        rows that actually aggregated (alive AND finite AND under the
        norm threshold).  Dead/quarantined rows are zeroed *before* the
        contraction (0-weight × NaN = NaN otherwise) and their sizes
        zeroed in the Eq.(7) renormalisation — a layer all of whose
        selectors died gets weight 0 everywhere and the global params
        pass through bit-exact (θ − η·0 = θ).
        """
        from repro.core import aggregation as agg

        def one(b, m):
            return self._local_update_impl(params, b, m, lr)

        deltas, losses = jax.vmap(one)(batches, masks)
        with jax.named_scope("aggregate"):
            deltas = agg.corrupt_delta_rows(deltas, codes, explode_scale)
            ok = agg.finite_row_mask(deltas, max_delta_sq) * survivors
            deltas = agg.zero_delta_rows(deltas, ok)
            weights = M.aggregation_weights(masks, sizes * ok)  # survivors
            update = agg.aggregate_stacked(deltas, weights, self.cfg)
            new_params = agg.apply_update(params, update, lr)
        return new_params, losses, ok

    def cohort_update_guarded_raw(self, params, batches, masks, sizes, lr,
                                  survivors, codes, explode_scale,
                                  max_delta_sq):
        """Async fault-guarded round step (device arrays, no sync)."""
        return self._cohort_update_guarded(
            params, batches, _dev_f32(masks), _dev_f32(sizes), _dev_f32(lr),
            _dev_f32(survivors), _dev_i32(codes), _dev_f32(explode_scale),
            _dev_f32(max_delta_sq))

    def cohort_update_guarded(self, params, batches, masks, sizes, lr,
                              survivors, codes, explode_scale, max_delta_sq
                              ) -> tuple[PyTree, np.ndarray, np.ndarray]:
        """Blocking :meth:`cohort_update_guarded_raw`: np losses + ok."""
        new_params, losses, ok = self.cohort_update_guarded_raw(
            params, batches, masks, sizes, lr, survivors, codes,
            explode_scale, max_delta_sq)
        # repro: allow[host-sync] -- fault accounting is a sanctioned round-boundary sync (DESIGN.md §12)
        return new_params, np.asarray(losses), np.asarray(ok)

    # -- mask-aware cohort round: frozen-prefix split at a static cut --------
    def _cohort_update_masked_impl(self, params: PyTree, batches: PyTree,
                                   masks: Array, sizes: Array, lr: Array,
                                   cut: int):
        """The mask-aware engine's round step (DESIGN.md §7).

        ``cut`` (static) is the round's prefix cut — the smallest layer any
        cohort member trains.  The forward below it runs as a frozen
        constant scan: no backward pass, no saved activations; embeddings,
        head and norms (frozen by the paper) are likewise never
        differentiated.  The τ-step scan carries only the trainable suffix
        slice; Δ and the Eq.(5)-(7) aggregation are computed over that
        slice and scattered back into the full tree.  One program compiles
        per distinct cut (≤ L+1 variants), pinned by ``jit_cache_stats``.
        """
        from repro.core import aggregation as agg

        model, cfg = self.model, self.cfg
        if cut >= model.n_selectable:
            # all-empty masks: nothing trains — forward-only losses (the
            # dense path's zero-masked steps never move params either)
            def one(b):
                def step(carry, batch):
                    return carry, model.loss(params, batch)
                _, losses = jax.lax.scan(step, 0, b)
                return jnp.mean(losses)

            with jax.named_scope("local_steps"):
                return params, jax.vmap(one)(batches)

        def one(b, m):
            def step(tr, batch):
                loss, g = jax.value_and_grad(
                    lambda t: model.loss(params, batch, trainable=t,
                                         cut=cut))(tr)
                new_tr = masked_suffix_sgd(tr, g, m, lr, cut, cfg)
                return new_tr, loss

            tr_fin, losses = jax.lax.scan(step, tr0, b)
            delta = jax.tree.map(lambda a, z: (a - z).astype(jnp.float32) / lr,
                                 tr0, tr_fin)
            return delta, jnp.mean(losses)

        with jax.named_scope("local_steps"):
            tr0 = trainable_slice(params, cut, cfg)
            deltas, losses = jax.vmap(one)(batches, masks)
        with jax.named_scope("aggregate"):
            weights = M.aggregation_weights(masks, sizes)    # (n, L), Eq. 7
            update = agg.aggregate_stacked_suffix(deltas, weights, cut,
                                                  self.cfg)
            new_params = agg.apply_update_suffix(params, update, lr, cut,
                                                 self.cfg)
        return new_params, losses

    def cohort_update_raw(self, params, batches, masks, sizes, lr,
                          cut: "int | None" = None):
        """Async variant: returns device arrays without forcing a sync, so
        the streaming pipeline can overlap host sampling with the in-flight
        XLA program (jax dispatches asynchronously).

        ``cut=None`` runs the dense program (every layer differentiated —
        the pre-mask-aware behaviour); an integer cut dispatches the
        mask-aware program for that frozen-prefix depth.
        """
        args = (params, batches, _dev_f32(masks), _dev_f32(sizes),
                _dev_f32(lr))
        if cut is None:
            return self._cohort_update(*args)
        return self._cohort_update_masked(*args, int(cut))  # repro: allow[host-sync] -- cut is a static python int, not a device value

    def cohort_update(self, params, batches, masks, sizes, lr,
                      cut: "int | None" = None) -> tuple[PyTree, np.ndarray]:
        """One fused round step for the whole cohort.

        batches: pytree with leading (cohort, τ) axes (``cohort_batches``);
        masks: (cohort, L); sizes: (cohort,) client dataset sizes d_i;
        cut: optional static prefix cut (see :meth:`cohort_update_raw`).
        Returns (new global params, per-client mean local losses).  Matches
        the sequential local_update → aggregate → apply_update composition
        within fp tolerance (see tests/test_round_engine.py) — with or
        without the mask-aware cut (tests/test_masked_engine.py).
        """
        new_params, losses = self.cohort_update_raw(params, batches, masks,
                                                    sizes, lr, cut)
        return new_params, np.asarray(losses)

    # -- selection probe: layer-wise gradient stats on one batch ------------
    def _probe_impl(self, params: PyTree, batch: PyTree,
                    reqs: tuple = PROBE_KEYS, score_fn=None):
        """Gradient stats for one batch, trimmed to the requested keys.

        ``reqs`` (static) is the strategy's ``probe_requirements``: only the
        requested stats are computed — SNR-only strategies skip the param
        norms, ``ours`` skips mean/var entirely (a cheaper reduction).  Keys
        not requested are never part of the program (XLA sees only the
        returned outputs).
        """
        with jax.named_scope("probe"):
            g = jax.grad(self.model.loss)(params, batch)
            out: dict[str, Array] = {}
            if "grad_means" in reqs or "grad_vars" in reqs:
                sq, mean, var = M.per_layer_stats(g, self.cfg)
                out["grad_sq_norms"] = sq
                out["grad_means"] = mean
                out["grad_vars"] = var
            elif "grad_sq_norms" in reqs:
                # the fused layer_grad_norm kernel (TPU) / its pinned jnp
                # fallback — the probe itself stays dense across all L
                # layers: next round's selection needs utilities for every
                # layer, trained or not (DESIGN.md §7)
                out["grad_sq_norms"] = M.per_layer_sq_norms(g, self.cfg)
            if "param_sq_norms" in reqs:
                out["param_sq_norms"] = M.per_layer_param_sq_norms(
                    params, self.cfg)
            return {k: v for k, v in out.items() if k in reqs}

    def probe(self, params, batch,
              reqs: tuple = PROBE_KEYS) -> dict[str, np.ndarray]:
        return probe_stats_dict(self._probe(params, batch, tuple(reqs), None))

    def _probe_cohort_impl(self, params: PyTree, batches: PyTree,
                           reqs: tuple = PROBE_KEYS, score_fn=None):
        def one_client(cb):
            outs = jax.vmap(lambda b: self._probe_impl(params, b, reqs))(cb)
            # mean over the selection_batches axis == the sequential
            # accumulate-then-divide in FLServer.probe_round
            return {k: v.mean(0) for k, v in outs.items()}

        with jax.named_scope("probe"):
            stats = jax.vmap(one_client)(batches)
            if score_fn is not None:
                # strategy's device-side scoring fused into the same
                # program; applied to the *meaned* stats, exactly like the
                # host path
                stats = dict(stats, scores=score_fn(stats))
        return stats

    def probe_cohort_raw(self, params, batches, reqs: tuple = PROBE_KEYS,
                         score_fn=None):
        """Async variant of :meth:`probe_cohort` (device arrays)."""
        return self._probe_cohort(params, batches, tuple(reqs), score_fn)

    def probe_cohort(self, params, batches, reqs: tuple = PROBE_KEYS,
                     score_fn=None) -> dict[str, np.ndarray]:
        """Batched probe: one vmapped grad+stats call over the whole cohort.

        batches: pytree with leading (cohort, selection_batches) axes.
        Returns (cohort, L) arrays for the requested stat keys (plus
        ``"scores"`` when a device score_fn is fused in).
        """
        return probe_stats_dict(
            self._probe_cohort(params, batches, tuple(reqs), score_fn))

    # -- fused probe+update: one program per round ---------------------------
    def _probe_update_cohort_impl(self, params: PyTree, batches: PyTree,
                                  masks: Array, sizes: Array, lr: Array,
                                  probe_batches: PyTree,
                                  reqs: tuple = PROBE_KEYS, score_fn=None):
        new_params, losses = self._cohort_update_impl(params, batches, masks,
                                                      sizes, lr)
        # next round's selection probe, on the *updated* params — identical
        # math to dispatching probe_cohort(new_params, ...) separately
        stats = self._probe_cohort_impl(new_params, probe_batches, reqs,
                                        score_fn)
        return new_params, losses, stats

    def _probe_update_cohort_masked_impl(self, params: PyTree, batches: PyTree,
                                         masks: Array, sizes: Array, lr: Array,
                                         probe_batches: PyTree, cut: int,
                                         reqs: tuple = PROBE_KEYS,
                                         score_fn=None):
        new_params, losses = self._cohort_update_masked_impl(
            params, batches, masks, sizes, lr, cut)
        # the probe stays dense: selection utilities are needed for all L
        # layers, including the ones this round froze
        stats = self._probe_cohort_impl(new_params, probe_batches, reqs,
                                        score_fn)
        return new_params, losses, stats

    def probe_update_cohort_raw(self, params, batches, masks, sizes, lr,
                                probe_batches, reqs: tuple = PROBE_KEYS,
                                score_fn=None, cut: "int | None" = None):
        """Cohort update + next-round probe as ONE XLA program (async).

        probe_batches: (next_cohort, selection_batches, ...) pytree;
        cut: optional static prefix cut (see :meth:`cohort_update_raw`).
        Returns (new_params, losses, stats-dict) device arrays.
        """
        args = (params, batches, _dev_f32(masks), _dev_f32(sizes),
                _dev_f32(lr), probe_batches)
        if cut is None:
            return self._probe_update_cohort(*args, tuple(reqs), score_fn)
        # repro: allow[host-sync] -- cut is a static python int, not a device value
        return self._probe_update_cohort_masked(*args, int(cut), tuple(reqs),
                                                score_fn)

    # -- evaluation -----------------------------------------------------------
    def _eval_impl(self, params: PyTree, batch: PyTree):
        """One forward for both loss and accuracy: the hidden state is
        computed once and shared between the loss tail
        (``Model.loss_from_hidden``) and the accuracy logits — labeled
        batches used to pay for ``model.loss`` *and* a second
        ``forward_seq`` (regression test: tests/test_masked_engine.py)."""
        model = self.model
        with jax.named_scope("eval"):
            h, aux, prefix_len = model.forward_seq(params, batch)
            loss = model.loss_from_hidden(params, h, aux, prefix_len, batch)
            acc = jnp.zeros(())
            if "label" in batch:
                logits = model._head(params,
                                     jnp.mean(h, axis=1)[:, None])[:, 0]
                acc = jnp.mean((jnp.argmax(logits, -1)
                                == batch["label"]).astype(jnp.float32))
        return loss, acc

    def evaluate_raw(self, params, batch):
        """Async variant of :meth:`evaluate` (device scalars)."""
        return self._eval(params, batch)

    def evaluate(self, params, batch) -> tuple[float, float]:
        loss, acc = self._eval(params, batch)
        return float(loss), float(acc)
