"""Device-side machinery for personalized-delta serving (DESIGN.md §9).

Two pieces:

* :class:`DeltaOverlay` — the capacity-C per-layer delta entry table the
  fused decode consumes.  Device state is ``{"slots": (L, C) int32 owner
  slot ids (-1 = free), "leaves": {name: (L, C, *shape)}}`` (shapes from
  :func:`overlay_shapes`); a host-side ``slot_ids`` mirror makes
  admit/release pure bookkeeping.  Admitting a
  user uploads only *their* delta rows (donated in-place entry writes);
  releasing a slot just marks entries free — the kernel neither reads nor
  applies a free entry's stale rows, so eviction is O(1) host work.

* :func:`serve_suite` — the jitted decode programs, registered in the
  same cache as the training suites (``core.client._JIT_CACHE``) so
  ``jit_cache_stats()["programs"]`` pins their counts: ONE program serves
  every mix of per-slot deltas (the overlay is data, not structure).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.client import _JIT_CACHE, _JIT_STATS
from repro.kernels.delta_matmul import d_minor
from repro.models.model import Model, _block_shapes, supports_delta_decode
from repro.serve.deltas import DeltaRecord


def overlay_shapes(cfg) -> dict:
    """Per-layer shape of each overlay leaf: a block leaf's own, except a
    (d, f) matrix that a TPU lays out d-minor, stored as (f, d) so that
    ``delta_matmul`` reads it in place, row-major (kernels/delta_matmul.py
    ``d_minor``)."""
    return {name: (tuple(shp[::-1]) if len(shp) == 2 and d_minor(*shp)
                   else tuple(shp))
            for name, shp in _block_shapes(cfg, "dense").items()}


def _write_entry(leaves: dict, l, c, rows: dict) -> dict:
    """Set entry (l, c) of every overlay leaf to the user's delta row (a
    matrix transposed where the leaf stores it as (f, d)).

    Jitted with the leaf dict donated: one compiled program per overlay
    shape, and each admit transfers only the (k,)-layer delta rows —
    never the (L, C, …) table.
    """
    out = {}
    for name, leaf in leaves.items():
        row = rows[name]
        if row.shape != leaf.shape[2:]:          # stored as (f, d)
            row = row.T
        out[name] = leaf.at[l, c].set(row.astype(leaf.dtype))
    return out


class DeltaOverlay:
    """Capacity-C per-layer delta entries over the scanned ``blocks`` stack.

    ``injector`` (a ``repro.faults.FaultInjector``, optional) makes entry
    uploads failable: each write retries up to ``max_upload_retries``
    times on :class:`TransientFault`; a permanently failed upload rolls
    the already-written entries back (owner ids cleared — the kernel masks
    the stale rows) and the admit reports False with
    ``stats["failed_admits"]`` bumped, so a fault never leaves a
    half-admitted user visible to the decode."""

    def __init__(self, model: Model, capacity: int, *,
                 injector=None, max_upload_retries: int = 3):
        if not supports_delta_decode(model.cfg):
            raise ValueError(
                f"family {model.cfg.family!r} has no delta-decode path")
        L = model.cfg.n_layers
        self.capacity = int(capacity)
        self.leaves = {
            name: jnp.zeros((L, self.capacity) + shp, jnp.float32)
            for name, shp in overlay_shapes(model.cfg).items()}
        self.slot_ids = np.full((L, self.capacity), -1, np.int32)
        self.entries: dict[int, list[tuple[int, int]]] = {}
        # live entries, which the decode's delta_matmul reads: kept by
        # admit and release so the serve loop never sums the table
        self.n_entries = 0
        self._slots_dev = jnp.asarray(self.slot_ids)
        self._dirty = False
        self._write = jax.jit(_write_entry, donate_argnums=0)
        self.injector = injector
        self.max_upload_retries = int(max_upload_retries)
        self.stats = {"upload_retries": 0, "failed_admits": 0}
        self._upload_seq = 0     # monotone entry-write counter (fault lane)

    def try_admit(self, slot: int, record: Optional[DeltaRecord],
                  step: int = -1) -> bool:
        """Claim one entry per selected layer for ``slot`` and upload the
        delta rows.  Returns False (writing nothing) if any layer's
        capacity is exhausted — the caller keeps the request queued.
        ``step``: the serve step admitting it, the id of its
        ``serve.upload`` spans."""
        self.release(slot)
        if record is None or record.n_layers == 0:
            self.entries[slot] = []
            return True
        extra = set(record.segments) - {"blocks"}
        if extra:
            raise ValueError(
                f"delta overlay only serves the scanned 'blocks' stack, "
                f"record touches {sorted(extra)}")
        rows_idx, leaves = record.segments["blocks"]
        plan = []
        taken: dict[int, int] = {}
        # repro: allow[host-sync] -- admission control runs at delta-publish time on the host row index, not per decode step
        for l in np.asarray(rows_idx, np.int32):
            li = int(l)  # repro: allow[host-sync] -- host np row index (admission time)
            free = np.nonzero(self.slot_ids[li] < 0)[0]
            free = free[taken.get(li, 0):]
            if free.size == 0:
                return False
            taken[li] = taken.get(li, 0) + 1
            plan.append((li, int(free[0])))  # repro: allow[host-sync] -- host np slot bookkeeping (admission time)
        ent = []
        for j, (li, c) in enumerate(plan):
            if not self._upload_entry(j, li, c, leaves, step):
                # permanent upload failure: roll back this admit's already-
                # written entries (owner -1 masks the stale leaf rows —
                # same O(1) trick as release) so no partial user is visible
                for rli, rc in ent:
                    self.slot_ids[rli, rc] = -1
                self.entries[slot] = []
                self._dirty = True
                self.stats["failed_admits"] += 1
                return False
            self.slot_ids[li, c] = slot
            ent.append((li, c))
        self.entries[slot] = ent
        self.n_entries += len(ent)
        self._dirty = True
        return True

    def _upload_entry(self, j: int, li: int, c: int, leaves: dict,
                      step: int) -> bool:
        """One entry write with bounded fault retry.  The injected failure
        fires BEFORE the donating write, so a failed attempt consumes no
        buffer and the retry re-reads intact overlay leaves."""
        from repro.faults.injector import TransientFault
        attempt = 0
        while True:
            seq = self._upload_seq
            self._upload_seq += 1
            try:
                if self.injector is not None and self.injector.enabled:
                    self.injector.maybe_fail_upload(seq)
            except TransientFault:
                attempt += 1
                if attempt > self.max_upload_retries:
                    return False
                self.stats["upload_retries"] += 1
                continue
            with TraceAnnotation("serve.upload", step=step):
                rows = {name: jnp.asarray(leaves[name][j])
                        for name in self.leaves}
                self.leaves = self._write(self.leaves, jnp.int32(li),
                                          jnp.int32(c), rows)
            return True

    def release(self, slot: int) -> None:
        for li, c in self.entries.pop(slot, []):
            self.slot_ids[li, c] = -1
            self.n_entries -= 1
            self._dirty = True

    def device(self) -> dict:
        """The ``delta`` argument for :meth:`Model.decode_step`."""
        if self._dirty:
            self._slots_dev = jnp.asarray(self.slot_ids)
            self._dirty = False
        return {"slots": self._slots_dev, "leaves": self.leaves}


def serve_suite(model: Model) -> dict:
    """Jitted serving programs, cached like the Client suites so
    ``jit_cache_stats()`` counts their traces.

    One trace per entry regardless of which users' deltas are resident:
    ``serve_decode`` (shared base), ``serve_decode_delta`` (base + overlay),
    ``serve_decode_dense`` (vmapped per-slot private params — the dense
    baseline), ``serve_reset_slot``, ``serve_write_params`` (dense refill).
    """
    key = (None if getattr(model, "custom_shard", False)
           else (model.cfg, model.runtime, "serve"))
    suite = _JIT_CACHE.get(key) if key is not None else None
    if suite is not None:
        _JIT_STATS["hits"] += 1
        return suite

    def _decode(params, tokens, pos, cache, window):
        return model.decode_step(params, tokens, pos, cache, window=window)

    def _decode_delta(params, tokens, pos, cache, delta, window):
        return model.decode_step(params, tokens, pos, cache, window=window,
                                 delta=delta)

    def _decode_dense(stacked, tokens, pos, cache, window):
        def one(p, tok, ps, kv):
            logits, nkv = model.decode_step(p, tok[None], ps[None], kv,
                                            window=window)
            return logits[0], nkv
        return jax.vmap(one)(stacked, tokens, pos, cache)

    def _write_params(stacked, p, b):
        return jax.tree.map(lambda s, x: s.at[b].set(x.astype(s.dtype)),
                            stacked, p)

    # decode entries never donate: the base params (and the dense bank)
    # are shared state serving EVERY slot across steps, and the parity
    # oracles replay one cache snapshot through shared/delta/dense
    # programs — the donated paths are the write programs below, whose
    # aliasing the program auditor verifies (donation-honored contract)
    suite = {
        "serve_decode": jax.jit(_decode, static_argnums=(4,)),  # repro: allow[donation-miss] -- shared base params + replayed cache snapshots outlive the call
        "serve_decode_delta": jax.jit(_decode_delta, static_argnums=(5,)),  # repro: allow[donation-miss] -- shared base params + replayed cache snapshots outlive the call
        "serve_decode_dense": jax.jit(_decode_dense, static_argnums=(4,)),  # repro: allow[donation-miss] -- the stacked bank is reused across decode steps; only refills rewrite it
        "serve_reset_slot": jax.jit(model.reset_slot,
                                    static_argnames=("stacked",)),
        "serve_write_params": jax.jit(_write_params, donate_argnums=0),
    }
    if key is None:
        _JIT_STATS["uncached"] += 1
    else:
        _JIT_CACHE[key] = suite
        _JIT_STATS["misses"] += 1
    return suite


# -- program-auditor enumeration hook ---------------------------------------

def serve_program_specs(model: Model, *, slots: int = 3, capacity: int = 2,
                        capacities: tuple = (1, 2, 3), max_seq: int = 16,
                        window: int = 0) -> list[dict]:
    """Shape-only audit specs for every serving program family.

    Covers shared decode, the delta decode at batch ``slots`` AND
    ``2·slots`` for each overlay capacity in ``capacities`` (the auditor's
    B-independence / C-linearity contract reads these), the dense vmapped
    baseline at both batches (its weight traffic MUST scale with B — the
    contrast that makes the delta contract meaningful), and the two donated
    writes (overlay entry write, dense bank refill).  Plain dicts; nothing
    allocates.
    """
    from repro.models.model import init_params
    suite = serve_suite(model)
    cfg = model.cfg
    SDS = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            SDS((2,), jnp.uint32))
    L = cfg.n_layers

    def cache_for(b):
        return jax.eval_shape(lambda: model.init_cache(
            b, max_seq, window=window, per_slot=True))

    def toks_pos(b):
        return SDS((b,), jnp.int32), SDS((b,), jnp.int32)

    base = dict(static_argnums=(), donate_argnums=(), weight_argnums=(0,))
    common = {"single_host": True, "dtype": cfg.dtype}
    specs = []
    for b in (slots, 2 * slots):
        tok, pos = toks_pos(b)
        specs.append(dict(
            base, name=f"serve_decode/B{b}", fn=suite["serve_decode"],
            args=(params, tok, pos, cache_for(b), window),
            static_argnums=(4,),
            meta=dict(common, kind="serve_decode", batch=b)))
    if supports_delta_decode(cfg):
        shapes = overlay_shapes(cfg)
        for b in (slots, 2 * slots):
            tok, pos = toks_pos(b)
            for C in capacities:
                delta = {
                    "slots": SDS((L, C), jnp.int32),
                    "leaves": {name: SDS((L, C) + tuple(shp), jnp.float32)
                               for name, shp in shapes.items()}}
                specs.append(dict(
                    base, name=f"serve_decode_delta/B{b}/C{C}",
                    fn=suite["serve_decode_delta"],
                    args=(params, tok, pos, cache_for(b), delta, window),
                    static_argnums=(5,), weight_argnums=(0, 4),
                    meta=dict(common, kind="serve_decode_delta", batch=b,
                              capacity=C)))
        leaves = {name: SDS((L, capacity) + tuple(shp), jnp.float32)
                  for name, shp in shapes.items()}
        rows = {name: SDS(tuple(shp), jnp.float32)
                for name, shp in _block_shapes(cfg, "dense").items()}
        specs.append(dict(
            base, name="serve_write_delta_entry",
            fn=jax.jit(_write_entry, donate_argnums=0),
            args=(leaves, SDS((), jnp.int32), SDS((), jnp.int32), rows),
            donate_argnums=(0,),
            meta=dict(common, kind="delta_write", donates=True)))
    for b in (slots, 2 * slots):
        tok, pos = toks_pos(b)
        stacked = jax.eval_shape(lambda t: stack_tree(t, b), params)
        dense_cache = jax.eval_shape(lambda: stack_tree(
            model.init_cache(1, max_seq, window=window, per_slot=True), b))
        specs.append(dict(
            base, name=f"serve_decode_dense/B{b}",
            fn=suite["serve_decode_dense"],
            args=(stacked, tok, pos, dense_cache, window),
            static_argnums=(4,),
            meta=dict(common, kind="serve_decode_dense", batch=b)))
    stacked = jax.eval_shape(lambda t: stack_tree(t, slots), params)
    specs.append(dict(
        base, name="serve_write_params", fn=suite["serve_write_params"],
        args=(stacked, params, SDS((), jnp.int32)),
        donate_argnums=(0,), weight_argnums=(0, 1),
        meta=dict(common, kind="dense_write", donates=True)))
    return specs


def stack_tree(tree, n: int):
    """n identical copies along a new leading axis (dense-baseline layout)."""
    return jax.tree.map(lambda x: jnp.repeat(jnp.asarray(x)[None], n, axis=0),
                        tree)
