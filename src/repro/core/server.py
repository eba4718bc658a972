"""The FL server: Algorithm 1 (selective layer fine-tuning in FL).

Single-host simulator with exact paper semantics: arbitrary per-client
masks, τ local steps, per-layer weighted aggregation, strategy-driven layer
selection with a configurable period.  The distributed pjit path
(sharding/fl_step.py) executes the same round math cohort-parallel on the
production mesh.

Two round engines (``FLServer(..., engine=...)``):

* ``"vectorized"`` (default) — the hot path is one XLA program per round:
  a single jitted step that vmaps the τ-step local update across the
  cohort and fuses the Eq.(5)-(7) weighted aggregation and Eq.(6) apply
  (Client.cohort_update); the selection probe is likewise one vmapped call
  over (cohort, selection_batches) (Client.probe_cohort).
* ``"sequential"`` — the paper-literal per-client loop, retained as the
  parity oracle.  Both engines draw identical per-client data and produce
  identical masks and params within fp tolerance
  (tests/test_round_engine.py).

A round is composed of explicit pipeline stages (DESIGN.md §5):

    plan → sample → probe → select → update → eval

:meth:`FLServer.run_round` executes them synchronously; the default
:meth:`FLServer.run` path for the vectorized engine streams them instead
(``pipeline=True``) through :class:`repro.core.scheduler.RoundScheduler`
— a depth-k lookahead pipeline (``pipeline_depth``, default 1): rounds
t+1..t+k are planned and sampled on the host while round t's jitted update
is still in flight (jax async dispatch), the host (P1) solve runs on a
background thread overlapped with the in-flight program, the t+1 selection
probe is dispatched on the not-yet-materialised updated params so it
overlaps the update on-device, and — when every round re-selects
(``selection_period == 1``) — probe and update are fused into a single XLA
program (Client.probe_update_cohort).  The scheduler consumes every host
rng and per-client data stream in exactly the same order as the
synchronous loop, so results are unchanged (tests/test_round_engine.py,
tests/test_scheduler.py).

Selection-period caching is per client id: probe statistics are cached at
refresh rounds (``t % selection_period == 0``) and masks are re-derived
every round from the *current* cohort's cached stats and budgets; cohort
members without cached stats are probed on demand.  (The previous
implementation reused the first ``len(cohort)`` mask rows computed for a
different cohort — wrong budgets and wrong clients.)

Pluggable seams (DESIGN.md §6): the strategy is resolved from the registry
(``fl.strategy`` string or a ``Strategy`` instance via the ``strategy``
kwarg) — its declared ``probe_requirements`` trim what the probes compute,
and score-based strategies fuse their device-side scoring into the
vectorized probe program.  ``data`` is any ``repro.api.Task``; its optional
``available_clients`` / ``drop_stragglers`` hooks act at the plan stage.
New code should construct servers through ``repro.api.Experiment``.
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.api.strategy import SelectionContext, Strategy, get_strategy
from repro.configs.base import FLConfig
from repro.core import aggregation as agg
from repro.core import masks as M
from repro.core.client import Client, probe_stats_dict
from repro.core.solver import greedy_rows
from repro.core.state import (ClientStateStore, rng_state_from_arrays,
                              rng_state_to_arrays, sub_state)
from repro.core.strategies import ProbeReport
from repro.faults.injector import TransientFault, coerce_injector
from repro.models.model import Model, supports_prefix_cut

PyTree = Any

# kept for back-compat; the engines now consult Strategy.probe_requirements
PROBE_STRATEGIES = ("snr", "rgn", "ours", "ours_unified")


@dataclass
class RoundRecord:
    round: int
    test_loss: float
    test_acc: float
    train_loss: float
    mask_matrix: np.ndarray
    cohort: np.ndarray
    union_frac: float
    uploaded_params: int
    wall_s: float


@dataclass
class History:
    records: list[RoundRecord] = field(default_factory=list)

    @staticmethod
    def _finite(r: RoundRecord) -> bool:
        return all(math.isfinite(v)
                   for v in (r.test_loss, r.test_acc, r.train_loss))

    def summary(self) -> dict:
        """Aggregate stats over the run.  Rounds poisoned by a non-finite
        loss/acc (e.g. an all-quarantined fault round) are *excluded* from
        final/best aggregates — NaN would silently propagate through them
        — and surfaced as ``nonfinite_rounds`` instead."""
        if not self.records:
            return {"final_loss": None, "final_acc": None, "best_acc": None,
                    "rounds": 0, "uploaded_params_total": 0,
                    "nonfinite_rounds": 0}
        clean = [r for r in self.records if self._finite(r)]
        last = clean[-1] if clean else None
        return {"final_loss": last.test_loss if last else None,
                "final_acc": last.test_acc if last else None,
                "best_acc": max(r.test_acc for r in clean) if clean else None,
                "rounds": len(self.records),
                "uploaded_params_total": sum(r.uploaded_params
                                             for r in self.records),
                "nonfinite_rounds": len(self.records) - len(clean)}

    def selection_heatmap(self) -> np.ndarray:
        """(T, L) count of clients selecting each layer — Figure 2 analogue."""
        return np.stack([r.mask_matrix.sum(0) for r in self.records])

    def to_json(self) -> dict:
        """JSON-serialisable dict (benchmarks/report.py consumes these)."""
        return {
            "summary": self.summary(),
            "records": [{
                "round": r.round, "test_loss": r.test_loss,
                "test_acc": r.test_acc, "train_loss": r.train_loss,
                "mask_matrix": np.asarray(r.mask_matrix).astype(int).tolist(),
                "cohort": np.asarray(r.cohort).astype(int).tolist(),
                "union_frac": r.union_frac,
                "uploaded_params": r.uploaded_params,
                "wall_s": r.wall_s,
            } for r in self.records]}

    @classmethod
    def from_json(cls, d: dict) -> "History":
        """Inverse of :meth:`to_json` (checkpoint restore path).  Mask and
        cohort entries come back as arrays of the engine's dtypes, so
        resumed histories compare equal to uninterrupted ones."""
        hist = cls()
        for r in d["records"]:
            hist.records.append(RoundRecord(
                round=int(r["round"]), test_loss=float(r["test_loss"]),
                test_acc=float(r["test_acc"]),
                train_loss=float(r["train_loss"]),
                mask_matrix=np.asarray(r["mask_matrix"], np.float32),
                cohort=np.asarray(r["cohort"], np.int64),
                union_frac=float(r["union_frac"]),
                uploaded_params=int(r["uploaded_params"]),
                wall_s=float(r["wall_s"])))
        return hist


@dataclass
class RoundPlan:
    """Host-side round schedule: who participates and who gets probed."""
    t: int
    cohort: np.ndarray
    budgets: np.ndarray
    sizes: np.ndarray
    probe_ids: np.ndarray    # cohort members needing a fresh probe (cohort order)
    refresh: bool            # full re-probe round (t % selection_period == 0)


@dataclass
class SampledRound:
    """All host-drawn data for one round (prefetchable ahead of time)."""
    plan: RoundPlan
    update_batches: dict                    # leaves (cohort, τ, B, ...)
    probe_batches: Optional[dict]           # leaves (len(probe_ids), sel, B, ...)


ENGINES = ("vectorized", "sequential")


class FLServer:
    def __init__(self, model: Model, fl: FLConfig,
                 data: "Task",
                 rng: Optional[np.random.RandomState] = None,
                 engine: str = "vectorized",
                 pipeline: Optional[bool] = None,
                 pipeline_depth: int = 1,
                 strategy: "Optional[Strategy | str]" = None,
                 mask_aware: Optional[bool] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 10,
                 faults: "Optional[object]" = None,
                 solver_deadline_s: Optional[float] = None):
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        if solver_deadline_s is not None and solver_deadline_s <= 0:
            raise ValueError(
                f"solver_deadline_s must be > 0, got {solver_deadline_s}")
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")
        if mask_aware and not supports_prefix_cut(model.cfg):
            raise ValueError(
                f"mask_aware=True but family {model.cfg.family!r} has no "
                f"prefix-cut path (models.model.supports_prefix_cut)")
        if mask_aware and engine != "vectorized":
            raise ValueError("mask_aware=True requires engine='vectorized' "
                             "(the sequential oracle stays dense)")
        self.model = model
        self.fl = fl
        self.data = data
        self.client = Client(model)
        self.rng = rng or np.random.RandomState(fl.seed)
        self.engine = engine
        # streaming round pipeline (vectorized engine only): depth-k host
        # prefetch + async solve + probe/update overlap, same results.
        # pipeline_depth = how many rounds ahead the scheduler plans/samples
        # (1 = the classic double buffer).
        self.pipeline = (engine == "vectorized") if pipeline is None else pipeline
        self.pipeline_depth = pipeline_depth
        # mask-aware compute engine (DESIGN.md §7): the vectorized update
        # skips the frozen-prefix backward, keyed on a static cut derived
        # from the round's masks.  Auto: on wherever the family's compute
        # order admits a prefix cut; the sequential oracle stays dense.
        self.mask_aware = (engine == "vectorized"
                           and supports_prefix_cut(model.cfg)
                           if mask_aware is None else bool(mask_aware))
        self.L = model.n_selectable
        self.layer_costs = None      # optional per-layer cost vector for (P1)
        # registry-resolved strategy (fl.strategy is the back-compat string
        # path; a Strategy instance or name passed here takes precedence)
        self.strategy = get_strategy(strategy if strategy is not None
                                     else fl.strategy)
        unknown = set(self.strategy.probe_requirements) - set(ProbeReport.KEYS)
        if unknown:
            raise ValueError(
                f"strategy {self.strategy.name!r} declares unknown "
                f"probe_requirements {sorted(unknown)}; the probe computes "
                f"{ProbeReport.KEYS}")
        # the probe computes only what the strategy declared it needs
        self._probe_reqs = tuple(k for k in ProbeReport.KEYS
                                 if k in self.strategy.probe_requirements)
        # device-side scoring fuses into the vectorized probe program; the
        # sequential oracle scores the uploaded stats on the host instead
        self._score_fn = (self.strategy.device_score_fn()
                          if engine == "vectorized" else None)
        # all per-client-id cross-round state — the probe-stat cache
        # (selection_period > 1, generation-invalidated at refresh), the
        # warm-start mask rows (a hint for the next (P1) solve via
        # SelectionContext.init; never cleared — solve outputs stay
        # budget-exact regardless), and last-seen rounds — lives in one
        # flat-array store indexed by client id: O(cohort) gather/scatter
        # per round at any population size, and the unit of round-boundary
        # checkpointing (save_state/restore_state)
        self.state = ClientStateStore(fl.n_clients, self.L)
        self._layer_params: Optional[np.ndarray] = None
        # _select_memo — (inputs-key, masks) of the last host solve; an
        # identical (cohort, budgets, stats, init) round skips the solve
        # entirely (the "unchanged utilities" early exit).  Deliberately
        # not checkpointed: a hit requires byte-identical inputs, under
        # which the solve is deterministic — dropping it on restore can
        # only change solve counters, never masks.
        # select_stats counts solves vs memo hits for tests/benchmarks.
        self._select_memo: Optional[tuple] = None
        self.select_stats = {"solves": 0, "memo_hits": 0,
                             "partial_warm_starts": 0,
                             "all_straggler_rounds": 0,
                             "quarantined_rows": 0, "dead_clients": 0,
                             "solver_timeouts": 0, "dispatch_retries": 0,
                             "ckpt_fallbacks": 0}
        self._straggler_warned = False
        # fault injection + graceful degradation (DESIGN.md §12): a
        # FaultPlan/FaultInjector (None = no injector).  A wired-but-
        # disabled injector never touches the round path — bit-identical
        # to no injector at all (tests/test_faults.py).
        self._injector = coerce_injector(faults)
        # optional *real* wall-clock deadline on the background (P1) solve
        # (scheduler path only; best-effort by nature — the deterministic
        # stall path is FaultPlan.stall_rate through select_round)
        self.solver_deadline_s = solver_deadline_s
        # round-boundary checkpointing (None = off): state is saved every
        # checkpoint_every completed rounds and at the end of run()
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every

    @property
    def _warm_masks(self):
        """Read-only dict-like view of the store's warm-mask rows
        (back-compat: iteration yields client ids, ``[id]``/``get`` return
        row copies)."""
        return self.state.warm_masks

    @property
    def needs_probe(self) -> bool:
        return bool(self._probe_reqs)

    # -- fault machinery (DESIGN.md §12) ---------------------------------
    @property
    def _faults_active(self) -> bool:
        return self._injector is not None and self._injector.enabled

    def _dispatch(self, t: int, fn, *args):
        """Run a round dispatch with bounded retry-with-backoff over
        *injected* transient failures.  Only :class:`TransientFault`
        retries — anything else is a real bug and propagates.  After
        ``max_dispatch_retries`` exhausted retries the fault re-raises:
        a permanently failing dispatch must kill the run loudly, not
        degrade it silently."""
        if not self._faults_active:
            return fn(*args)
        plan = self._injector.plan
        attempt = 0
        while True:
            try:
                self._injector.maybe_fail_dispatch(t, attempt)
                return fn(*args)
            except TransientFault:
                attempt += 1
                self.select_stats["dispatch_retries"] += 1
                if attempt > plan.max_dispatch_retries:
                    raise
                if plan.retry_backoff_s > 0:
                    time.sleep(plan.retry_backoff_s * (2 ** (attempt - 1)))

    # -- stage 1: plan ---------------------------------------------------
    def _budgets(self, cohort: np.ndarray) -> np.ndarray:
        return np.array([self.fl.budget_of(int(i)) for i in cohort])

    def _plan_for(self, cohort: np.ndarray, t: int) -> RoundPlan:
        fl = self.fl
        needs_probe = self.needs_probe
        refresh = needs_probe and t % fl.selection_period == 0
        if refresh:
            probe_ids = np.asarray(cohort)
        elif needs_probe:
            probe_ids = self.state.missing_stats(np.asarray(cohort))
        else:
            probe_ids = np.zeros((0,), np.int64)
        return RoundPlan(t=t, cohort=cohort, budgets=self._budgets(cohort),
                         sizes=self.data.sizes[cohort], probe_ids=probe_ids,
                         refresh=refresh)

    def plan_round(self, t: int) -> RoundPlan:
        """Draw the round-t cohort, honouring the task's plan-stage hooks.

        Tasks may expose ``available_clients(t, rng) -> ids`` (per-round
        availability: the cohort is drawn from the returned pool) and
        ``drop_stragglers(t, cohort, rng) -> keep-mask`` (members that fail
        to report this round are dropped before probing/budgeting).  Tasks
        without hooks — e.g. ``SyntheticFederatedData`` — consume the server
        rng exactly as before, so seeds and parity are unchanged.
        """
        with TraceAnnotation("fl.plan", round=t) as span:
            avail = getattr(self.data, "available_clients", None)
            pool = avail(t, self.rng) if callable(avail) else None
            if pool is None:                 # full availability: legacy rng path
                cohort = self.rng.choice(self.fl.n_clients,
                                         size=self.fl.cohort_size, replace=False)
            else:
                pool = np.asarray(pool)
                if pool.size == 0:
                    # an empty cohort would reach aggregation/np.mean(losses)
                    # and crash with an opaque error several stages later —
                    # fail at the plan stage with the actual cause instead
                    raise ValueError(
                        f"available_clients returned an empty pool for round "
                        f"{t}: no cohort can be drawn (the task's availability "
                        f"hook must return at least one client id, or None for "
                        f"full availability)")
                k = min(self.fl.cohort_size, len(pool))
                cohort = pool[self.rng.choice(len(pool), size=k, replace=False)]
            drop = getattr(self.data, "drop_stragglers", None)
            if callable(drop):
                keep = np.asarray(drop(t, cohort, self.rng), bool)
                if keep.shape != cohort.shape:
                    raise ValueError(
                        f"drop_stragglers returned keep-mask of shape "
                        f"{keep.shape} for a round-{t} cohort of shape "
                        f"{cohort.shape}")
                if keep.any():               # never drop the whole cohort
                    cohort = cohort[keep]
                else:
                    # every member straggled: the round runs on the full cohort
                    # (an empty round would crash downstream), but no longer
                    # silently — a run dominated by these is not degrading the
                    # way its straggler model says it should
                    self.select_stats["all_straggler_rounds"] += 1
                    if not self._straggler_warned:
                        warnings.warn(
                            f"round {t}: drop_stragglers marked the entire "
                            f"cohort; running it in full instead (counted in "
                            f"select_stats['all_straggler_rounds']; warning "
                            f"once per server)", stacklevel=2)
                        self._straggler_warned = True
            span.set_metadata(cohort=len(cohort))
            return self._plan_for(cohort, t)

    # -- stage 2: sample (host; prefetchable) ----------------------------
    def sample_round(self, plan: RoundPlan) -> SampledRound:
        """Draw all of this round's data.  Per-client stream order is probe
        batches first, then update batches — the order both engines consume
        them in, and the order the synchronous loop draws them in."""
        fl = self.fl
        with TraceAnnotation("fl.sample", round=plan.t,
                             cohort=len(plan.cohort)):
            probe_b = (self.data.cohort_batches(
                plan.probe_ids, fl.batch_size, fl.selection_batches)
                if len(plan.probe_ids) else None)
            update_b = self.data.cohort_batches(plan.cohort, fl.batch_size,
                                                fl.local_steps)
            # Explicit h2d here, in the (pipelined, overlapped) sample
            # stage: handing raw np batches to the jitted engines would be
            # an implicit transfer at dispatch time — REPRO_STRICT's
            # transfer guard rejects exactly that, and the copy would land
            # in the hot segment.
            update_b = jax.device_put(update_b)
            probe_b = jax.device_put(probe_b) if probe_b is not None else None
        return SampledRound(plan=plan, update_batches=update_b,
                            probe_batches=probe_b)

    # -- stage 3: probe (device) -----------------------------------------
    def probe_round(self, params: PyTree,
                    sampled: SampledRound) -> Optional[dict[str, np.ndarray]]:
        """Stat rows for ``plan.probe_ids`` (engine-specific compute)."""
        if sampled.probe_batches is None:
            return None
        if self.engine == "vectorized":
            return self.client.probe_cohort(params, sampled.probe_batches,
                                            self._probe_reqs, self._score_fn)
        nb = self.fl.selection_batches
        rows: list[dict[str, np.ndarray]] = []
        for r in range(len(sampled.plan.probe_ids)):
            acc = None
            for b in range(nb):
                batch = jax.tree.map(lambda x, r=r, b=b: x[r, b],
                                     sampled.probe_batches)
                out = self.client.probe(params, batch, self._probe_reqs)
                acc = out if acc is None else {k: acc[k] + out[k] for k in out}
            rows.append({k: v / nb for k, v in acc.items()})
        return {k: np.stack([r[k] for r in rows]) for k in rows[0]}

    # -- stage 4: select (host) ------------------------------------------
    def _warm_init(self, cohort: np.ndarray, probe: ProbeReport,
                   budgets: np.ndarray) -> Optional[np.ndarray]:
        """Warm-start rows for an iterative host solve: the cohort's
        previous converged masks.  Cohorts with *unseen* members no longer
        bail to a full cold start — unseen rows are greedily filled with
        the solver's own cold-start masks (``solver.greedy_rows`` on this
        round's utilities), so one new client cannot discard every other
        member's warm start (``select_stats["partial_warm_starts"]``
        counts these rounds)."""
        if not self.strategy.host or not self.state.has_warm:
            return None
        rows, valid = self.state.warm_rows(cohort)
        if not valid.all():
            if probe.grad_sq_norms is None:
                return None      # no utilities to greedy-fill from
            G = np.asarray(probe.grad_sq_norms)
            budgets = np.broadcast_to(np.asarray(budgets), (len(rows),))
            missing = np.flatnonzero(~valid)
            rows[missing] = greedy_rows(G[missing], budgets[missing],
                                        costs=self.layer_costs)
            self.select_stats["partial_warm_starts"] += 1
        return rows

    def _memo_key(self, plan: RoundPlan, probe: ProbeReport,
                  init: Optional[np.ndarray]) -> tuple:
        """Exact-inputs key for the host-solve memo: cohort ids, budgets, λ,
        layer costs, every present probe stat AND the warm-start init rows,
        byte-compared (no fp tolerance).

        The init must be part of the key: an iterative solver that stopped
        at ``max_iters`` without converging is *not* a pure function of the
        other inputs — a replay would freeze masks a real (differently
        warm-started) solve could still advance.  Since the warm rows are
        the previous solve's output, the memo simply starts hitting one
        round later, once the masks reach a fixed point.
        """
        stat_bytes = tuple(
            (k, v.tobytes()) for k, v in (
                (k, getattr(probe, k)) for k in (*ProbeReport.KEYS, "scores"))
            if v is not None)
        costs = (None if self.layer_costs is None
                 else np.asarray(self.layer_costs, np.float64).tobytes())
        return (np.asarray(plan.cohort, np.int64).tobytes(),
                np.asarray(plan.budgets, np.float64).tobytes(),
                float(self.fl.lam), costs, stat_bytes,
                None if init is None else init.astype(np.float32).tobytes())

    def select_round(self, plan: RoundPlan,
                     stats: Optional[dict[str, np.ndarray]]) -> np.ndarray:
        """Derive the round's masks (host).  For host strategies (the (P1)
        solvers) two accelerations apply, shared by the synchronous loop and
        the pipelined scheduler so parity is preserved by construction:
        a per-client-id warm start (``SelectionContext.init`` — a hint a
        strategy is free to ignore) and, for strategies declaring
        ``memoizable_select``, an early exit when (cohort, budgets,
        utilities) are byte-identical to the previous solve.
        """
        fl = self.fl
        if plan.refresh:
            self.state.clear_stats()     # generation bump: O(1), any n
        if stats is not None:
            self.state.set_stat_rows(plan.probe_ids, stats)
        if self.needs_probe:
            probe = ProbeReport(**self.state.stat_rows(plan.cohort))
        else:
            probe = ProbeReport(grad_sq_norms=np.zeros((len(plan.cohort),
                                                        self.L), np.float32))
        ctx = SelectionContext(client_ids=np.asarray(plan.cohort),
                               round=plan.t, lam=fl.lam,
                               costs=self.layer_costs, n_layers=self.L,
                               init=self._warm_init(plan.cohort, probe,
                                                    plan.budgets))
        if not self.strategy.host:
            with TraceAnnotation("fl.solve", round=plan.t, memo_hit=False,
                                 clients=len(plan.cohort)):
                return self.strategy.select(probe, plan.budgets, ctx)
        if self._faults_active and self._injector.solver_stalls(plan.t):
            # injected solver stall: the (P1) solve missed its deadline —
            # degrade to warm/greedy fallback masks instead of blocking
            # the round (deterministic per (seed, t); engine-uniform)
            return self._select_fallback(plan, probe)
        # the early exit only applies to strategies declaring their select
        # round-independent (Strategy.memoizable_select) — a custom host
        # strategy with e.g. an annealing schedule must never be replayed
        memoizable = getattr(self.strategy, "memoizable_select", False)
        with TraceAnnotation("fl.solve", round=plan.t,
                             clients=len(plan.cohort)) as span:
            key = (self._memo_key(plan, probe, ctx.init) if memoizable
                   else None)
            hit = (memoizable and self._select_memo is not None
                   and self._select_memo[0] == key)
            if hit:
                self.select_stats["memo_hits"] += 1
                masks = self._select_memo[1].copy()
            else:
                masks = self.strategy.select(probe, plan.budgets, ctx)
                self.select_stats["solves"] += 1
                if memoizable:
                    self._select_memo = (key, masks.copy())
            span.set_metadata(memo_hit=hit)
        self.state.set_warm_rows(plan.cohort, masks, t=plan.t)
        return masks

    def _select_fallback(self, plan: RoundPlan,
                         probe: Optional[ProbeReport]) -> np.ndarray:
        """Deadline-degraded masks: each member's previous converged mask
        (warm row) where one exists, a greedy solve on this round's
        utilities for unseen members, zeros (a forward-only round) when
        neither is available.  The memo is invalidated — fallback masks
        are not a solve output and must never be replayed as one — but
        they DO become the next round's warm start, exactly like real
        masks, so a recovered solver resumes from where degradation left
        the cohort."""
        self.select_stats["solver_timeouts"] += 1
        rows, valid = self.state.warm_rows(plan.cohort)
        if not valid.all() and probe is not None \
                and probe.grad_sq_norms is not None:
            G = np.asarray(probe.grad_sq_norms)
            budgets = np.broadcast_to(np.asarray(plan.budgets), (len(rows),))
            missing = np.flatnonzero(~valid)
            rows[missing] = greedy_rows(G[missing], budgets[missing],
                                        costs=self.layer_costs)
        self._select_memo = None
        self.state.set_warm_rows(plan.cohort, rows, t=plan.t)
        return rows

    def _fallback_rows(self, plan: RoundPlan) -> np.ndarray:
        """Read-only fallback for the scheduler's *real* wall-clock
        deadline (``solver_deadline_s``): warm rows where valid, zeros
        elsewhere.  Deliberately touches no store/memo state — the late
        solve is still running on the solver thread and remains the
        single writer (RoundScheduler joins it before anything reads
        what it wrote)."""
        self.select_stats["solver_timeouts"] += 1
        rows, _ = self.state.warm_rows(plan.cohort)
        return rows

    def select_masks(self, params: PyTree, cohort: np.ndarray,
                     t: int) -> np.ndarray:
        """Compat wrapper: plan + probe + select for an externally drawn
        cohort.  Masks always correspond to *this* cohort's clients and
        budgets (per-client stat caching — no stale rows).  Only probe
        batches are drawn — the caller owns the update draws."""
        plan = self._plan_for(np.asarray(cohort), t)
        probe_b = (self.data.cohort_batches(plan.probe_ids, self.fl.batch_size,
                                            self.fl.selection_batches)
                   if len(plan.probe_ids) else None)
        stats = self.probe_round(params, SampledRound(plan, {}, probe_b))
        return self.select_round(plan, stats)

    # -- stage 5: update (device) ----------------------------------------
    def _cut_for(self, masks: np.ndarray) -> Optional[int]:
        """The round's static prefix cut for the mask-aware engine, or None
        for the dense program.  Computed on host from the selected masks —
        selection always completes before update dispatch, in both the
        synchronous loop and the streaming scheduler."""
        return M.first_trainable_layer(masks) if self.mask_aware else None

    def update_round(self, params: PyTree, sampled: SampledRound,
                     masks: np.ndarray) -> tuple[PyTree, np.ndarray]:
        fl, plan = self.fl, sampled.plan
        if self._faults_active:
            return self._update_round_faulty(params, sampled, masks)
        if self.engine == "vectorized":
            return self.client.cohort_update(params, sampled.update_batches,
                                             masks, plan.sizes, fl.lr,
                                             cut=self._cut_for(masks))
        deltas, losses = [], []
        for row in range(len(plan.cohort)):
            batches = jax.tree.map(lambda x, row=row: x[row],
                                   sampled.update_batches)
            delta, loss = self.client.local_update(params, batches,
                                                   masks[row], fl.lr)
            deltas.append(delta)
            losses.append(loss)
        update = agg.aggregate(deltas, masks, plan.sizes, self.model.cfg)
        return agg.apply_update(params, update, fl.lr), np.asarray(losses)

    # -- stage 5, fault path (DESIGN.md §12) ------------------------------
    def _update_round_faulty(self, params: PyTree, sampled: SampledRound,
                             masks: np.ndarray
                             ) -> tuple[PyTree, np.ndarray]:
        """The round step with the injector live: mid-round client death
        (survivor-reweighted Eq.(7)), injected delta corruption, and the
        finite guard that quarantines poisoned rows before they touch the
        global params.  The vectorized engine runs the ONE guarded jitted
        variant (``cohort_update_guarded`` — survivors/codes are runtime
        arrays, no per-fault recompiles); the sequential engine is the
        survivors-only oracle the parity tests compare against.  Reported
        ``losses`` cover the rows that actually aggregated (``[nan]``
        when the whole cohort died — the record surfaces the poisoned
        round instead of faking a finite loss)."""
        fl, plan = self.fl, sampled.plan
        inj = self._injector
        fp = inj.plan
        survivors, codes = inj.round_faults(plan.t, len(plan.cohort))
        if self.engine == "vectorized":
            params, losses, ok = self._dispatch(
                plan.t, self.client.cohort_update_guarded, params,
                sampled.update_batches, masks, plan.sizes, fl.lr,
                survivors, codes, fp.explode_scale, fp.max_delta_sq)
        else:
            params, losses, ok = self._dispatch(
                plan.t, self._sequential_guarded, params, sampled, masks,
                survivors, codes)
        self._account_faults(survivors, ok)
        kept = np.asarray(losses)[np.asarray(ok) > 0]
        return params, (kept if kept.size
                        else np.asarray([np.nan], np.float32))

    def _sequential_guarded(self, params: PyTree, sampled: SampledRound,
                            masks: np.ndarray, survivors: np.ndarray,
                            codes: np.ndarray
                            ) -> tuple[PyTree, np.ndarray, np.ndarray]:
        """Paper-literal fault oracle: per-client updates, host-side
        corruption + finite guard, then Eq.(5)-(7) over exactly the
        surviving finite rows — the ground truth the guarded vectorized
        program must match (tests/test_faults.py parity (c))."""
        fl, plan = self.fl, sampled.plan
        fp = self._injector.plan
        deltas, losses = [], []
        for row in range(len(plan.cohort)):
            batches = jax.tree.map(lambda x, row=row: x[row],
                                   sampled.update_batches)
            delta, loss = self.client.local_update(params, batches,
                                                   masks[row], fl.lr)
            deltas.append(delta)
            losses.append(loss)
        ok = np.asarray(survivors, np.float32).copy()
        for i, code in enumerate(np.asarray(codes, np.int32)):
            if code:
                deltas[i] = self._corrupt_host(deltas[i], int(code),
                                               fp.explode_scale)
            finite, sq = True, np.float32(0.0)
            for leaf in jax.tree.leaves(deltas[i]):
                a = np.asarray(leaf, np.float32)  # repro: allow[host-sync] -- the sequential oracle is host-side by definition
                finite = finite and bool(np.isfinite(a).all())
                sq = np.float32(sq + a.astype(np.float32).ravel().dot(
                    a.astype(np.float32).ravel()))
            if not finite or not sq <= fp.max_delta_sq:
                ok[i] = 0.0
        idx = np.flatnonzero(ok > 0)
        if idx.size:                     # all-quarantined round: θ unchanged
            update = agg.aggregate([deltas[i] for i in idx],
                                   np.asarray(masks)[idx], plan.sizes[idx],
                                   self.model.cfg)
            params = agg.apply_update(params, update, fl.lr)
        return params, np.asarray(losses), ok

    @staticmethod
    def _corrupt_host(delta: PyTree, code: int, scale: float) -> PyTree:
        """Host twin of ``aggregation.corrupt_delta_rows`` for one client's
        delta tree (sequential oracle)."""
        if code == 3:
            return jax.tree.map(
                lambda x: np.asarray(x, np.float32) * np.float32(scale),
                delta)
        fill = np.nan if code == 1 else np.inf
        return jax.tree.map(
            lambda x: np.full_like(np.asarray(x, np.float32), fill), delta)

    def _account_faults(self, survivors: np.ndarray,
                        ok: np.ndarray) -> None:
        survivors = np.asarray(survivors)
        ok = np.asarray(ok)  # repro: allow[host-sync] -- fault accounting at the round boundary (sanctioned sync)
        self.select_stats["dead_clients"] += int((survivors <= 0).sum())
        self.select_stats["quarantined_rows"] += int(
            ((ok <= 0) & (survivors > 0)).sum())

    # -- stage 6: eval + record ------------------------------------------
    def _ensure_layer_params(self, params: PyTree) -> None:
        """Shape-only per-layer param counts; computed once, params not kept."""
        if self._layer_params is None:
            self._layer_params = M.count_layer_params(params, self.model.cfg)

    def _make_record(self, plan: RoundPlan, masks: np.ndarray,
                     train_loss: float, test_loss: float, test_acc: float,
                     wall_s: float) -> RoundRecord:
        # repro: allow[host-sync] -- round-boundary record finalisation on host np masks (lazy _finalize)
        uploaded = int(sum(int(masks[r] @ self._layer_params)
                           for r in range(len(plan.cohort))))
        return RoundRecord(
            round=plan.t, test_loss=test_loss, test_acc=test_acc,
            train_loss=train_loss, mask_matrix=masks, cohort=plan.cohort,
            union_frac=float(M.union_mask(masks).mean()),  # repro: allow[host-sync] -- host np mask matrix, no device value
            uploaded_params=uploaded, wall_s=wall_s)

    # ------------------------------------------------------------------
    def run_round(self, params: PyTree, t: int) -> tuple[PyTree, RoundRecord]:
        """One synchronous round: plan → sample → probe → select → update →
        eval.  The streaming :meth:`run` loop produces identical results."""
        t0 = time.time()  # repro: allow[nondeterminism] -- wall_s telemetry only, never an input to round math
        plan = self.plan_round(t)
        sampled = self.sample_round(plan)
        stats = self.probe_round(params, sampled)
        masks = self.select_round(plan, stats)
        self._ensure_layer_params(params)
        params, losses = self.update_round(params, sampled, masks)
        test_loss, test_acc = self.client.evaluate(params,
                                                   self.data.test_batch())
        rec = self._make_record(plan, masks, float(np.mean(losses)),
                                test_loss, test_acc, time.time() - t0)  # repro: allow[nondeterminism] -- wall_s telemetry only
        return params, rec

    # -- round-boundary checkpointing ------------------------------------
    def _is_ckpt_round(self, t_next: int, T: int) -> bool:
        """Save once ``t_next`` rounds have completed?  Boundaries fall
        every ``checkpoint_every`` rounds plus the end of the run."""
        if self.checkpoint_dir is None:
            return False
        return t_next % self.checkpoint_every == 0 or t_next == T

    def save_state(self, params: PyTree, t_next: int,
                   history: History) -> str:
        """Checkpoint the full resumable state after ``t_next`` completed
        rounds: params, the client-state store, the server rng, and (when
        the task exposes ``state_dict``) the task's stream state, as one
        flat-array tree; History and select_stats ride the manifest."""
        from repro.ckpt import save_checkpoint
        with TraceAnnotation("fl.checkpoint", round=t_next - 1):
            tree = {"params": params,
                    "client": self.state.state_dict(),
                    "server_rng": rng_state_to_arrays(self.rng)}
            task_sd = getattr(self.data, "state_dict", None)
            if callable(task_sd):
                tree["task"] = task_sd()
            extra = {"round": t_next, "history": history.to_json(),
                     "select_stats": dict(self.select_stats)}
            path = save_checkpoint(self.checkpoint_dir, t_next, tree,
                                   extra=extra)
        if self._faults_active:          # post-save media damage (DESIGN.md §12)
            self._injector.maybe_corrupt_checkpoint(path, t_next)
        return path

    def restore_state(self, params_template: PyTree,
                      step: Optional[int] = None
                      ) -> Optional[tuple[PyTree, int, History]]:
        """Restore the latest (or ``step``) checkpoint into this server.

        Returns ``(params, completed_rounds, history)``, or None when the
        checkpoint dir is unset/empty.  Params restore strictly against the
        template (shape-checked); store/rng/task namespaces restore
        byte-exact, so ``run(params, start=completed_rounds)`` continues
        bit-identically on masks.

        Self-healing (DESIGN.md §12): with no explicit ``step``, the scan
        verifies manifests + per-array checksums newest-first and resumes
        from the latest *intact* checkpoint, counting the fallback in
        ``select_stats["ckpt_fallbacks"]`` and warning with the skipped
        steps.  An explicit ``step`` is trusted as asked-for (corruption
        there surfaces as the underlying load error)."""
        from repro.ckpt import (latest_intact_step, load_checkpoint_arrays,
                                restore_checkpoint)
        if self.checkpoint_dir is None:
            return None
        fell_back = False
        if step is None:
            step, skipped = latest_intact_step(self.checkpoint_dir)
            if skipped:
                fell_back = True
                detail = "; ".join(f"step {s}: {why}" for s, why in skipped)
                warnings.warn(
                    f"skipping corrupt checkpoint(s) [{detail}]; resuming "
                    f"from {'step %d' % step if step is not None else 'scratch'}",
                    RuntimeWarning, stacklevel=2)
        if step is None:
            return None
        restored, _ = restore_checkpoint(self.checkpoint_dir,
                                         {"params": params_template}, step)
        flat, manifest = load_checkpoint_arrays(self.checkpoint_dir, step)
        self.state.load_state_dict(sub_state(flat, "client/"))
        rng_state_from_arrays(sub_state(flat, "server_rng/"), self.rng)
        task_state = sub_state(flat, "task/")
        task_ld = getattr(self.data, "load_state_dict", None)
        if task_state and callable(task_ld):
            task_ld(task_state)
        self._select_memo = None         # value-safe to drop (see __init__)
        extra = manifest["extra"]
        self.select_stats.update(extra.get("select_stats", {}))
        if fell_back:                    # after the update: the restored
            self.select_stats["ckpt_fallbacks"] += 1   # dict must not clobber it
        return (restored["params"], int(extra["round"]),
                History.from_json(extra["history"]))

    def run(self, params: PyTree, rounds: Optional[int] = None,
            verbose: bool = False, *, start: int = 0,
            history: Optional[History] = None) -> tuple[PyTree, History]:
        """Run rounds ``start..rounds-1`` (``start``/``history`` come from
        :meth:`restore_state` on resume), checkpointing at boundaries when
        ``checkpoint_dir`` is set."""
        T = rounds if rounds is not None else self.fl.rounds
        # legacy sampling redraws the test set every round (mutating
        # _test_rng) — hoisting eval data out of the loop would change its
        # semantics, so legacy runs always take the synchronous path
        legacy = getattr(self.data, "legacy_sampling", False)
        if self.engine == "vectorized" and self.pipeline and not legacy \
                and T > start:
            from repro.core.scheduler import RoundScheduler
            return RoundScheduler(self, depth=self.pipeline_depth).run(
                params, T, verbose, start=start, history=history)
        hist = history if history is not None else History()
        for t in range(start, T):
            params, rec = self.run_round(params, t)
            hist.records.append(rec)
            if verbose:
                self._print_round(rec)
            if self._is_ckpt_round(t + 1, T):
                self.save_state(params, t + 1, hist)
        return params, hist

    # -- streaming pipeline (repro.core.scheduler.RoundScheduler) ---------
    @staticmethod
    def _stats_np(stats_dev, t: int) -> Optional[dict[str, np.ndarray]]:
        """Materialise round ``t``'s raw probe result (the pipeline's one
        sync point)."""
        if stats_dev is None:
            return None
        with TraceAnnotation("fl.stats_sync", round=t,
                             probed=jax.tree.leaves(stats_dev)[0].shape[0]):
            return probe_stats_dict(stats_dev)

    def _finalize(self, entry: tuple) -> RoundRecord:
        plan, masks, losses, loss_dev, acc_dev, wall_s = entry
        with TraceAnnotation("fl.finalize", round=plan.t):
            # repro: allow[host-sync] -- the round boundary: lazy record finalisation is the sanctioned d2h point
            return self._make_record(plan, masks, float(np.mean(np.asarray(losses))),
                                     float(loss_dev), float(acc_dev), wall_s)  # repro: allow[host-sync] -- same round-boundary materialisation

    @staticmethod
    def _print_round(rec: RoundRecord) -> None:
        print(f"[round {rec.round:3d}] test_loss={rec.test_loss:.4f} "
              f"acc={rec.test_acc:.4f} union={rec.union_frac:.2f} "
              f"({rec.wall_s:.2f}s)")
