"""Depth-k lookahead round scheduler with async (P1) solver overlap.

Replaces the hand-rolled double buffer that used to live in
``FLServer._run_pipelined``: the streaming round pipeline is now a
subsystem that (a) plans and samples rounds t+1..t+k on the host while the
round-t device program is still in flight, (b) runs the host layer-selection
solve — materialising the probe stats and solving (P1) — on a background
thread so it overlaps both the in-flight XLA program and the host-side
prefetch, and (c) keeps the device-side structure of the double buffer:
the t+1 selection probe rides round t's update program (fused into one XLA
program when ``selection_period == 1``, chained on the params future
otherwise).

Parity contract (tests/test_scheduler.py, tests/test_round_engine.py): the
scheduler is a pure *scheduling* change — cohorts and masks are
bit-identical to the synchronous :meth:`FLServer.run_round` loop and params
agree within fp tolerance, at every depth, including under Task
availability/straggler hooks.  Three orderings pin when work may fire:

* **Server rng** — ``plan_round`` consumes the server RandomState (cohort
  draw + availability/straggler hooks), so plans must fire in round order.
  The prefetch queue issues them strictly ascending.
* **Per-client data streams** — each client's rng must see round t's draws
  (probe before update) before round t+1's.  ``sample_round`` draws a whole
  round at enqueue time, so queue order preserves stream order.
* **Stats-cache reads** — with ``selection_period > 1`` a non-refresh
  ``plan_round(t+1)`` reads the per-client stats cache as left by
  select(t), so its plan may only fire once that select completed
  (:meth:`RoundScheduler._can_plan`).  Refresh rounds and probe-free
  strategies are cache-free and may plan arbitrarily deep — with
  ``selection_period == 1`` the full depth-k lookahead is always available.

The select stage itself never touches an rng and only the solver thread
mutates the server's stats/warm-mask caches (one solve in flight at a
time), so running it concurrently with host sampling is race-free.

``wall_s`` in pipelined records is the *host* time per round (async-select
submit → dispatch complete, including the prefetch that ran inside the
round), not device latency: in-flight rounds report milliseconds and the
end-of-run drain is excluded, so ``sum(wall_s)`` ≤ total elapsed run time
(pinned in tests/test_scheduler.py).  ``verbose=True`` never syncs the
just-dispatched round: round t's record is printed at the end of iteration
t+1, when its program has long been retired — printing no longer destroys
the overlap it is reporting on.

Each stage runs inside a ``jax.profiler`` span that carries its round
(``fl.round`` per iteration; ``fl.select_wait``, ``fl.dispatch``,
``fl.eval`` on this thread; ``fl.select`` on the solver thread; ``fl.plan``,
``fl.sample``, ``fl.stats_sync``, ``fl.solve``, ``fl.finalize`` and
``fl.checkpoint`` in ``FLServer``), so a profiler trace puts the device's
idle gaps down to a stage; PERF.md §3 lists what reads each.
"""
from __future__ import annotations

import time
from collections import deque

import jax
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from typing import Any, Optional

from repro.core.server import (FLServer, History, RoundRecord, SampledRound)

PyTree = Any


class RoundScheduler:
    """Depth-k streaming executor for ``FLServer``'s round stages.

    ``depth`` is how many rounds ahead of the in-flight round the host
    plans and samples; ``depth=1`` reproduces the classic double buffer.
    A scheduler instance drives one ``run`` at a time (it owns a
    single-worker solver thread for the duration of the run).
    """

    def __init__(self, server: FLServer, depth: int = 1):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.server = server
        self.depth = depth
        self._queue: deque[SampledRound] = deque()   # rounds, t ascending
        self._next_plan = 0          # next round index to plan (rng order)
        self._selected_through = -1  # highest t whose select completed
        self._barrier = -1           # next unsaved checkpoint boundary
        self._late = None            # (future, t) of a deadline-missed solve

    # -- host prefetch ----------------------------------------------------
    def _next_barrier(self, after: int, T: int) -> int:
        """The first checkpoint boundary past ``after`` (T+1 = none left).
        Planning round b consumes the server rng and client data streams,
        so rounds at/after an unsaved boundary must not be planned — a
        checkpoint written at b would otherwise capture post-b draws and
        break bit-exact resume."""
        srv = self.server
        if srv.checkpoint_dir is None:
            return T + 1
        b = after + 1
        while b <= T and not srv._is_ckpt_round(b, T):
            b += 1
        return b if b <= T else T + 1

    def _can_plan(self, t: int) -> bool:
        """May ``plan_round(t)`` fire now?  Plans always fire in t order
        (queue discipline); additionally a non-refresh plan's probe_ids
        read the stats cache as left by select(t-1), and no plan may cross
        an unsaved checkpoint boundary (:meth:`_next_barrier`)."""
        srv = self.server
        if t >= self._barrier:
            return False
        if not srv.needs_probe or t % srv.fl.selection_period == 0:
            return True
        return self._selected_through >= t - 1

    def _prefetch(self, T: int, want: int) -> None:
        """Top the queue up to ``want`` pending rounds (plan + sample)."""
        while (self._next_plan < T and len(self._queue) < want
               and self._can_plan(self._next_plan)):
            plan = self.server.plan_round(self._next_plan)
            self._queue.append(self.server.sample_round(plan))
            self._next_plan += 1

    # -- async select -----------------------------------------------------
    def _join_late(self, block: bool) -> None:
        """Join a deadline-missed solve (DESIGN.md §12).  The late solver
        thread is still the store's single writer — once it lands, its
        warm-row/stats-cache writes unblock the cache-dependent plans that
        :meth:`_can_plan` kept gated on ``_selected_through``.  Called
        non-blocking each iteration and blocking before a checkpoint save
        (the barrier must capture a settled store)."""
        if self._late is None:
            return
        fut, t_late = self._late
        if not block and not fut.done():
            return
        fut.result()
        self._selected_through = max(self._selected_through, t_late)
        self._late = None

    def _select(self, plan, stats_dev):
        """Solver-thread body: materialise the probe stats (the pipeline's
        one device sync) and run the host selection.  Mutates only the
        server's stats/warm-mask caches — reads of those by the main thread
        are gated on this select having completed (:meth:`_can_plan`)."""
        srv = self.server
        with TraceAnnotation("fl.select", round=plan.t):
            return srv.select_round(plan, srv._stats_np(stats_dev, plan.t))

    # -- the round loop ---------------------------------------------------
    def run(self, params: PyTree, T: int, verbose: bool, start: int = 0,
            history: Optional[History] = None) -> tuple[PyTree, History]:
        srv = self.server
        fl, client = srv.fl, srv.client
        reqs, score_fn = srv._probe_reqs, srv._score_fn
        fuse = srv.needs_probe and fl.selection_period == 1
        srv._ensure_layer_params(params)
        # hoisted once for the whole run; explicit h2d so the per-round
        # evaluate_raw dispatch never pays (or strict-mode-trips on) an
        # implicit np→device transfer
        test = jax.device_put(srv.data.test_batch())

        self._next_plan = start
        self._selected_through = start - 1
        self._barrier = self._next_barrier(start, T)
        prefix = list(history.records) if history is not None else []

        self._prefetch(T, self.depth)
        sampled = self._queue.popleft()              # round `start`
        stats_dev = (client.probe_cohort_raw(params, sampled.probe_batches,
                                             reqs, score_fn)
                     if sampled.probe_batches is not None else None)
        pending: list = []       # raw entries; finalized lazily (verbose)
        printed = 0              # pending entries already printed (in order)
        pool = ThreadPoolExecutor(max_workers=1,
                                  thread_name_prefix="p1-solver")
        try:
            for t in range(start, T):
                with StepTraceAnnotation("fl.round", step_num=t):
                    t0 = time.time()  # repro: allow[nondeterminism] -- wall_s telemetry only, never an input to round math
                    self._join_late(block=False)
                    plan = sampled.plan
                    # the host solve (stats sync + (P1)) overlaps the
                    # in-flight device program *and* the prefetch below
                    masks_fut = pool.submit(self._select, plan, stats_dev)
                    # lookahead: sample rounds t+1..t+depth whose plans
                    # are cache-free while the solver thread works
                    self._prefetch(T, self.depth)
                    with TraceAnnotation("fl.select_wait", round=t) as wait:
                        fallback = False
                        if srv.solver_deadline_s is None:
                            masks = masks_fut.result()
                            self._selected_through = t
                        else:
                            try:
                                masks = masks_fut.result(
                                    timeout=srv.solver_deadline_s)
                                self._selected_through = t
                            except FutureTimeout:
                                # degrade, don't stall: round t proceeds on
                                # the warm-start rows while the solve
                                # finishes in the background (read-only
                                # fallback — the solver thread stays the
                                # store's single writer).
                                # _selected_through is NOT bumped, so cache-
                                # dependent plans stay gated until _join_late.
                                masks = srv._fallback_rows(plan)
                                self._late = (masks_fut, t)
                                fallback = True
                        wait.set_metadata(fallback=fallback)
                    # cache-dependent plans (selection_period > 1,
                    # non-refresh) unblock once select(t) has landed in
                    # the stats cache
                    self._prefetch(T, self.depth)

                    # mask-aware engine: the static prefix cut is derived
                    # from the just-solved masks, so the update program
                    # skips the frozen layers' backward (None = dense;
                    # DESIGN.md §7)
                    cut = srv._cut_for(masks)
                    nxt = self._queue[0] if self._queue else None
                    probe_next = (nxt is not None
                                  and nxt.probe_batches is not None)
                    fused = fuse and probe_next and not srv._faults_active
                    nstats = None
                    with TraceAnnotation(
                            "fl.dispatch", round=t,
                            cut=-1 if cut is None else cut,
                            probed=len(nxt.plan.probe_ids) if probe_next
                            else 0, fused=fused):
                        if srv._faults_active:
                            # fault path (DESIGN.md §12): the guarded round
                            # step replaces the fused/chained dispatch —
                            # ONE extra compiled program, survivors/codes
                            # as runtime arrays
                            params, losses = srv._update_round_faulty(
                                params, sampled, masks)
                            if probe_next:
                                nstats = client.probe_cohort_raw(
                                    params, nxt.probe_batches, reqs,
                                    score_fn)
                        elif fused:
                            # round t+1's probe rides round t's update
                            # program
                            params, losses, nstats = \
                                client.probe_update_cohort_raw(
                                    params, sampled.update_batches, masks,
                                    plan.sizes, fl.lr, nxt.probe_batches,
                                    reqs, score_fn, cut=cut)
                        else:
                            params, losses = client.cohort_update_raw(
                                params, sampled.update_batches, masks,
                                plan.sizes, fl.lr, cut=cut)
                            if probe_next:
                                # chained on the params future: overlaps
                                # the update on-device, no host round-trip
                                # in between
                                nstats = client.probe_cohort_raw(
                                    params, nxt.probe_batches, reqs,
                                    score_fn)
                    with TraceAnnotation("fl.eval", round=t):
                        loss_dev, acc_dev = client.evaluate_raw(params, test)
                    pending.append((plan, masks, losses, loss_dev, acc_dev,
                                    time.time() - t0))  # repro: allow[nondeterminism] -- wall_s telemetry only
                    if verbose:
                        # print up to the *previous* round: its program
                        # has retired, so materialising it cannot stall
                        # the round just dispatched (printing used to sync
                        # every round)
                        while printed < len(pending) - 1:
                            if not isinstance(pending[printed], RoundRecord):
                                pending[printed] = srv._finalize(
                                    pending[printed])
                            srv._print_round(pending[printed])
                            printed += 1
                    if t + 1 == self._barrier:
                        # checkpoint boundary: the prefetch gate drained
                        # the queue here (no round past the boundary was
                        # planned), so syncing params + pending records
                        # captures exactly the synchronous loop's state
                        # after round t
                        self._join_late(block=True)
                        for i in range(len(pending)):
                            if not isinstance(pending[i], RoundRecord):
                                pending[i] = srv._finalize(pending[i])
                        srv.save_state(params, t + 1,
                                       History(records=prefix + pending))
                        self._barrier = self._next_barrier(t + 1, T)
                        self._prefetch(T, self.depth)
                        if self._queue:
                            # restart the stream: the boundary round's
                            # probe runs standalone on the just-saved
                            # params (same math as the fused/chained
                            # dispatch — pinned by the engine-parity tests)
                            sampled = self._queue.popleft()
                            stats_dev = (client.probe_cohort_raw(
                                params, sampled.probe_batches, reqs,
                                score_fn)
                                if sampled.probe_batches is not None
                                else None)
                    elif self._queue:
                        sampled, stats_dev = self._queue.popleft(), nstats
        finally:
            pool.shutdown(wait=True)

        hist = History(records=prefix)
        for i, p in enumerate(pending):              # end-of-run drain
            rec = p if isinstance(p, RoundRecord) else srv._finalize(p)
            if verbose and i >= printed:
                srv._print_round(rec)
            hist.records.append(rec)
        return params, hist
