"""Batched serving driver: slot-based continuous batching over decode_step.

A minimal production-shaped server loop: a fixed pool of B slots, each
holding one request; finished slots are refilled from the queue without
stalling the running batch.  The KV cache is the ``per_slot`` layout
(models/model.py ``init_cache(per_slot=True)``), so every slot advances
its own position — refills never align the batch.

Three personalization modes (DESIGN.md §9):

* ``shared`` — every request decodes against the base parameters.
* ``delta``  — per-user selected-layer deltas ride a capacity-C
  :class:`repro.serve.DeltaOverlay`; ONE jitted decode program serves
  slots with *different* users' deltas.
* ``dense``  — the honest baseline: each slot holds the user's private
  full-parameter copy (materialised on refill), decode is vmapped over
  the stacked per-slot params.

Each pass of the loop is a ``serve.step`` profiler span (its counters:
occupied slots, slots fed a prompt token whose output is discarded, queued
requests, and in delta mode the live overlay entries) holding
``serve.admit``, ``serve.feed``, ``serve.dispatch``, ``serve.sync`` and
``serve.bookkeep``; PERF.md §3 lists what reads each.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --slots 4 --requests 10 --max-new 16 --mode delta --delta-layers 2
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import RuntimeConfig, get_arch, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.serve import DeltaOverlay, DeltaStore, serve_suite, stack_tree


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    user_id: int = -1                       # -1: anonymous (base params)
    generated: list[int] = field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new


class SlotServer:
    """B decode slots over a single jitted decode_step.

    ``mode``: "shared" | "delta" | "dense" (see module docstring); the
    latter two look requests' ``user_id`` up in ``store``.
    """

    def __init__(self, model: Model, params, slots: int, max_seq: int,
                 window: int = 0, *, mode: str = "shared",
                 store: Optional[DeltaStore] = None, capacity: int = 0,
                 admit_retries: int = 16, max_slot_retries: int = 2,
                 injector=None):
        assert mode in ("shared", "delta", "dense"), mode
        if mode != "shared" and store is None:
            raise ValueError(f"mode={mode!r} needs a DeltaStore")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.window = window
        self.mode = mode
        self.store = store
        self.suite = serve_suite(model)
        self.active: list[Request | None] = [None] * slots
        self.pos = np.zeros(slots, np.int32)        # per-slot position
        # degradation policy (DESIGN.md §12): a request that cannot admit
        # after admit_retries attempts, or whose slot is struck more than
        # max_slot_retries times, is dropped (self.dropped) instead of
        # livelocking the loop / killing the batch
        self.admit_retries = int(admit_retries)
        self.max_slot_retries = int(max_slot_retries)
        self.injector = injector
        self.dropped: list[Request] = []
        self._admit_attempts: dict[int, int] = {}   # rid -> failed admits
        self._fail_counts: dict[int, int] = {}      # rid -> slot strikes
        self._dropped_requests = 0
        self._slot_failures = 0
        if mode == "dense":
            # stacked per-slot state: private params + a batch-1 cache per slot
            self.bank = stack_tree(params, slots)
            self.cache = stack_tree(
                model.init_cache(1, max_seq, window=window, per_slot=True),
                slots)
        else:
            self.cache = model.init_cache(slots, max_seq, window=window,
                                          per_slot=True)
            self.overlay = (DeltaOverlay(model, capacity or slots,
                                         injector=injector)
                            if mode == "delta" else None)

    def _record(self, req: Request):
        if self.store is None or req.user_id < 0:
            return None
        return self.store.get(req.user_id)

    def _free(self, i: int) -> None:
        self.active[i] = None
        if self.mode == "delta":
            self.overlay.release(i)

    def _drop(self, req: Request, why: str) -> None:
        self.dropped.append(req)
        self._dropped_requests += 1
        self._admit_attempts.pop(req.rid, None)
        self._fail_counts.pop(req.rid, None)
        print(f"  dropping request {req.rid} (user {req.user_id}): {why}")

    def _admit(self, queue: list[Request], step: int = -1) -> int:
        """Fill free slots from the head of ``queue`` at serve step
        ``step``; the number admitted."""
        admitted = 0
        for i in range(self.slots):
            if self.active[i] is not None or not queue:
                continue
            if self.mode == "delta":
                req = None
                while queue:
                    head = queue[0]
                    if self.overlay.try_admit(i, self._record(head),
                                              step=step):
                        req = queue.pop(0)
                        break
                    # overlay full for this request: bounded retry, then
                    # drop — the old unconditional requeue livelocked the
                    # loop when the head request could never fit
                    n = self._admit_attempts.get(head.rid, 0) + 1
                    self._admit_attempts[head.rid] = n
                    if n > self.admit_retries:
                        queue.pop(0)
                        self._drop(head, f"no overlay capacity after "
                                         f"{n - 1} admit attempts")
                        continue    # head dropped: try the next request
                    break           # keep queued; retry after a release
                if req is None:
                    continue        # nothing admissible for this slot now
            else:
                req = queue.pop(0)
            self._admit_attempts.pop(req.rid, None)
            if self.mode == "dense":
                private = (self.store.materialize(self.params, req.user_id)
                           if req.user_id >= 0 else self.params)
                self.bank = self.suite["serve_write_params"](
                    self.bank, private, jnp.int32(i))
                self.cache = self.suite["serve_reset_slot"](
                    self.cache, jnp.int32(i), stacked=True)
            else:
                self.cache = self.suite["serve_reset_slot"](
                    self.cache, jnp.int32(i))
            self.active[i] = req
            self.pos[i] = 0
            admitted += 1
        return admitted

    def _decode(self, toks, pos):
        if self.mode == "shared":
            return self.suite["serve_decode"](self.params, toks, pos,
                                              self.cache, self.window)
        if self.mode == "delta":
            return self.suite["serve_decode_delta"](
                self.params, toks, pos, self.cache, self.overlay.device(),
                self.window)
        return self.suite["serve_decode_dense"](self.bank, toks, pos,
                                                self.cache, self.window)

    def run(self, requests: list[Request], verbose: bool = False):
        queue = list(requests)
        done: list[Request] = []
        steps = 0
        t0 = time.time()  # repro: allow[nondeterminism] -- serve wall-clock telemetry only
        while queue or any(r is not None for r in self.active):
            # every span of one pass carries the id of the decode step it
            # runs (``steps`` before the increment)
            with StepTraceAnnotation("serve.step", step_num=steps) as span:
                with TraceAnnotation("serve.admit", step=steps) as admit:
                    admit.set_metadata(admitted=self._admit(queue, steps))
                if queue and all(r is None for r in self.active):
                    # nothing admitted onto an idle server: skip the decode
                    # — admit attempts ramp every pass, so the stuck head
                    # is dropped within admit_retries iterations (no
                    # livelock, no RuntimeError: the batch degrades
                    # instead of dying)
                    continue
                with TraceAnnotation("serve.feed", step=steps):
                    toks = np.zeros(self.slots, np.int32)
                    active = prompt = 0
                    for i, r in enumerate(self.active):
                        if r is None:
                            continue
                        p = int(self.pos[i])  # repro: allow[host-sync] -- self.pos is the host np position mirror, no device value
                        toks[i] = (r.prompt[p] if p < len(r.prompt)
                                   else r.generated[-1])
                        active += 1
                        # a prompt token but the last: its output is
                        # discarded
                        prompt += p < len(r.prompt) - 1
                counts = dict(active=active, prompt=prompt,
                              queued=len(queue))
                if self.mode == "delta":
                    counts["entries"] = self.overlay.n_entries
                span.set_metadata(**counts)
                # per-slot position vector: each slot decodes at its own
                # stream position; empty slots idle at 0 and are masked on
                # refill
                with TraceAnnotation("serve.dispatch", step=steps):
                    logits, self.cache = self._decode(jnp.asarray(toks),
                                                      jnp.asarray(self.pos))
                with TraceAnnotation("serve.sync", step=steps):
                    # repro: allow[host-sync] -- the serve loop's one sanctioned sync: greedy feedback, next token depends on this step's logits
                    nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
                with TraceAnnotation("serve.bookkeep", step=steps):
                    steps += 1
                    for i, r in enumerate(self.active):
                        if r is None:
                            continue
                        self.pos[i] += 1
                        if self.pos[i] >= len(r.prompt):
                            r.generated.append(int(nxt[i]))  # repro: allow[host-sync] -- nxt already materialised at the sanctioned sync above
                        if r.done or self.pos[i] >= self.max_seq - 1:
                            done.append(r)
                            self._free(i)
                    if self.injector is not None and self.injector.enabled:
                        # injected slot failures (DESIGN.md §12): the struck slot's
                        # request loses its progress; bounded per-request retries
                        # from scratch (generated cleared — admit resets pos/cache),
                        # then dropped
                        struck = self.injector.slot_faults(steps, self.slots)
                        for i in np.flatnonzero(struck):
                            r = self.active[i]
                            if r is None:
                                continue
                            self._slot_failures += 1
                            self._free(int(i))  # repro: allow[host-sync] -- i is a host np index from the injector's host draw
                            n = self._fail_counts.get(r.rid, 0) + 1
                            self._fail_counts[r.rid] = n
                            if n > self.max_slot_retries:
                                self._drop(r, f"slot failed {n} times")
                            else:
                                r.generated.clear()
                                queue.append(r)
                    if verbose and steps % 8 == 0:
                        print(f"  step {steps}: {sum(x is not None for x in self.active)}"
                              f" active, {len(queue)} queued, {len(done)} done")
        dt = time.time() - t0  # repro: allow[nondeterminism] -- serve wall-clock telemetry only
        gen = sum(len(r.generated) for r in done)
        return done, {"steps": steps, "wall_s": dt, "gen_tokens": gen,
                      "tok_per_s": gen / dt if dt > 1e-9 else 0.0,
                      "dropped_requests": self._dropped_requests,
                      "slot_failures": self._slot_failures}


def demo_store(model: Model, params, users: int, layers_per_user: int,
               seed: int = 0) -> DeltaStore:
    """A store of synthetic per-user deltas: small noise on a random
    selected-layer subset per user (stand-in for real FL output)."""
    cfg = model.cfg
    store = DeltaStore(cfg)
    rng = np.random.RandomState(seed)
    for uid in range(users):
        layers = rng.choice(cfg.n_layers, size=min(layers_per_user,
                                                   cfg.n_layers),
                            replace=False)
        idx = np.sort(layers).astype(np.int32)
        tuned = dict(params)
        tuned["blocks"] = {
            name: np.asarray(leaf, np.float32)
            + 0.01 * np.isin(np.arange(leaf.shape[0]), idx).reshape(
                (-1,) + (1,) * (leaf.ndim - 1))
            * rng.standard_normal(leaf.shape).astype(np.float32)
            for name, leaf in params["blocks"].items()}
        store.put_from_params(uid, params, tuned, layers=idx)
    return store


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--mode", default="shared",
                    choices=["shared", "delta", "dense"])
    ap.add_argument("--users", type=int, default=4)
    ap.add_argument("--delta-layers", type=int, default=2)
    ap.add_argument("--delta-capacity", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced(get_arch(args.arch))
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=32))
    params = model.init(jax.random.PRNGKey(0))
    store = (demo_store(model, params, args.users, args.delta_layers)
             if args.mode != "shared" else None)
    rng = np.random.RandomState(0)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size,
                                   args.prompt_len).tolist(), args.max_new,
                    user_id=(i % args.users if store else -1))
            for i in range(args.requests)]
    server = SlotServer(model, params, args.slots,
                        args.prompt_len + args.max_new + 1,
                        window=args.window, mode=args.mode, store=store,
                        capacity=args.delta_capacity)
    done, stats = server.run(reqs, verbose=True)
    print(f"served {len(done)} requests in {stats['steps']} steps "
          f"[mode={args.mode}] ({stats['tok_per_s']:.1f} tok/s, "
          f"{stats['gen_tokens']} tokens in {stats['wall_s']:.2f}s)")
    for r in done[:3]:
        print(f"  req {r.rid} (user {r.user_id}): gen={r.generated}")


if __name__ == "__main__":
    main()
