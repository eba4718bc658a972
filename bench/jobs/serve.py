"""Job ``serve``: personalised decoding through the program's own
``SlotServer.run`` (``repro.launch.serve``), unmodified.

A closed loop of callers, one per slot: ``SlotServer.run`` takes its whole
queue up front, so the queue holds every caller's requests in the order
they become due, and the k-th request after the first round of callers is
due when the k-th request completes (it takes the freed slot at once).
The callers' first requests are short and staggered, so the loop is in its
steady state when the last of them completes: that completion opens the
window, and everything before it is set-up.  The window closes inside the
loop, at the first decode call after its end, with no drain.

The harness sees the loop through the objects it hands the program: the
``Request``s (each generated token is timestamped where the loop appends
it) and the server's decode call (each step: its time, the active slots,
their users and positions).
"""
from __future__ import annotations

import gc
import statistics

import numpy as np

from bench import gen
from bench.harness import (Check, JobResult, WindowClosed, arch_config,
                           device_barrier, family_module, now, peak_bytes,
                           split_layers)
from bench.reference.decode import ServeReference, sample_requests
from bench.weights import make_deltas, make_params


class _Generated(list):
    """A request's generated tokens, timestamped as the loop appends them."""

    def __init__(self, loop: "_Loop", rid: int):
        super().__init__()
        self.loop, self.rid, self.times = loop, rid, []

    def append(self, tok):
        t = now()
        super().append(tok)
        self.times.append(t)
        self.loop.on_token(self.rid, t)


class _Loop:
    """Clock of the closed loop: token and step times, completions."""

    def __init__(self, server, requests, n_priming: int, seconds: float,
                 clock, tracer):
        self.server, self.requests = server, requests
        self.n_priming, self.seconds = n_priming, seconds
        self.clock, self.tracer = clock, tracer
        self.completions: list[float] = []       # times, in order
        self.primed = 0
        self.w0 = self.w1 = None
        self.compiled = 0
        self.steps: list[tuple] = []    # (time, [(user, position), ...])
        self._decode = server._decode
        server._decode = self.decode

    def on_token(self, rid: int, t: float) -> None:
        r = self.requests[rid]
        if not r.done:
            return
        self.completions.append(t)
        if rid < self.n_priming:
            self.primed += 1
            if self.primed == self.n_priming:
                self.w0, self.w1 = t, t + self.seconds
                self.compiled = self.clock.programs
                self.tracer.start()

    def decode(self, toks, pos):
        t = now()
        if self.w1 is not None and t >= self.w1:
            raise WindowClosed
        act = [(r.user_id, int(p)) for r, p in zip(self.server.active,
                                                    self.server.pos)
               if r is not None]
        self.steps.append((t, act))
        return self._decode(toks, pos)


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) of all samples, by
    ``statistics.quantiles`` with the inclusive method."""
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100, method="inclusive")[
        int(round(q)) - 1])


def window_numbers(loop: _Loop, requests, n_slots: int) -> dict:
    """End-to-end numbers of the window [w0, w1] from the loop's clock."""
    w0, w1 = loop.w0, loop.w1
    tokens = sum(w0 <= t <= w1 for r in requests for t in r.generated.times)
    ttft, due_ids = [], []
    for j, r in enumerate(requests):
        k = j - n_slots                 # due at the (k+1)-th completion
        if k < 0 or k >= len(loop.completions):
            continue
        due = loop.completions[k]
        if not w0 <= due <= w1:
            continue
        first = r.generated.times[0] if r.generated.times else None
        ttft.append((first if first is not None and first <= w1 else w1) - due)
        due_ids.append(j)
    return {"tokens": tokens, "ttft_s": ttft, "due": due_ids,
            "admitted": sum(1 for j in range(n_slots, len(requests))
                            if j - n_slots < len(loop.completions)
                            and w0 <= loop.completions[j - n_slots] <= w1)}


def build_store(cell, cfg, shapes, seed: int):
    """The users' selected-layer deltas: drawn on the device, fetched once,
    held by the program's ``DeltaStore``.  Each stacked segment of the
    family draws rows of its own leaves for a user's k layers, and a layer
    takes its rows from the segment that holds it."""
    from repro.serve import DeltaRecord, DeltaStore
    mix = cell.mix
    users, k = int(mix["users"]), int(mix["delta_layers"])
    segments = family_module(cell.config, "reference").segments(cell.config)
    rows = {seg: make_deltas({n: s.shape[1:] for n, s in shapes[seg].items()},
                             users, k, seed)
            for seg, _ in segments}
    layers = gen.delta_layers(mix, sum(n for _, n in segments), seed)
    store = DeltaStore(cfg)
    for u in range(users):
        store.put(u, DeltaRecord(layers=layers[u], segments={
            seg: (idx, {n: v[u][pos] for n, v in rows[seg].items()})
            for seg, (pos, idx) in split_layers(segments, layers[u]).items()}))
    return store, layers, rows


def run(ctx) -> JobResult:
    import jax
    from repro.configs.base import RuntimeConfig
    from repro.launch.serve import Request, SlotServer
    from repro.models.model import Model

    cell, seed, mix = ctx.cell, ctx.seed, ctx.cell.mix
    cfg = arch_config(cell.config)
    slots = int(mix["callers"])
    mode = mix["mode"]
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=32))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_params(shapes, seed)
    store = layers = rows = None
    if mode == "delta":
        store, layers, rows = build_store(cell, cfg, shapes, seed)
    max_seq = int(mix["prompt"]["max"]) + int(mix["output"]["max"]) + 1
    server = SlotServer(model, params, slots, max_seq, mode=mode, store=store,
                        capacity=int(mix.get("overlay_capacity", 0)))
    device_barrier()        # compiled here, where the window ends on it

    specs = gen.serve_requests(mix, cfg.vocab_size, seed)
    prime_rng = gen.rng_for(seed, 8)
    prime = [gen.RequestSpec(c, prime_rng.integers(0, cfg.vocab_size, n - 1,
                                                   dtype=np.int32), 1,
                             specs[c].user)
             for c, n in enumerate(gen.priming_lengths(mix))]
    specs = prime + specs
    with ctx.tracing(start=False) as tracer:
        loop = _Loop(server, None, slots, ctx.window_seconds, ctx.clock,
                     tracer)
        requests = [Request(j, s.prompt.tolist(), s.max_new, user_id=s.user,
                            generated=_Generated(loop, j))
                    for j, s in enumerate(specs)]
        loop.requests = requests
        try:
            server.run(requests)
            raise RuntimeError("serve: the queue ran dry before the window "
                               "closed; the mix needs more requests_per_caller")
        except WindowClosed:
            pass
        device_barrier()
    compiles = ctx.clock.programs - loop.compiled
    setup_s = loop.w0 - ctx.t_start
    peak = peak_bytes()
    nums = window_numbers(loop, requests, slots)
    window_s = loop.w1 - loop.w0
    steps = [s for s in loop.steps if loop.w0 <= s[0] < loop.w1]

    # -- free the program's state, then the reference -------------------------
    served = [(r.prompt, list(r.generated), r.user_id)
              for r in requests[slots:] if r.done]
    failed = len(server.dropped)
    del server, loop, requests, params
    gc.collect()
    t_ref = now()
    sample = sample_requests(served, int(mix["check_tokens"]), seed)
    ref = ServeReference(cell.config, shapes, seed, max_seq, layers, rows)
    numbers = {"logit_gap": ref.widest_gap(sample)}
    if ctx.control is not None:
        ctx.control(ref, sample)
    limits = mix["limits"]
    metrics = {"serve_tokens_per_s": nums["tokens"] / window_s,
               "setup_s": setup_s}
    return JobResult(
        setup_s=setup_s, metrics=metrics, attempted=nums["admitted"],
        failed=failed, peak_bytes=peak, compiles_in_window=compiles,
        checks=[Check(k, v, float(limits[k])) for k, v in numbers.items()],
        context={"job": "serve", "mix": mix, "config": cell.config,
                 "steps": steps, "layers": layers, "window_s": window_s,
                 "trace": tracer.result, "ttft_samples": len(nums["ttft_s"]),
                 "ttft_p50_ms": 1e3 * percentile(nums["ttft_s"], 50),
                 "window_lengths": {
                     "prompt": sorted(len(specs[j].prompt) for j in nums["due"]),
                     "output": sorted(specs[j].max_new for j in nums["due"])},
                 "reference_s": now() - t_ref,
                 "step_s": [float(v) for v in np.quantile(
                     np.diff([t for t, _ in steps]), [0, .25, .5, .75, 1])]
                 if len(steps) > 1 else [],
                 "checked_tokens": sum(len(g) for _, g, _ in sample)})
