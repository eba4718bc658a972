"""Job ``fl``: the paper's federated round, through the program's own entry
``repro.api.Experiment`` → ``FLServer.run`` (the streaming
``RoundScheduler``, vectorized engine, strategy ``ours``).

Set-up builds one server and drives it from the seed with its own ``run``
through the checked rounds, which the reference follows: three with the
selection as the algorithm makes it, then one at each prefix cut, forced
from a hook on the selection.  The masked update takes the cut as a static
argument, so each cut is a program of its own, and these rounds are how
set-up warms every program the window can meet.  The window runs
``FLServer.run`` on from there until the host's (P1) selection of a round
starts after the window's end; the rounds dispatched before it all
complete, and the window closes when the device has finished them.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import gen
from bench.harness import (Check, JobResult, WindowClosed, arch_config,
                           device_barrier, family_module, now, peak_bytes,
                           program_seed)
from bench.reference.fl_round import (FLReference, Readings, checked_rounds,
                                      checked_schedule, compare, draw_cohorts,
                                      forced_masks, per_round)
from bench.weights import leaf_names, make_params


def fl_params(cell) -> dict:
    """The round's parameters: the deployment from the configuration file
    (clients, cohort, batch), the rest from the traffic mix."""
    c = cell.config
    return {**cell.mix, "n_clients": c["n_clients"],
            "cohort_size": c["cohort_size"], "batch_size": c["batch_size"]}


def tokens_per_round(fl: dict) -> int:
    return (int(fl["cohort_size"]) * int(fl["local_steps"])
            * int(fl["batch_size"]) * int(fl["seq_len"]))


def _norms_fn(segments: list):
    """Per leaf of the stacked segments, ‖(old − new)·scale‖ in f32, as one
    program, keyed as the reference keys it (``blocks/attn_wq``)."""
    import jax
    import jax.numpy as jnp

    def norms(new, old, scale):
        return {f"{seg}/{name}": jnp.linalg.norm(
            (a.astype(jnp.float32) - b.astype(jnp.float32)) * scale)
            for seg, _ in segments
            for name, a, b in zip(leaf_names(old[seg]),
                                  jax.tree.leaves(old[seg]),
                                  jax.tree.leaves(new[seg]))}
    return jax.jit(norms)


def _floats(d: dict) -> dict:
    return {k: float(v) for k, v in d.items()}


def _cuts(rounds: list) -> dict:
    """How many of the window's rounds had each prefix cut."""
    cuts = [int(np.flatnonzero(r["masks"].sum(0) > 0)[0]) for r in rounds]
    return {c: cuts.count(c) for c in sorted(set(cuts))}


def _quartiles(x) -> list:
    return [float(v) for v in np.quantile(x, [0.0, 0.25, 0.5, 0.75, 1.0])] \
        if len(x) else []


def run(ctx) -> JobResult:
    import jax
    from repro.api import Experiment
    from repro.configs.base import FLConfig

    cell, seed = ctx.cell, ctx.seed
    fl = fl_params(cell)
    cfg = arch_config(cell.config)
    L = cfg.n_layers
    C, B, tau = int(fl["cohort_size"]), int(fl["batch_size"]), int(fl["local_steps"])
    period = int(fl["selection_period"])
    budgets = gen.fl_budgets(fl, seed)
    task = gen.ClassTask(fl, cfg.vocab_size, cfg.n_classes, seed)
    exp = Experiment(cfg, task, "ours", fl=FLConfig(
        n_clients=int(fl["n_clients"]), cohort_size=C, local_steps=tau,
        lr=float(fl["lr"]), batch_size=B, budgets=budgets,
        lam=float(fl["lam"]), selection_period=period, selection_batches=1,
        seed=program_seed(seed)), engine="vectorized")
    server, client = exp.server, exp.server.client
    shapes = jax.eval_shape(exp.model.init, jax.random.PRNGKey(0))
    p0 = make_params(shapes, seed)

    # -- the checked rounds, through the window's own call ----------------
    R, forced = checked_rounds(L), checked_schedule(L)
    lr = float(fl["lr"])
    norms = _norms_fn(family_module(cell.config, "reference")
                      .segments(cell.config))
    seen = Readings([], [], [], [], {})
    select = server.select_round

    def checked_select(plan, stats):
        masks = select(plan, stats)
        seen.masks.append(np.asarray(masks))
        seen.stats.append(np.zeros((0, L)) if stats is None
                          else np.asarray(stats["grad_sq_norms"], np.float64))
        if plan.t in forced:
            return forced_masks(plan.budgets, forced[plan.t], L)
        return masks

    def recorded(update):
        def call(params, *a, **k):
            out = update(params, *a, **k)
            seen.updates.append(norms(out[0], params, 1.0 / lr))
            return out
        return call

    server.select_round = checked_select
    client.probe_update_cohort_raw = recorded(client.probe_update_cohort_raw)
    client.cohort_update_raw = recorded(client.cohort_update_raw)
    p_r, hist = server.run(p0, rounds=R)
    del client.probe_update_cohort_raw, client.cohort_update_raw
    server.select_round = select
    seen.losses = [r.train_loss for r in hist.records]
    seen.updates = [_floats(u) for u in seen.updates]
    seen.change = _floats(norms(p_r, p0, 1.0))
    del p0, hist

    if period > 1:          # the chained probe of the members not cached
        pb = jax.device_put(gen.ClassTask(fl, cfg.vocab_size, cfg.n_classes,
                                          seed + 1).cohort_batches(range(C), B, 1))
        for k in range(1, C + 1):
            jax.block_until_ready(client.probe_cohort_raw(
                p_r, jax.tree.map(lambda x: x[:k], pb), server._probe_reqs,
                server._score_fn))
        del pb
    device_barrier()
    setup_s = now() - ctx.t_start

    # -- the window -----------------------------------------------------------
    rounds: list[dict] = []

    def timed_select(plan, stats):
        if now() >= deadline:
            raise WindowClosed
        masks = select(plan, stats)
        rounds.append({"masks": np.asarray(masks), "at": now(),
                       "probed": 0 if stats is None
                       else len(next(iter(stats.values())))})
        return masks

    server.select_round = timed_select
    compiled = ctx.clock.programs
    handed = [p_r]          # the loop holds the only reference from here
    del p_r
    with ctx.tracing() as tr:
        w0 = now()
        deadline = w0 + ctx.window_seconds
        try:
            server.run(handed.pop(), rounds=2 ** 62, start=R)
        except WindowClosed:
            pass
        device_barrier()
        w1 = now()
    compiles = ctx.clock.programs - compiled
    peak = peak_bytes()
    window_s = w1 - w0
    tokens = len(rounds) * tokens_per_round(fl)

    # -- free the program's state, then the reference -------------------------
    del server, exp, client, select
    gc.collect()
    t_ref = now()
    shapes_flat = dict(zip(leaf_names(shapes), jax.tree.leaves(shapes)))
    params = dict(zip(shapes_flat, jax.tree.leaves(make_params(shapes, seed))))
    ref = FLReference(cell.config, fl).run(
        params, gen.ClassTask(fl, cfg.vocab_size, cfg.n_classes, seed),
        draw_cohorts(program_seed(seed), int(fl["n_clients"]), C, R),
        budgets, R, forced)
    del params
    numbers = compare(seen, ref)
    limits = fl["limits"]
    return JobResult(
        setup_s=setup_s,
        metrics={"fl_tokens_per_s": tokens / window_s, "setup_s": setup_s},
        attempted=len(rounds) * C, failed=0,
        checks=[Check(k, v, float(limits[k])) for k, v in numbers.items()
                if k in limits],
        peak_bytes=peak, compiles_in_window=compiles,
        context={"job": "fl", "fl": fl, "config": cell.config,
                 "rounds": rounds, "window_s": window_s, "trace": tr.result,
                 "reference_s": now() - t_ref, "checked_rounds": R,
                 "cuts": _cuts(rounds), "round_s": _quartiles(
                     np.diff([w0] + [r["at"] for r in rounds])),
                 "uncompared": {k: v for k, v in numbers.items()
                                if k not in limits},
                 "round_gaps": per_round(seen, ref)})
