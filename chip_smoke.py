#!/usr/bin/env python3
"""Chip smoke test: the federated round and delta serving on a TPU.

    python3 chip_smoke.py             # one chip: phases `fl` and `serve`
    python3 chip_smoke.py --chips 4   # four chips: phase `sharded` only

Each phase drives the system through the entry points a user calls, at the
published widths of its model, with random weights made from ``--seed``:

* ``fl`` — ``repro.api.Experiment`` on XLM-R base (12 layers, d 768, 12
  heads, d_ff 3072, vocab 250002, bf16) with the paper's strategy: three
  rounds of the vectorized engine, whose update and probe programs run the
  Pallas kernels, then one round from the same seed through the vectorized
  and the sequential engine.  The sequential engine is the jnp reference:
  its masks must be identical and its params agree within ``FL_PARAM_RTOL``.
* ``serve`` — ``SlotServer(mode="delta")`` on TinyLlama-1.1B (22 layers,
  d 2048, 32/4 GQA heads, d_ff 5632, vocab 32000, bf16): 8 requests over
  4 slots for 4 users carrying 2 delta layers each.  The first decode
  step's logits must match ``mode="dense"`` (private params materialised)
  within ``SERVE_LOGIT_RTOL``.
* ``sharded`` — ``make_fl_train_step`` on a 4x1 mesh with TinyLlama-1.1B,
  4 clients of batch 2 at sequence 256, against the single-device
  Eq.(5)-(7) reference accumulated client by client on one chip.

Each phase prints one JSON line: device, compile and steady-step seconds,
device memory, the Pallas kernels (``tpu_custom_call``) in its compiled
programs and its checks.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when a check fails, the script exits non-zero and prints
no result line.  It runs in one process: the chip belongs to it alone.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FL_PARAM_RTOL = 2.0 ** -6        # vectorized (kernels) vs sequential (jnp)
SERVE_LOGIT_RTOL = 2.0 ** -5     # delta overlay vs materialised dense params
SHARDED_PARAM_RTOL = 2.0 ** -6   # sharded step vs single-device reference

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), and how many programs it compiled."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event == _COMPILE_EVENTS[-1]

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.programs


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def kernel_calls(compiled) -> int:
    """Pallas TPU kernels in a compiled program."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def max_rel_diff(a, b) -> float:
    """max over leaves of max|a − b| / max|b|."""
    def leaf(x, y):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        return float(jnp.max(jnp.abs(x - y)) / jnp.maximum(
            jnp.max(jnp.abs(y)), 1e-30))
    return max(jax.tree.leaves(jax.tree.map(leaf, a, b)))


def report(phase: str, clock: CompileClock, start: tuple[float, int],
           **fields) -> None:
    secs, progs = clock.snapshot()
    print(json.dumps({"phase": phase, **device_info(),
                      "compile_s": secs - start[0],
                      "programs_compiled": progs - start[1],
                      "peak_bytes_in_use": peak_bytes(), **fields}),
          flush=True)


# ---------------------------------------------------------------------------
# phase fl: Experiment rounds, vectorized (kernels) against sequential (jnp)
# ---------------------------------------------------------------------------

def phase_fl(cfg, clock: CompileClock, *, seq_len=128, n_clients=16,
             cohort=4, local_steps=2, batch=8, budget=2, rounds=3, seed=0,
             steady_reps=3) -> None:
    from repro.api import Experiment
    from repro.core.masks import first_trainable_layer
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)

    start = clock.snapshot()

    def experiment(engine: str, n_rounds: int) -> Experiment:
        task = SyntheticFederatedData(FederatedTaskConfig(
            n_clients=n_clients, n_classes=cfg.n_classes,
            vocab_size=cfg.vocab_size, seq_len=seq_len,
            objective="classification", seed=seed))
        return Experiment(cfg, task, "ours", engine=engine,
                          cohort_size=cohort, local_steps=local_steps,
                          batch_size=batch, budget=budget, rounds=n_rounds,
                          seed=seed)

    t0 = time.perf_counter()
    params, hist = experiment("vectorized", rounds).run()
    jax.block_until_ready(params)
    run_s = time.perf_counter() - t0
    losses = [(r.train_loss, r.test_loss) for r in hist.records]
    check(len(hist.records) == rounds, f"fl: {len(hist.records)} rounds")
    check(all(math.isfinite(v) for pair in losses for v in pair),
          f"fl: non-finite round loss {losses}")
    for r in hist.records:
        m = np.asarray(r.mask_matrix)
        check(bool(np.isin(m, (0.0, 1.0)).all())
              and bool((m.sum(1) <= budget).all()),
              f"fl: round {r.round} masks break the budget {budget}: {m}")

    exp_v = experiment("vectorized", 1)
    params_v, hist_v = exp_v.run()
    params_s, hist_s = experiment("sequential", 1).run()
    masks_v = np.asarray(hist_v.records[0].mask_matrix)
    masks_s = np.asarray(hist_s.records[0].mask_matrix)
    check(np.array_equal(masks_v, masks_s),
          f"fl: vectorized masks {masks_v} != sequential {masks_s}")
    rel = max_rel_diff(params_v, params_s)
    moved = max_rel_diff(exp_v.init_params(), params_s)
    check(rel <= FL_PARAM_RTOL,
          f"fl: vectorized vs sequential params rel diff {rel}")
    check(moved > 2 * rel, f"fl: the round barely moves the params "
                           f"({moved} vs an engine gap of {rel})")

    # steady step: the vectorized round's update program, on one sampled
    # round with the masks the first round selected (compiled above)
    server = exp_v.server
    sampled = server.sample_round(server.plan_round(1))
    for _ in range(2):        # the second call is warm on any backend
        new, _ = server.update_round(params_v, sampled, masks_v)
        jax.block_until_ready(new)
    times = []
    for _ in range(steady_reps):
        t = time.perf_counter()
        new, _ = server.update_round(params_v, sampled, masks_v)
        jax.block_until_ready(new)
        times.append(time.perf_counter() - t)

    # the Pallas kernels in the compiled update and probe programs
    client, plan = server.client, sampled.plan
    cut = first_trainable_layer(masks_v)
    f32 = lambda x: jax.device_put(np.asarray(x, np.float32))  # noqa: E731
    update_prog = client._cohort_update_masked.lower(
        params_v, sampled.update_batches, f32(masks_v), f32(plan.sizes),
        f32(server.fl.lr), cut).compile()
    probe_prog = client._probe_cohort.lower(
        params_v, sampled.probe_batches, server._probe_reqs,
        server._score_fn).compile()
    kernels = {"update": kernel_calls(update_prog),
               "probe": kernel_calls(probe_prog)}
    check(min(kernels.values()) > 0, f"fl: no Pallas kernel in {kernels}")

    report("fl", clock, start, model=cfg.name, rounds=rounds,
           run_s=run_s, steady_step_s=float(np.median(times)),
           steady_step_what=f"round update program, cohort {cohort}, "
                            f"tau {local_steps}, cut {cut}",
           round_losses=losses, tpu_custom_calls=kernels,
           masks_equal_sequential=True, params_rel_diff=rel,
           params_rtol=FL_PARAM_RTOL, round_rel_move=moved)


# ---------------------------------------------------------------------------
# phase serve: SlotServer delta mode against dense private params
# ---------------------------------------------------------------------------

def demo_deltas(cfg, users: int, layers_per_user: int, seed: int):
    """A DeltaStore whose users own disjoint round-robin layer sets of
    random (0.01-scale) deltas over every leaf of their layers."""
    from repro.models.model import _block_shapes
    from repro.serve import DeltaRecord, DeltaStore
    rng = np.random.RandomState(seed)
    shapes = _block_shapes(cfg, "dense")
    store = DeltaStore(cfg)
    for u in range(users):
        idx = np.sort(np.asarray(
            [(u * layers_per_user + j) % cfg.n_layers
             for j in range(layers_per_user)], np.int32))
        leaves = {name: (0.01 * rng.standard_normal((len(idx),) + tuple(shp)))
                  .astype(np.float32) for name, shp in shapes.items()}
        store.put(u, DeltaRecord(layers=idx,
                                 segments={"blocks": (idx, leaves)}))
    return store


def phase_serve(cfg, clock: CompileClock, *, slots=4, n_requests=8,
                prompt_len=16, max_new=16, users=4, layers_per_user=2,
                capacity=2, dense_slots=2, seed=0, steady_reps=8) -> None:
    from repro.configs.base import RuntimeConfig
    from repro.launch.serve import Request, SlotServer
    from repro.models.model import Model

    start = clock.snapshot()
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=32))
    params = model.init(jax.random.PRNGKey(seed))
    store = demo_deltas(cfg, users, layers_per_user, seed)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    max_seq = prompt_len + max_new + 1

    def requests():
        return [Request(i, list(p), max_new, user_id=i % users)
                for i, p in enumerate(prompts)]

    def server(mode: str, n_slots: int = slots) -> SlotServer:
        return SlotServer(model, params, n_slots, max_seq, mode=mode,
                          store=store, capacity=capacity)

    def first_step(srv: SlotServer, queue=None):
        """The first decode step, through the server's own admit + decode."""
        srv._admit(requests() if queue is None else queue)
        toks = jnp.asarray([r.prompt[0] for r in srv.active], jnp.int32)
        return toks, jnp.asarray(srv.pos)

    # delta mode: first-step logits, then the steady decode step
    srv = server("delta")
    toks, pos = first_step(srv)
    logits_delta, _ = srv._decode(toks, pos)
    logits_delta = np.asarray(logits_delta, np.float32)
    times = []
    for _ in range(steady_reps):
        t = time.perf_counter()
        jax.block_until_ready(srv._decode(toks, pos))
        times.append(time.perf_counter() - t)
    decode_prog = srv.suite["serve_decode_delta"].lower(
        params, toks, pos, srv.cache, srv.overlay.device(),
        srv.window).compile()
    kernels = {"decode_delta": kernel_calls(decode_prog)}
    check(kernels["decode_delta"] > 0, f"serve: no Pallas kernel {kernels}")
    del srv, decode_prog

    # the whole request loop
    srv = server("delta")
    done, stats = srv.run(requests())
    check(len(done) == n_requests and all(r.done for r in done),
          f"serve: {len(done)}/{n_requests} requests completed")
    check(stats["dropped_requests"] == 0,
          f"serve: {stats['dropped_requests']} requests dropped")
    del srv

    # dense mode: every slot decodes its user's materialised private params.
    # Its bank holds a full param copy per slot and its decode program a
    # transposed copy of the bank, so at TinyLlama width four dense slots
    # do not fit 16 GB: the first-step slots go through in groups (slots
    # decode independently, so each row is the same either way)
    dense = []
    for g in range(0, slots, dense_slots):
        srv = server("dense", dense_slots)
        logits, _ = srv._decode(*first_step(srv, requests()[g:g + dense_slots]))
        dense.append(np.asarray(logits, np.float32))
        del srv, logits
    logits_dense = np.concatenate(dense)
    # shared mode: the base params alone, to show the deltas move the logits
    srv = server("shared")
    logits_base, _ = srv._decode(*first_step(srv))
    del srv

    def rel_to_dense(x):
        return float(np.max(np.abs(np.asarray(x, np.float32) - logits_dense))
                     / np.max(np.abs(logits_dense)))
    rel, effect = rel_to_dense(logits_delta), rel_to_dense(logits_base)
    check(bool(np.isfinite(logits_delta).all()), "serve: non-finite logits")
    check(rel <= SERVE_LOGIT_RTOL, f"serve: delta vs dense logits {rel}")
    check(effect > 2 * rel, f"serve: the deltas barely move the logits "
                            f"({effect} vs a delta-dense gap of {rel})")

    report("serve", clock, start, model=cfg.name, slots=slots,
           requests=n_requests, capacity=capacity,
           steady_step_s=float(np.median(times)),
           steady_step_what=f"delta decode step, {slots} slots",
           run=stats, tpu_custom_calls=kernels,
           first_logits_rel_diff_vs_dense=rel, logits_rtol=SERVE_LOGIT_RTOL,
           base_logits_rel_diff_vs_dense=effect)


# ---------------------------------------------------------------------------
# phase sharded: the mesh round step against the Eq.(5)-(7) reference
# ---------------------------------------------------------------------------

def phase_sharded(cfg, clock: CompileClock, *, clients=4, per_client=2,
                  seq=256, budget=2, lr=0.1, seed=0) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import RuntimeConfig
    from repro.core import aggregation as agg
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import Model, apply_layer_mask
    from repro.sharding.fl_step import make_fl_train_step

    start = clock.snapshot()
    mesh = make_host_mesh(clients, 1)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=seq))
    L = model.n_selectable
    params = model.init(jax.random.PRNGKey(seed))     # reference copy
    rng = np.random.RandomState(seed)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (clients, per_client, seq)), jnp.int32)
    masks_np = np.zeros((clients, L), np.float32)
    for i in range(clients):
        masks_np[i, rng.choice(L, budget, replace=False)] = 1.0
    masks = jnp.asarray(masks_np)
    sizes = jnp.asarray([10.0 * (i + 1) for i in range(clients)])

    step_fn, specs = make_fl_train_step(model, mesh, zero3=True)(
        jax.eval_shape(lambda: params))
    pshard = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))
    args = (pshard, {"tokens": tokens}, masks, sizes, jnp.float32(lr))
    new_params, metrics = step_fn(*args)
    jax.block_until_ready(new_params)
    t = time.perf_counter()
    jax.block_until_ready(step_fn(*args))
    step_s = time.perf_counter() - t
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()]
    collectives = sum(step_fn.lower(*args).compile().as_text().count(op)
                      for op in ("all-gather", "reduce-scatter",
                                 "all-reduce"))
    check(collectives > 0, "sharded: no collective in the step program")

    # single-device reference, Eq.(5)-(7) accumulated one client at a time
    W = agg.aggregation_weights(masks, sizes)
    grad = jax.jit(jax.grad(model.loss))

    @jax.jit
    def accumulate(total, g, m, w):
        scaled = agg.scale_by_layer(apply_layer_mask(g, m, cfg), w, cfg)
        return jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                            total, scaled)

    update = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    for i in range(clients):
        g = grad(params, {"tokens": tokens[i]})
        update = accumulate(update, g, masks[i], W[i])
        del g
    ref = agg.apply_update(params, update, lr)
    del update
    dev0 = jax.devices()[0]
    rel = max_rel_diff(jax.device_put(new_params, dev0), ref)
    moved = max_rel_diff(params, ref)
    check(rel <= SHARDED_PARAM_RTOL, f"sharded: params rel diff {rel}")
    check(moved > 2 * rel, f"sharded: the step barely moves the params "
                           f"({moved} vs a sharded-reference gap of {rel})")
    check(math.isfinite(float(metrics["loss"])), "sharded: non-finite loss")

    report("sharded", clock, start, model=cfg.name,
           mesh=dict(mesh.shape), clients=clients, per_client_batch=per_client,
           seq=seq, loss=float(metrics["loss"]), steady_step_s=step_s,
           bytes_in_use_per_device=in_use, collectives=collectives,
           params_rel_diff=rel, params_rtol=SHARDED_PARAM_RTOL,
           step_rel_move=moved)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases fl and serve; 4: the sharded round")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found platform "
                         f"{info['platform']!r} ({info['kind']})")
    if info["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{info['count']} {info['kind']} device(s)")

    from repro.configs.base import get_arch
    from repro.launch.compile_cache import use_compile_cache

    cache = Path(use_compile_cache())
    before = len(list(cache.glob("*"))) if cache.is_dir() else 0
    clock = CompileClock()
    if args.chips == 4:
        phase_sharded(get_arch("tinyllama-1.1b"), clock, seed=args.seed)
    else:
        phase_fl(get_arch("xlm-roberta-base"), clock, seed=args.seed)
        phase_serve(get_arch("tinyllama-1.1b"), clock, seed=args.seed)
    after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(json.dumps({"compile_cache": str(cache), "entries_before": before,
                      "entries_after": after}), flush=True)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


if __name__ == "__main__":
    main()
