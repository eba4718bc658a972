"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure plus micro-benchmarks and the roofline
report.  Prints ``name,us_per_call,derived`` CSV lines for the micro
section, then the formatted tables.

Env knobs: BENCH_ROUNDS (default 25), BENCH_FAST=1 (8 rounds, micro only
reps=1).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

FAST = os.environ.get("BENCH_FAST") == "1"
ROUNDS = 8 if FAST else None


def micro_benchmarks():
    """name,us_per_call,derived CSV: kernels + FL primitives."""
    from benchmarks.common import timer
    from repro.kernels import ops
    from repro.core.solver import solve_icm

    print("name,us_per_call,derived")

    # flash attention kernel (interpret) vs jnp reference
    B, H, K, S, D = 1, 4, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    us = timer(lambda: jax.block_until_ready(
        ops.flash_attention(q, k, v, interpret=True)), reps=1 if FAST else 3)
    flops = 4 * B * H * S * S * D
    print(f"flash_attention_interp_{S}x{D},{us:.1f},{flops/us*1e-3:.2f}GFLOPs")

    # ssd kernel
    x = jax.random.normal(ks[0], (4, 256, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (4, 256)))
    A = -jnp.exp(jax.random.uniform(ks[2], (4,)))
    Bm = jax.random.normal(ks[0], (4, 256, 32))
    Cm = jax.random.normal(ks[1], (4, 256, 32))
    Dp = jnp.ones((4,))
    from repro.kernels.ssd_scan import ssd_scan
    us = timer(lambda: jax.block_until_ready(
        ssd_scan(x, dt, A, Bm, Cm, Dp, chunk=64, interpret=True)),
        reps=1 if FAST else 3)
    print(f"ssd_scan_interp_bh4_s256,{us:.1f},-")

    # layer grad norms (fused) vs per-leaf jnp
    g = {"w": jax.random.normal(ks[0], (16, 64, 256)),
         "b": jax.random.normal(ks[1], (16, 256))}
    us = timer(lambda: jax.block_until_ready(
        ops.layer_grad_norms(g, interpret=True)), reps=1 if FAST else 3)
    print(f"layer_grad_norms_L16,{us:.1f},-")

    # (P1) solver
    G = np.abs(np.random.RandomState(0).randn(20, 24))
    t0 = time.perf_counter()
    for _ in range(10):
        solve_icm(G, 2, lam=1.0)
    us = (time.perf_counter() - t0) / 10 * 1e6
    print(f"p1_solver_icm_n20_L24,{us:.1f},-")

    # one FL round (simulator, reduced model)
    from benchmarks.common import SCENARIOS, build_world, run_fl
    t0 = time.perf_counter()
    run_fl(SCENARIOS["cifar"], "ours", budget=1, rounds=1)
    us = (time.perf_counter() - t0) * 1e6
    print(f"fl_round_sim_cifar,{us:.1f},includes_jit")
    t0 = time.perf_counter()
    run_fl(SCENARIOS["cifar"], "ours", budget=1, rounds=2)
    us2 = (time.perf_counter() - t0) / 2 * 1e6
    print(f"fl_round_sim_cifar_warm,{us2:.1f},-")

    # round engine: sequential per-client loop vs the fused vmap round step
    round_engine_benchmarks()
    # mask-aware engine: frozen-prefix backward skipping vs the dense path
    masked_backward_benchmarks()
    # full round including host-side sampling: pre-PR scalar path vs the
    # vectorized sampler + streaming pipeline
    full_round_benchmarks()
    # requirements-trimmed selection probe vs the all-stats probe
    probe_trim_benchmarks()
    # depth-k lookahead scheduler vs the classic depth-1 double buffer
    pipeline_depth_benchmarks()
    # population-state store: per-round host cost flat in population size
    population_state_benchmarks()
    # personalized-delta serving: fused overlay decode vs per-user params
    delta_serving_benchmarks()


def round_engine_benchmarks() -> list[dict]:
    """Warm µs per cohort *engine step* at cohort_size ∈ {4, 8}.

    Times exactly what the engine switch changes — the probe + τ-step local
    updates + Eq.(5)-(7) aggregation + apply — on pre-drawn batches, in the
    FL-realistic small-microbatch regime (synthetic data generation and test
    evaluation are identical across engines and excluded).  The vectorized
    row's derived column reports the speedup over the sequential oracle at
    the same cohort size.  Returns the rows for BENCH_*.json recording.
    """
    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core import aggregation as agg
    from repro.core.client import Client
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.models.model import Model

    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=4, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticFederatedData(FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=8,
        samples_per_client=16, skew="label", objective="classification"))
    fl = FLConfig(n_clients=20, local_steps=2, lr=0.01, batch_size=4,
                  strategy="ours", budget=1)
    reps = 1 if FAST else 5
    rows: list[dict] = []
    for cohort_n in (4, 8):
        client = Client(model)       # shared jit suite (module-level cache);
                                     # per-shape compiles handled by warmup
        cohort = np.arange(cohort_n)
        masks = np.zeros((cohort_n, model.n_selectable), np.float32)
        masks[:, 1] = 1.0
        sizes = data.sizes[cohort]
        batches = data.cohort_batches(cohort, fl.batch_size, fl.local_steps)
        probe_b = data.cohort_batches(cohort, fl.batch_size,
                                      fl.selection_batches)

        def vec_step():
            client.probe_cohort(params, probe_b)
            _, losses = client.cohort_update(params, batches, masks, sizes,
                                             fl.lr)
            return losses

        def seq_step():
            for i in range(cohort_n):
                client.probe(params, jax.tree.map(lambda x: x[i, 0], probe_b))
            outs = [client.local_update(params,
                                        jax.tree.map(lambda x, i=i: x[i],
                                                     batches),
                                        masks[i], fl.lr)
                    for i in range(cohort_n)]
            update = agg.aggregate([o[0] for o in outs], masks, sizes, cfg)
            return agg.apply_update(params, update, fl.lr)

        seq_us = None
        for engine, step in (("sequential", seq_step),
                             ("vectorized", vec_step)):
            step()                               # warmup: jit compile
            t0 = time.perf_counter()
            for _ in range(reps):
                out = step()
            jax.block_until_ready(out)
            us = (time.perf_counter() - t0) / reps * 1e6
            derived = "-"
            if engine == "sequential":
                seq_us = us
            else:
                derived = f"{seq_us / us:.2f}x_vs_seq"
            print(f"round_engine_{engine}_c{cohort_n},{us:.1f},{derived}")
            rows.append({"name": f"round_engine_{engine}_c{cohort_n}",
                         "engine": engine, "cohort": cohort_n,
                         "us_per_call": us, "derived": derived})
    return rows


def masked_backward_benchmarks(cohort_n: int = 8) -> dict:
    """Warm µs per cohort update: mask-aware engine vs the dense program,
    sweeping the frozen-prefix depth (cut ∈ {0, L/2, L−1}).

    Both rows run ``Client.cohort_update_raw`` on identical pre-drawn
    batches and identical masks (every layer ≥ cut selected); the only
    change is ``cut`` — dense (None) differentiates all L layers and zeroes
    frozen gradients afterwards, mask-aware skips the frozen prefix's
    backward, activations and scan-carry entirely (plus the always-frozen
    embed/head/norm backward, which is why even cut=0 wins).  ``micro_ci``
    gates mask-aware ≤ dense at every cut and ≥1.5× at cut = L−1 via the
    median of *paired* per-rep ratios.  Returns a dict for
    BENCH_masked_backward.json.
    """
    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core.client import Client
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.models.model import Model

    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=8, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    L = model.n_selectable
    data = SyntheticFederatedData(FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=8,
        samples_per_client=16, skew="label", objective="classification"))
    fl = FLConfig(n_clients=20, local_steps=2, lr=0.01, batch_size=4)
    client = Client(model)
    cohort = np.arange(cohort_n)
    sizes = data.sizes[cohort]
    batches = data.cohort_batches(cohort, fl.batch_size, fl.local_steps)
    reps = 2 if FAST else 10
    cuts = (0, L // 2, L - 1)
    out: dict = {"cohort": cohort_n, "L": L, "reps": reps, "cuts": list(cuts)}

    def masks_for(cut):
        m = np.zeros((cohort_n, L), np.float32)
        m[:, cut:] = 1.0
        return m

    for cut in cuts:                         # warmup: compile both variants
        m = masks_for(cut)
        jax.block_until_ready(client.cohort_update_raw(
            params, batches, m, sizes, fl.lr)[0])
        jax.block_until_ready(client.cohort_update_raw(
            params, batches, m, sizes, fl.lr, cut=cut)[0])

    for cut in cuts:
        m = masks_for(cut)
        dense_t, masked_t = [], []
        for _ in range(reps):                # interleave: paired reps
            for which, times in (("dense", dense_t), ("masked", masked_t)):
                t0 = time.perf_counter()
                p, _ = client.cohort_update_raw(
                    params, batches, m, sizes, fl.lr,
                    cut=None if which == "dense" else cut)
                jax.block_until_ready(p)
                times.append(time.perf_counter() - t0)
        dense_t, masked_t = np.asarray(dense_t), np.asarray(masked_t)
        ratio = float(np.median(masked_t / dense_t))   # paired per-rep
        out[f"cut{cut}_dense_us"] = float(np.min(dense_t) * 1e6)
        out[f"cut{cut}_masked_us"] = float(np.min(masked_t) * 1e6)
        out[f"cut{cut}_ratio"] = ratio
        print(f"masked_backward_cut{cut}_c{cohort_n},"
              f"{out[f'cut{cut}_masked_us']:.1f},"
              f"{1.0 / ratio:.2f}x_vs_dense")
    return out


def probe_trim_benchmarks(cohort_n: int = 8) -> dict:
    """Warm µs per cohort probe: requirements-trimmed vs all-stats.

    Strategies declare ``probe_requirements`` (repro.api.strategy), so the
    probe computes only the stats the strategy consumes — ``ours`` pays for
    gradient square norms only, while the pre-API probe always paid for the
    full SNR+RGN stat set.  Times ``Client.probe_cohort`` on pre-drawn
    batches for each requirement set; ``micro_ci`` gates trimmed <= all.
    Returns a dict suitable for BENCH_probe_trim.json.
    """
    from repro.api import get_strategy
    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core.client import Client
    from repro.core.strategies import PROBE_KEYS
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.models.model import Model

    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=4, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticFederatedData(FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=8,
        samples_per_client=16, skew="label", objective="classification"))
    fl = FLConfig(n_clients=20, batch_size=4, selection_batches=2)
    client = Client(model)
    probe_b = data.cohort_batches(np.arange(cohort_n), fl.batch_size,
                                  fl.selection_batches)
    reps = 3 if FAST else 25
    variants = [
        ("all_stats", PROBE_KEYS, None),
        ("ours_trimmed", ("grad_sq_norms",), None),
        ("snr_trimmed", ("grad_means", "grad_vars"),
         get_strategy("snr").device_score_fn()),
    ]
    for _, reqs, score_fn in variants:       # warmup: jit compile
        jax.block_until_ready(
            client.probe_cohort_raw(params, probe_b, reqs, score_fn))
    # interleave variants across reps (decorrelates host noise) and take
    # min-of-N: the probe is grad-dominated on these tiny CPU models, so
    # the trim delta is small relative to scheduler jitter
    times: dict = {name: [] for name, _, _ in variants}
    for _ in range(reps):
        for name, reqs, score_fn in variants:
            t0 = time.perf_counter()
            jax.block_until_ready(
                client.probe_cohort_raw(params, probe_b, reqs, score_fn))
            times[name].append(time.perf_counter() - t0)
    out: dict = {"cohort": cohort_n, "reps": reps}
    base = np.asarray(times["all_stats"])
    for name, _, _ in variants:
        t = np.asarray(times[name])
        us = float(np.min(t) * 1e6)
        derived = "-"
        if name != "all_stats":
            # paired per-rep ratio vs the all-stats call of the same
            # interleave round — load spikes hit both sides and cancel
            ratio = float(np.median(t / base))
            out[f"{name}_ratio"] = ratio
            derived = f"{1.0 / ratio:.2f}x_vs_all"
        print(f"probe_{name}_c{cohort_n},{us:.1f},{derived}")
        out[f"{name}_us"] = us
    return out


def pipeline_depth_benchmarks(depth: int = 4, cohort_n: int = 8,
                              rounds: int = 4) -> dict:
    """Warm µs per full round: depth-k lookahead scheduler vs depth-1.

    Both rows run the streaming pipeline (RoundScheduler) on the
    sampling-bound config of :func:`full_round_benchmarks`; the only change
    is ``pipeline_depth`` — how many rounds ahead the host plans/samples
    while the (P1) solve runs on its background thread.  Results are
    bit-identical across depths (tests/test_scheduler.py); the delta is
    pure host scheduling.  ``micro_ci`` gates depth-k ≥ depth-1 throughput
    via the median of *paired* per-rep ratios (each rep times both depths
    back to back, so load spikes hit both sides and cancel).  Returns a
    dict suitable for BENCH_pipeline_depth.json.
    """
    from dataclasses import replace

    if depth < 2:
        raise ValueError(f"depth must be >= 2 to compare against depth-1, "
                         f"got {depth}")

    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core.server import FLServer
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.models.model import Model

    cfg = replace(reduced(get_arch("xlm_roberta_base"), n_layers=2,
                          d_model=16), vocab_size=4096)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=4))
    params = model.init(jax.random.PRNGKey(0))
    task = FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=4,
        samples_per_client=16, skew="label", objective="classification",
        test_samples=4096)
    fl = FLConfig(n_clients=20, cohort_size=cohort_n, local_steps=2,
                  lr=0.01, batch_size=16, strategy="ours", budget=1)
    rounds = 1 if FAST else rounds
    reps = 2 if FAST else 5

    def fresh(d):
        # fresh data + server per timed run: the per-client streams and
        # solver warm caches start identical for both depths
        return FLServer(model, fl, SyntheticFederatedData(task),
                        pipeline=True, pipeline_depth=d)

    for d in (1, depth):                     # warmup: compile both shapes
        fresh(d).run(params, rounds=2)
    times: dict = {1: [], depth: []}
    for _ in range(reps):
        for d in (1, depth):                 # interleave: paired reps
            server = fresh(d)
            t0 = time.perf_counter()
            server.run(params, rounds=rounds)    # run() syncs on finalize
            times[d].append((time.perf_counter() - t0) / rounds)
    t1, tk = np.asarray(times[1]), np.asarray(times[depth])
    ratio = float(np.median(tk / t1))
    out = {"cohort": cohort_n, "rounds_timed": rounds, "reps": reps,
           "depth": depth, "paired_ratio": ratio,
           "depth1_us_per_round": float(np.min(t1) * 1e6),
           f"depth{depth}_us_per_round": float(np.min(tk) * 1e6)}
    print(f"pipeline_depth1_c{cohort_n},{out['depth1_us_per_round']:.1f},-")
    print(f"pipeline_depth{depth}_c{cohort_n},"
          f"{out[f'depth{depth}_us_per_round']:.1f},"
          f"{1.0 / ratio:.2f}x_vs_depth1")
    return out


def fault_overhead_benchmarks(cohort_n: int = 8, rounds: int = 4) -> dict:
    """Warm µs per round: wired-but-disabled FaultInjector vs no injector.

    The chaos seam's standing cost when nothing is injected must be noise:
    a disabled injector short-circuits before any rng draw and the guarded
    program is never dispatched, so the only admissible delta is the
    ``_faults_active`` property check per stage.  ``micro_ci`` gates the
    median of *paired* per-rep ratios at ≤ 1.05x (each rep times both
    sides back to back on a fresh server, so host load spikes cancel).
    Returns a dict suitable for BENCH_fault_overhead.json.
    """
    from dataclasses import replace

    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core.server import FLServer
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.faults import FaultPlan
    from repro.models.model import Model

    cfg = replace(reduced(get_arch("xlm_roberta_base"), n_layers=2,
                          d_model=16), vocab_size=4096)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=4))
    params = model.init(jax.random.PRNGKey(0))
    task = FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=4,
        samples_per_client=16, skew="label", objective="classification",
        test_samples=4096)
    fl = FLConfig(n_clients=20, cohort_size=cohort_n, local_steps=2,
                  lr=0.01, batch_size=16, strategy="ours", budget=1)
    # the 1.05x gate is tight, so keep enough samples even in FAST mode —
    # the config is tiny and the median of paired ratios converges fast
    rounds = 2 if FAST else rounds
    reps = 3 if FAST else 5
    # every fault class armed, master switch off: the contractually-free
    # configuration (bit-identical results, tests/test_faults.py)
    disabled = FaultPlan(seed=7, enabled=False, death_rate=0.5,
                         corrupt_rate=0.5, stall_rate=0.5,
                         dispatch_fail_rate=0.5, ckpt_corrupt_rate=0.5)

    def fresh(faults):
        return FLServer(model, fl, SyntheticFederatedData(task),
                        faults=faults)

    for f in (None, disabled):               # warmup: compile both sides
        fresh(f).run(params, rounds=2)
    times: dict = {"none": [], "disabled": []}
    for _ in range(reps):
        for key, f in (("none", None), ("disabled", disabled)):
            server = fresh(f)
            t0 = time.perf_counter()
            server.run(params, rounds=rounds)    # run() syncs on finalize
            times[key].append((time.perf_counter() - t0) / rounds)
    t_none = np.asarray(times["none"])
    t_off = np.asarray(times["disabled"])
    out = {"cohort": cohort_n, "rounds_timed": rounds, "reps": reps,
           "paired_ratio": float(np.median(t_off / t_none)),
           "none_us_per_round": float(np.min(t_none) * 1e6),
           "disabled_us_per_round": float(np.min(t_off) * 1e6)}
    print(f"fault_none_c{cohort_n},{out['none_us_per_round']:.1f},-")
    print(f"fault_disabled_c{cohort_n},{out['disabled_us_per_round']:.1f},"
          f"{out['paired_ratio']:.3f}x_vs_none")
    return out


def full_round_benchmarks(cohort_n: int = 8, rounds: int = 4) -> dict:
    """End-to-end warm µs per *full round* — sampling included.

    Compares the pre-PR host path (legacy per-sample token loops + per-round
    test-set resampling, no prefetch) against the streaming pipeline
    (vectorized sampler, construction-time test set, double-buffered
    prefetch + fused probe/update).  The device math is identical in both
    rows; the delta is pure host-side sampling + scheduling.  The config is
    sampling-bound (short sequences, wide vocab, large held-out set — the
    regime where ROADMAP observed the per-sample loops dominating): XLA:CPU
    per-program overhead otherwise hides the host path entirely.  Returns a
    dict suitable for BENCH_full_round.json.
    """
    from dataclasses import replace

    from repro.configs.base import (FLConfig, RuntimeConfig, get_arch,
                                    reduced)
    from repro.core.server import FLServer
    from repro.data.synthetic import (FederatedTaskConfig,
                                      SyntheticFederatedData)
    from repro.models.model import Model

    cfg = replace(reduced(get_arch("xlm_roberta_base"), n_layers=2,
                          d_model=16), vocab_size=4096)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=4))
    params = model.init(jax.random.PRNGKey(0))
    task = FederatedTaskConfig(
        n_clients=20, n_classes=10, vocab_size=cfg.vocab_size, seq_len=4,
        samples_per_client=16, skew="label", objective="classification",
        test_samples=4096)
    fl = FLConfig(n_clients=20, cohort_size=cohort_n, local_steps=2,
                  lr=0.01, batch_size=16, strategy="ours", budget=1)
    rounds = 1 if FAST else rounds
    out = {"cohort": cohort_n, "rounds_timed": rounds}
    for mode in ("legacy", "vectorized"):
        data = SyntheticFederatedData(task)
        data.legacy_sampling = mode == "legacy"
        server = FLServer(model, fl, data, pipeline=mode != "legacy")
        # warmup: 2 rounds so the fused probe+update program (used when a
        # next round exists) compiles outside the timed region
        server.run(params, rounds=2)
        t0 = time.perf_counter()
        server.run(params, rounds=rounds)        # run() syncs on finalize
        us = (time.perf_counter() - t0) / rounds * 1e6
        out[f"{mode}_us_per_round"] = us
        print(f"full_round_{mode}_c{cohort_n},{us:.1f},"
              + ("-" if mode == "legacy" else
                 f"{out['legacy_us_per_round'] / us:.2f}x_vs_legacy"))
    out["speedup"] = out["legacy_us_per_round"] / out["vectorized_us_per_round"]
    return out


def population_state_benchmarks(cohort_n: int = 8,
                                populations: tuple = (10_000, 100_000),
                                n_layers: int = 24) -> dict:
    """Host µs per round of ClientStateStore/ClientStreamState traffic.

    Times one round's worth of population-state ops — warm-mask gather +
    scatter, stats validity check + scatter + gather, per-client stream
    draw + advance, and a periodic O(1) generation clear — against stores
    sized at 10⁴ and 10⁵ clients with the same cohort.  Every op is an
    O(cohort) fancy-index into flat arrays, so the per-round cost must be
    independent of the population size: ``micro_ci`` gates the median of
    *paired* per-rep ratios (each rep times both populations back to back,
    so load spikes hit both sides and cancel) flat at ≤ 2.0.  Returns a
    dict suitable for BENCH_population_state.json.
    """
    from repro.core.state import ClientStateStore, ClientStreamState

    reps = 3 if FAST else 7
    rounds = 20 if FAST else 100
    rng = np.random.RandomState(0)
    stat_keys = ("grad_sq_norms", "param_sq_norms", "scores")

    def one_round(store, streams, cohort, t):
        # plan: which cohort members need a fresh probe?
        probe_ids = store.missing_stats(cohort)
        if len(probe_ids):
            store.set_stat_rows(probe_ids, {
                k: np.ones((len(probe_ids), n_layers), np.float32)
                for k in stat_keys})
        stats = store.stat_rows(cohort)
        # warm-start gather, (P1)-solve stand-in, scatter back
        rows, valid = store.warm_rows(cohort)
        rows[~valid] = 1.0
        store.set_warm_rows(cohort, rows, t=t)
        # per-client data streams
        for i in cohort:
            streams.rng(int(i)).randint(0, 1 << 16, 4)
            streams.advance(int(i), 4)
        if t % 10 == 9:                      # selection refresh: O(1) bump
            store.clear_stats()
        return stats

    def fresh(n):
        store = ClientStateStore(n, n_layers)
        streams = ClientStreamState(n, lambda i: 7 * i + 1)
        cohorts = rng.randint(0, n, size=(rounds, cohort_n))
        return store, streams, cohorts

    for n in populations:                    # warmup: allocator + caches
        store, streams, cohorts = fresh(n)
        for t in range(5):
            one_round(store, streams, cohorts[t], t)
    times: dict = {n: [] for n in populations}
    for _ in range(reps):
        for n in populations:                # interleave: paired reps
            store, streams, cohorts = fresh(n)
            t0 = time.perf_counter()
            for t in range(rounds):
                one_round(store, streams, cohorts[t], t)
            times[n].append((time.perf_counter() - t0) / rounds)
    lo, hi = populations[0], populations[-1]
    t_lo, t_hi = np.asarray(times[lo]), np.asarray(times[hi])
    ratio = float(np.median(t_hi / t_lo))
    out = {"cohort": cohort_n, "rounds_timed": rounds, "reps": reps,
           "populations": list(populations), "paired_ratio": ratio}
    for n in populations:
        us = float(np.min(np.asarray(times[n])) * 1e6)
        out[f"pop{n}_us_per_round"] = us
        print(f"population_state_n{n}_c{cohort_n},{us:.1f},"
              + ("-" if n == lo else f"{ratio:.2f}x_vs_n{lo}"))
    return out


def delta_serving_benchmarks(slot_counts: tuple = (4, 6),
                             densities: tuple = (1, 2, 4)) -> dict:
    """Steady-state decode tok/s: batched delta overlay vs the dense
    per-user-params baseline, sweeping delta density and slot count.

    Each config serves B slots whose users tuned ``k`` selected layers
    (k ∈ {1, L/4, L/2} at L=8) of an 8-layer dense model.  The delta row
    decodes the whole batch against ONE shared parameter set plus a
    capacity-C per-layer delta entry table (kernels/delta_matmul.py
    linearity split: per-step weight traffic (1+C)·d·f); the dense row is
    the honest baseline — a vmapped decode over B private full-parameter
    copies (B·d·f traffic).  Capacity is the exact per-layer load of a
    round-robin layer assignment, so C+1 < B at every density and the
    traffic model predicts the win.  ``micro_ci`` gates delta ≤ dense at
    every (slots, density) via the median of *paired* per-rep ratios.
    Returns a dict suitable for BENCH_delta_serving.json.
    """
    from repro.configs.base import RuntimeConfig, get_arch, reduced
    from repro.models.model import Model, _block_shapes
    from repro.serve import (DeltaOverlay, DeltaRecord, serve_suite,
                             stack_tree)

    cfg = reduced(get_arch("tinyllama_1_1b"), n_layers=8, d_model=128)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    suite = serve_suite(model)
    shapes = _block_shapes(cfg, "dense")
    L, W = cfg.n_layers, 64
    steps = 8 if FAST else 20
    reps = 2 if FAST else 5
    rng = np.random.RandomState(0)
    out: dict = {"L": L, "d_model": cfg.d_model, "steps": steps,
                 "reps": reps, "configs": []}

    def record_for(layers):
        idx = np.sort(np.asarray(layers, np.int32))
        leaves = {
            name: (0.01 * rng.standard_normal((len(idx),) + tuple(shp)))
            .astype(np.float32) for name, shp in shapes.items()}
        return DeltaRecord(layers=idx, segments={"blocks": (idx, leaves)})

    for B in slot_counts:
        toks = jnp.arange(B, dtype=jnp.int32)
        pos = jnp.zeros(B, jnp.int32)
        bank = stack_tree(params, B)
        dense_cache0 = stack_tree(
            model.init_cache(1, W, per_slot=True), B)
        for k in densities:
            # round-robin layer assignment: per-layer load == capacity
            C = -(-B * k // L)                       # ceil(B·k/L)
            overlay = DeltaOverlay(model, C)
            for u in range(B):
                rec = record_for([(u * k + j) % L for j in range(k)])
                assert overlay.try_admit(u, rec)
            cache = model.init_cache(B, W, per_slot=True)
            dcache = dense_cache0

            def delta_step(c):
                return suite["serve_decode_delta"](params, toks, pos, c,
                                                   overlay.device(), 0)

            def dense_step(c):
                return suite["serve_decode_dense"](bank, toks, pos, c, 0)

            # warmup: compile both programs for this (B, C)
            _, cache = delta_step(cache)
            _, dcache = dense_step(dcache)
            delta_t, dense_t = [], []
            for _ in range(reps):                    # interleave: paired reps
                for which, times in (("delta", delta_t), ("dense", dense_t)):
                    step = delta_step if which == "delta" else dense_step
                    c = cache if which == "delta" else dcache
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        lg, c = step(c)
                    jax.block_until_ready(lg)
                    times.append((time.perf_counter() - t0) / steps)
                    if which == "delta":
                        cache = c
                    else:
                        dcache = c
            delta_t, dense_t = np.asarray(delta_t), np.asarray(dense_t)
            ratio = float(np.median(delta_t / dense_t))   # paired per-rep
            row = {"slots": B, "density": k, "capacity": C,
                   "paired_ratio": ratio,
                   "delta_tok_s": float(B / np.min(delta_t)),
                   "dense_tok_s": float(B / np.min(dense_t))}
            out["configs"].append(row)
            print(f"delta_serving_b{B}_k{k}_cap{C},"
                  f"{np.min(delta_t) * 1e6:.1f},"
                  f"{1.0 / ratio:.2f}x_vs_dense")
    return out


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    micro_benchmarks()
    print()
    from benchmarks import (ablation_lambda, fig2, roofline, seeds, table1,
                            table2, table3)
    table1.main(rounds=ROUNDS)
    print()
    seeds.main(rounds=ROUNDS, seeds=(0,) if FAST else (0, 1, 2))
    print()
    table2.main(rounds=ROUNDS)
    print()
    table3.main()
    print()
    ablation_lambda.main(rounds=ROUNDS)
    print()
    fig2.main(rounds=ROUNDS)
    print()
    roofline.main(None)


if __name__ == '__main__':
    main()
