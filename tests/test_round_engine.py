"""Vectorized vs. sequential round-engine parity + seed determinism.

The vectorized engine (Client.cohort_update / Client.probe_cohort) must be
an exact drop-in for the paper-literal sequential loop: identical cohorts,
identical masks, params equal within fp tolerance — across strategies and
heterogeneous per-client budgets.
"""
import jax
import numpy as np
import pytest

from repro.api.task import DirichletTaskConfig, DirichletTokenMixtureTask
from repro.configs.base import FLConfig, RuntimeConfig, get_arch, reduced
from repro.core.server import FLServer
from repro.data.synthetic import FederatedTaskConfig, SyntheticFederatedData
from repro.models.model import Model


@pytest.fixture(scope="module")
def world():
    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=4, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    task = FederatedTaskConfig(
        n_clients=12, n_classes=10, vocab_size=cfg.vocab_size, seq_len=8,
        samples_per_client=16, skew="label", objective="classification")
    return model, params, task


def _run(model, params, task, fl, engine):
    # fresh data per run: both engines must consume identical RNG streams
    data = SyntheticFederatedData(task)
    server = FLServer(model, fl, data, engine=engine)
    return server.run(params)


def _assert_parity(model, params, task, fl, atol=1e-5):
    p_seq, h_seq = _run(model, params, task, fl, "sequential")
    p_vec, h_vec = _run(model, params, task, fl, "vectorized")
    for rs, rv in zip(h_seq.records, h_vec.records):
        np.testing.assert_array_equal(rs.cohort, rv.cohort)
        np.testing.assert_array_equal(rs.mask_matrix, rv.mask_matrix)
        assert rs.uploaded_params == rv.uploaded_params
        assert rs.train_loss == pytest.approx(rv.train_loss, abs=1e-4)
        assert rs.test_loss == pytest.approx(rv.test_loss, abs=1e-4)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)).max()),
        p_seq, p_vec)))
    assert err < atol, f"param divergence {err}"


@pytest.mark.parametrize("strategy", ["ours", "top", "rgn", "full"])
def test_engine_parity_across_strategies(world, strategy):
    model, params, task = world
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=2, local_steps=2,
                  lr=0.01, batch_size=4, strategy=strategy, budget=2,
                  lam=1.0, seed=3)
    _assert_parity(model, params, task, fl)


@pytest.mark.parametrize("strategy", ["ours", "top"])
def test_engine_parity_heterogeneous_budgets(world, strategy):
    model, params, task = world
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=2, local_steps=2,
                  lr=0.01, batch_size=4, strategy=strategy,
                  budgets=(1, 2, 3, 4), lam=1.0, seed=7)
    _assert_parity(model, params, task, fl)


def test_engine_parity_hybrid_shared_attn():
    """The hybrid family's unstacked shared block exercises the (n,)-weight
    einsum branch of aggregate_stacked."""
    cfg = reduced(get_arch("zamba2_7b"), n_layers=2, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(1))
    task = FederatedTaskConfig(n_clients=8, vocab_size=cfg.vocab_size,
                               seq_len=8, samples_per_client=16, skew="label",
                               objective="lm")
    fl = FLConfig(n_clients=8, cohort_size=3, rounds=1, local_steps=2,
                  lr=0.01, batch_size=2, strategy="ours", budget=2,
                  lam=1.0, seed=0)
    _assert_parity(model, params, task, fl)


@pytest.mark.parametrize("engine", ["sequential", "vectorized"])
def test_seed_determinism(world, engine):
    """Fixed FLConfig.seed => identical cohort sequence and summary twice."""
    model, params, task = world
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=3, local_steps=1,
                  lr=0.01, batch_size=4, strategy="ours", budget=2,
                  lam=1.0, seed=11)
    _, h1 = _run(model, params, task, fl, engine)
    _, h2 = _run(model, params, task, fl, engine)
    for r1, r2 in zip(h1.records, h2.records):
        np.testing.assert_array_equal(r1.cohort, r2.cohort)
        np.testing.assert_array_equal(r1.mask_matrix, r2.mask_matrix)
    assert h1.summary() == h2.summary()


@pytest.mark.parametrize("period", [2, 3])
def test_engine_parity_selection_period(world, period):
    """Both engines share the per-client stat cache + on-demand probes."""
    model, params, task = world
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=4, local_steps=1,
                  lr=0.01, batch_size=4, strategy="ours",
                  budgets=(1, 2, 3, 4), selection_period=period, lam=1.0,
                  seed=5)
    _assert_parity(model, params, task, fl)


@pytest.mark.parametrize("period", [1, 2])
def test_pipelined_run_matches_synchronous(world, period):
    """The streaming pipeline (prefetch + async/fused probe) is a pure
    scheduling change: cohorts and masks bit-identical, params within fp."""
    model, params, task = world
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=3, local_steps=2,
                  lr=0.01, batch_size=4, strategy="ours", budget=2,
                  selection_period=period, lam=1.0, seed=13)
    data_p = SyntheticFederatedData(task)
    data_s = SyntheticFederatedData(task)
    p_pipe, h_pipe = FLServer(model, fl, data_p, pipeline=True).run(params)
    p_sync, h_sync = FLServer(model, fl, data_s, pipeline=False).run(params)
    for rp, rs in zip(h_pipe.records, h_sync.records):
        np.testing.assert_array_equal(rp.cohort, rs.cohort)
        np.testing.assert_array_equal(rp.mask_matrix, rs.mask_matrix)
        assert rp.train_loss == pytest.approx(rs.train_loss, abs=1e-5)
        assert rp.test_loss == pytest.approx(rs.test_loss, abs=1e-5)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)).max()),
        p_pipe, p_sync)))
    assert err < 1e-5, f"pipelined param divergence {err}"


@pytest.mark.parametrize("depth,period", [(1, 1), (3, 1), (3, 2)])
def test_pipelined_hooks_match_synchronous(world, depth, period):
    """Availability + straggler hooks consume the server rng at the plan
    stage, which pins when the scheduler may fire plan_round(t+1): the
    depth-k pipeline must still draw bit-identical cohorts and masks versus
    the synchronous loop."""
    model, params, _ = world
    dcfg = DirichletTaskConfig(n_clients=12, vocab_size=model.cfg.vocab_size,
                               seq_len=8, test_samples=32, availability=0.5,
                               straggler_rate=0.3, seed=4)
    fl = FLConfig(n_clients=12, cohort_size=4, rounds=5, local_steps=1,
                  lr=0.01, batch_size=4, strategy="ours", budget=2,
                  selection_period=period, lam=1.0, seed=19)
    p_pipe, h_pipe = FLServer(model, fl, DirichletTokenMixtureTask(dcfg),
                              pipeline=True,
                              pipeline_depth=depth).run(params)
    p_sync, h_sync = FLServer(model, fl, DirichletTokenMixtureTask(dcfg),
                              pipeline=False).run(params)
    assert len(h_pipe.records) == len(h_sync.records) == 5
    shrunk = False
    for rp, rs in zip(h_pipe.records, h_sync.records):
        np.testing.assert_array_equal(rp.cohort, rs.cohort)
        np.testing.assert_array_equal(rp.mask_matrix, rs.mask_matrix)
        assert rp.train_loss == pytest.approx(rs.train_loss, abs=1e-5)
        assert rp.test_loss == pytest.approx(rs.test_loss, abs=1e-5)
        shrunk = shrunk or len(rp.cohort) < 4
    assert shrunk, "straggler hook never fired — test lost its teeth"
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)).max()),
        p_pipe, p_sync)))
    assert err < 1e-5, f"hooked pipelined param divergence {err}"


@pytest.mark.parametrize("engine", ["sequential", "vectorized"])
def test_bf16_params_keep_their_dtype(engine):
    """Published configs are bf16: a round must hand bf16 params back.  The
    traced f32 learning rate used to promote every updated leaf to f32,
    which broke the next program's bf16 scan carry (the fused probe after
    a mask-aware update) and doubled the params' memory."""
    from dataclasses import replace
    cfg = replace(reduced(get_arch("xlm_roberta_base"), n_layers=3,
                          d_model=32), dtype="bfloat16")
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    task = FederatedTaskConfig(
        n_clients=6, n_classes=10, vocab_size=cfg.vocab_size, seq_len=8,
        samples_per_client=8, objective="classification")
    fl = FLConfig(n_clients=6, cohort_size=3, rounds=2, local_steps=2,
                  lr=0.01, batch_size=4, budget=1, lam=1.0, seed=1)
    new, hist = _run(model, params, task, fl, engine)
    assert len(hist.records) == 2
    assert jax.tree.map(lambda p: p.dtype, new) == \
        jax.tree.map(lambda p: p.dtype, params)
