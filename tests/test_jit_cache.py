"""Shared jit suite cache: repeated FLServer/Client construction for the
same (ArchConfig, RuntimeConfig) must reuse compiled programs — zero
recompilation across benchmark sweeps and multi-server runs."""
import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig, RuntimeConfig, get_arch, reduced
from repro.core import client as client_mod
from repro.core.client import Client
from repro.core.server import FLServer
from repro.data.synthetic import FederatedTaskConfig, SyntheticFederatedData
from repro.models.model import Model


def _world():
    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=2, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    task = FederatedTaskConfig(n_clients=8, n_classes=10,
                               vocab_size=cfg.vocab_size, seq_len=8,
                               samples_per_client=16, skew="label",
                               objective="classification")
    fl = FLConfig(n_clients=8, cohort_size=3, rounds=2, local_steps=2,
                  lr=0.01, batch_size=4, strategy="ours", budget=1, lam=1.0,
                  seed=0)
    return model, model.init(jax.random.PRNGKey(0)), task, fl


def test_repeated_server_construction_zero_recompilation():
    model, params, task, fl = _world()
    client_mod.clear_jit_cache()

    s1 = FLServer(model, fl, SyntheticFederatedData(task))
    _, h1 = s1.run(params)
    stats = client_mod.jit_cache_stats()
    assert stats["misses"] == 1 and stats["entries"] == 1
    hot = {name: getattr(s1.client, f"_{name}")
           for name in ("cohort_update", "probe_cohort",
                        "probe_update_cohort", "eval")}
    sizes = {name: fn._cache_size() for name, fn in hot.items()}

    # same model object, and a *fresh* Model with an equal config: both hit
    s2 = FLServer(model, fl, SyntheticFederatedData(task))
    model2 = Model(model.cfg, model.runtime)
    s3 = FLServer(model2, fl, SyntheticFederatedData(task))
    stats = client_mod.jit_cache_stats()
    assert stats["hits"] >= 2 and stats["misses"] == 1

    for name, fn in hot.items():
        assert getattr(s2.client, f"_{name}") is fn
        assert getattr(s3.client, f"_{name}") is fn

    _, h2 = s2.run(params)
    _, h3 = s3.run(params)
    for name, fn in hot.items():
        assert fn._cache_size() == sizes[name], \
            f"{name} recompiled on repeated server construction"
    # identical configuration => identical runs through the shared programs
    assert h1.summary() == h2.summary() == h3.summary()


def test_masked_engine_compile_stability():
    """Mask-aware programs are keyed on the static prefix cut: with a fixed
    budget pattern across rounds, the engine compiles at most one variant
    per *distinct* cut seen, and repeated rounds / repeated servers with
    the same configuration add zero recompiles (jit_cache_stats()).
    """
    from repro.core.masks import first_trainable_layer

    model, params, task, fl = _world()
    # fixed heterogeneous budget pattern; 'top' selects the highest R_i
    # layers, so the round cut is L − max(cohort budgets) — at most two
    # distinct cuts ever occur with this pattern
    from dataclasses import replace
    fl = replace(fl, strategy="top", budgets=(1, 2), budget=1, rounds=6)
    client_mod.clear_jit_cache()

    server = FLServer(model, fl, SyntheticFederatedData(task))
    assert server.mask_aware
    _, hist = server.run(params)
    cuts = {first_trainable_layer(r.mask_matrix) for r in hist.records}
    stats = client_mod.jit_cache_stats()
    masked = {k: v for k, v in stats["programs"].items() if "masked" in k}
    assert sum(masked.values()) >= 1, "mask-aware engine never dispatched"
    for name, count in masked.items():
        assert count <= len(cuts), \
            f"{name}: {count} program variants for {len(cuts)} distinct cuts"

    # zero per-round recompiles: more rounds and a fresh server over the
    # same (ArchConfig, RuntimeConfig) reuse every compiled variant
    server.run(params)
    _, hist2 = FLServer(model, fl, SyntheticFederatedData(task)).run(params)
    cuts2 = cuts | {first_trainable_layer(r.mask_matrix)
                    for r in hist2.records}
    assert cuts2 == cuts
    after = client_mod.jit_cache_stats()["programs"]
    for name, count in masked.items():
        assert after[name] == count, f"{name} recompiled on repeated rounds"


def test_custom_shard_models_bypass_cache():
    model, _, _, _ = _world()
    client_mod.clear_jit_cache()
    Client(model)
    sharded = Model(model.cfg, model.runtime, shard=lambda x, kind=None: x)
    Client(sharded)
    stats = client_mod.jit_cache_stats()
    assert stats["misses"] == 1 and stats["uncached"] == 1


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """Entry points keep compiled programs in JAX_COMPILATION_CACHE_DIR when
    it is set (JAX reads it; nothing is set in code), else in the fixed
    .jax_cache/ at the repo root."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = use_compile_cache()
        if env_dir is None:
            assert got == str(REPO_CACHE_DIR)
            assert REPO_CACHE_DIR.parent.joinpath("ROADMAP.md").is_file()
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
