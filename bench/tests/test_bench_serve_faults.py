"""The serve job driven end to end at a tiny size, with the harness's look
for a chip skipped: a sound run is correct; a served token altered where
the decode produces it, or a decode that leaves the cache unchanged, makes
``correct`` false; the fp8 control's gap fails the limit."""
import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("workload", [
    "smollm-serve-delta",
    pytest.param(("smollm-360m", "serve-chat-base"), id="smollm-serve-base")])
def test_sound_run_is_correct(workload):
    r = run_tiny(workload, seed=31)
    assert r.correct, [(c.name, c.value, c.limit) for c in r.checks]
    assert r.compiles_in_window == 0
    assert r.context["checked_tokens"] > 0
    assert r.metrics["serve_tokens_per_s"] > 0
    assert r.context["ttft_p50_ms"] > 0


def _patch(monkeypatch, fault):
    from repro.launch.serve import SlotServer
    orig = SlotServer._decode

    def broken(self, toks, pos):
        logits, cache = orig(self, toks, pos)
        return fault(self, logits, cache)
    monkeypatch.setattr(SlotServer, "_decode", broken)


def test_an_altered_token_is_caught(monkeypatch):
    def alter(srv, logits, cache):
        top = jnp.argmax(logits, -1)
        rows = jnp.arange(logits.shape[0])
        return logits.at[rows, (top + 1) % logits.shape[1]].add(1e4), cache
    _patch(monkeypatch, alter)
    assert not run_tiny("smollm-serve-delta", seed=32).correct


def test_a_decode_that_leaves_its_cache_unchanged_is_caught(monkeypatch):
    _patch(monkeypatch, lambda srv, logits, cache: (logits, srv.cache))
    assert not run_tiny("smollm-serve-delta", seed=33).correct


@pytest.mark.parametrize("seed", [1, 2])
def test_the_fp8_control_fails_the_limit(seed):
    """The reference in fp8 in the program's place: at every position of
    768 tokens, the gap (under the f32 reference) of the token fp8 puts
    first exceeds the cell's limit."""
    import jax
    import numpy as np

    from bench import harness
    from bench.reference.decode import ServeReference
    from repro.models.model import Model
    cell = tiny_cell("smollm-serve-delta")
    cfg = harness.arch_config(cell.config)
    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    ref = ServeReference(cell.config, shapes, seed, 160)
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, cfg.vocab_size, 32).tolist(),
               rng.integers(0, cfg.vocab_size, 96).tolist(), -1)
              for _ in range(8)]
    limit = cell.mix["limits"]["logit_gap"]
    assert ref.control_gap(sample, jnp.float8_e4m3fn) > limit
