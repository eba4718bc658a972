"""The plain references against the program at a tiny size, with seeded
weights, in float32: the classifier's loss, the decoder's logits over a
sequence, and the decoder's logits decoded token by token through the
program's per-slot cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense as R
from bench.tests.tiny import tiny_cell
from bench.weights import leaf_names, make_params


def _world(workload):
    from repro.configs.base import RuntimeConfig
    from repro.models.model import Model
    config = dict(tiny_cell(workload).config, torch_dtype="float32")
    cfg = harness.arch_config(config)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=8))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = make_params(shapes, 5)
    # give the norm scales something to do
    params = jax.tree.map(lambda x: x + 0.1 * (x == 0), params)
    flat = dict(zip(leaf_names(shapes), jax.tree.leaves(params)))
    return config, model, params, flat


def test_classifier_loss_matches_the_program():
    config, model, params, flat = _world("xlmr-fl-select")
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (3, 16)), jnp.int32),
             "label": jnp.asarray(rng.integers(0, 10, 3), jnp.int32)}
    with jax.default_matmul_precision("highest"):
        want = float(model.loss(params, batch))
        got = float(R.class_loss(flat, batch, R.Arch.from_config(config)))
    assert got == pytest.approx(want, rel=1e-5)


def test_decoder_logits_match_the_program():
    config, model, params, flat = _world("smollm-serve-delta")
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 16)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        h, _, _ = model.forward_seq(params, {"tokens": toks})
        want = np.asarray(model._head(params, h))
        got = np.asarray(R.lm_logits(flat, toks, R.Arch.from_config(config)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_decoder_logits_match_decoding_through_the_cache():
    config, model, params, flat = _world("smollm-serve-delta")
    seq = np.random.default_rng(2).integers(0, 256, 12).astype(np.int32)
    cache = model.init_cache(1, 16, per_slot=True)
    steps = []
    with jax.default_matmul_precision("highest"):
        for p, t in enumerate(seq):
            logits, cache = model.decode_step(
                params, jnp.asarray([t]), jnp.asarray([p], jnp.int32), cache)
            steps.append(np.asarray(logits[0]))
        ref = np.asarray(R.lm_logits(flat, jnp.asarray(seq[None]),
                                     R.Arch.from_config(config))[0])
    np.testing.assert_allclose(np.stack(steps), ref, rtol=1e-4, atol=1e-5)


def test_lower_precision_control_moves_the_logits():
    config, _, _, flat = _world("smollm-serve-delta")
    a = R.Arch.from_config(config)
    toks = jnp.asarray(np.arange(10)[None] % 256, jnp.int32)
    with jax.default_matmul_precision("highest"):
        hi = np.asarray(R.lm_logits(flat, toks, a))
        lo = np.asarray(R.lm_logits(flat, toks, a, jnp.float8_e4m3fn))
    assert np.abs(hi - lo).max() > 1e-3


def test_p1_selection_respects_budgets_and_agrees_with_consensus():
    from bench.reference.fl_round import eq7_weights, pick_budget, solve_p1
    G = np.array([[5.0, 1.0, 0.5], [0.4, 6.0, 0.3], [3.0, 2.0, 1.0]])
    budgets = np.array([1.0, 2.0, 1.0])
    init = np.stack([pick_budget(G[i], budgets[i]) for i in range(3)])
    m = solve_p1(G, budgets, lam=10.0, init=init)
    assert (m.sum(1) <= budgets).all() and (m.sum(1) >= 1).all()
    # λ large: every client agrees on the same top layer
    assert (m[:, np.argmax(m.sum(0))] == 1).all()
    W = eq7_weights(m, np.array([1.0, 3.0, 6.0]))
    np.testing.assert_allclose(W.sum(0)[m.sum(0) > 0], 1.0)
