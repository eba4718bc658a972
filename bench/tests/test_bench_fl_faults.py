"""The FL job driven end to end at a tiny size, with the harness's look for
a chip skipped: a sound run is correct, and with the timed path broken
underneath (a round that returns its state unchanged; half of each batch
left out) ``correct`` comes out false.  The fp8 control fails too."""
import jax
import pytest

from bench.tests.tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("cell", [
    "xlmr-fl-select",
    pytest.param(("xlmr-base", "fl-period5"), id="xlmr-fl-period5")])
def test_sound_run_is_correct(cell):
    r = run_tiny(cell, seed=21)
    assert r.correct, [(c.name, c.value, c.limit) for c in r.checks]
    assert r.compiles_in_window == 0
    assert r.metrics["fl_tokens_per_s"] > 0 and r.attempted > 0


def _patch(monkeypatch, wrap):
    from repro.core.client import Client
    for name in ("probe_update_cohort_raw", "cohort_update_raw",
                 "probe_cohort_raw"):
        monkeypatch.setattr(Client, name, wrap(name, getattr(Client, name)))


def test_a_round_that_leaves_the_state_unchanged_is_caught(monkeypatch):
    def wrap(name, orig):
        if name == "probe_cohort_raw":
            return orig

        def stuck(self, params, *a, **k):
            out = orig(self, params, *a, **k)
            return (params,) + tuple(out[1:])
        return stuck

    _patch(monkeypatch, wrap)
    assert not run_tiny("xlmr-fl-select", seed=22).correct


@pytest.mark.parametrize("fault", ["update", "probe"])
def test_a_fault_in_one_cuts_program_is_caught(monkeypatch, fault):
    """The window can meet any prefix cut, each a program of its own: a
    round wrong only at the top cut (its update, or the probe fused into
    it) is caught while every other cut's rounds are sound."""
    top = tiny_cell("xlmr-fl-select").config["num_hidden_layers"] - 1

    def wrap(name, orig):
        if name == "probe_cohort_raw":
            return orig

        def at_top(self, params, *a, cut=None, **k):
            out = orig(self, params, *a, cut=cut, **k)
            if cut != top:
                return out
            if fault == "update":
                return (params,) + tuple(out[1:])
            if len(out) < 3:
                return out
            stats = {k: v * 1.5 for k, v in out[2].items()}
            return out[0], out[1], stats
        return at_top

    _patch(monkeypatch, wrap)
    assert not run_tiny("xlmr-fl-select", seed=25).correct


def test_half_the_batch_left_out_is_caught(monkeypatch):
    def half(tree):
        return jax.tree.map(lambda x: x[:, :, :max(x.shape[2] // 2, 1)], tree)

    def wrap(name, orig):
        def dropped(self, params, batches, *a, **k):
            a = list(a)
            if name == "probe_update_cohort_raw":
                a[3] = half(a[3])                   # the probe's batches
            return orig(self, params, half(batches), *a, **k)
        return dropped

    _patch(monkeypatch, wrap)
    assert not run_tiny("xlmr-fl-select", seed=23).correct


def test_the_fp8_control_fails_a_limit():
    from bench.control import fl_control
    cell = tiny_cell("xlmr-fl-select")
    nums = fl_control(cell, 24)
    limits = cell.mix["limits"]
    assert any(nums[k] > v for k, v in limits.items()), (nums, limits)
