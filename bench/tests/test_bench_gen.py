"""The traffic generators and configuration files: the same seed gives the
same inputs, every seed the same multiset of sizes; the harness finds a
configuration, mix, job and metric by file name alone."""
import json

import numpy as np
import pytest

from bench import gen, harness
from bench.tests.tiny import tiny_cell

FL_MIX = {"n_clients": 40, "seq_len": 8, "signal": 0.7, "label_alpha": 0.5,
          "test_samples": 4, "client_sizes": {"median": 500, "sigma": 1.0,
                                              "min": 8},
          "budgets": {"min": 1, "max": 4}}
SERVE_MIX = {"callers": 4, "block": 4, "requests_per_caller": 9,
             "users": 8, "zipf_s": 0.8, "delta_layers": 2,
             "prompt": {"median": 32, "sigma": 0.8, "min": 2, "max": 100},
             "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 30}}
BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_fl_task_is_a_function_of_the_seed(seed):
    a, b = (gen.ClassTask(FL_MIX, 64, 4, seed) for _ in range(2))
    np.testing.assert_array_equal(a.sizes, b.sizes)
    for _ in range(2):
        x, y = a.cohort_batches([3, 5], 2, 3), b.cohort_batches([3, 5], 2, 3)
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    np.testing.assert_array_equal(a.test_batch()["tokens"],
                                  b.test_batch()["tokens"])


def test_fl_draws_never_repeat_and_sizes_are_one_multiset():
    a = gen.ClassTask(FL_MIX, 64, 4, 1)
    first, second = a.cohort_batches([3], 2, 1), a.cohort_batches([3], 2, 1)
    assert not np.array_equal(first["tokens"], second["tokens"])
    c = gen.ClassTask(FL_MIX, 64, 4, 2)
    assert sorted(a.sizes) == sorted(c.sizes)
    assert not np.array_equal(a.sizes, c.sizes)
    assert np.median(a.sizes) == pytest.approx(500, rel=0.1)


def test_budgets_are_one_multiset_in_the_seeds_order():
    a, b = gen.fl_budgets(FL_MIX, 1), gen.fl_budgets(FL_MIX, 2)
    assert sorted(a) == sorted(b) and a != b
    assert set(a) <= {1, 2, 3, 4}
    assert gen.fl_budgets(FL_MIX, 1) == a


@pytest.mark.parametrize("seed", [3, BIG])
def test_serve_requests_are_a_function_of_the_seed(seed):
    a, b = (gen.serve_requests(SERVE_MIX, 50, seed) for _ in range(2))
    assert len(a) == 4 * 9
    for x, y in zip(a, b):
        assert (x.caller, x.max_new, x.user) == (y.caller, y.max_new, y.user)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_serve_lengths_do_not_depend_on_the_seed():
    """Every seed queues the same lengths in the same order; each block of
    a caller's requests holds the block's quantiles."""
    def lengths(seed):
        return [(r.caller, len(r.prompt), r.max_new)
                for r in gen.serve_requests(SERVE_MIX, 50, seed)]
    assert lengths(1) == lengths(2) == lengths(BIG)
    by_caller = {}
    for c, p, _ in lengths(1):
        by_caller.setdefault(c, []).append(p)
    assert all(sorted(v[:4]) == sorted(v[4:8]) for v in by_caller.values())
    reqs = gen.serve_requests(SERVE_MIX, 50, 1)
    assert all(2 <= len(r.prompt) <= 100 and 2 <= r.max_new <= 30 for r in reqs)


def test_the_callers_first_blocks_cover_the_mix_evenly():
    """With a block as long as a caller's requests in a window, the
    callers' first blocks together hold callers × block quantiles, evenly
    spaced over the whole mix."""
    mix = dict(SERVE_MIX, block=3)
    reqs = gen.serve_requests(mix, 50, 1)
    first = sorted(len(r.prompt) for r in reqs[:4 * 3])
    p = mix["prompt"]
    want = gen.lognormal_quantiles(4 * 3, p["median"], p["sigma"], lo=p["min"],
                                   hi=p["max"])
    assert first == sorted(want.tolist())


def test_zipf_owners_and_delta_layers():
    p = gen.zipf_owner_probs(8, 0.8)
    assert p.sum() == pytest.approx(1.0) and all(np.diff(p) < 0)
    layers = gen.delta_layers(SERVE_MIX, 12, 5)
    assert len(layers) == 8
    assert all(len(set(l.tolist())) == 2 and l.max() < 12 for l in layers)
    assert all(np.array_equal(a, b) for a, b in
               zip(layers, gen.delta_layers(SERVE_MIX, 12, 5)))


def test_priming_is_staggered():
    lens = gen.priming_lengths({"callers": 4, "prime_stride": 5})
    assert lens == [2, 7, 12, 17]


def test_weights_are_a_function_of_the_seed():
    import jax
    import jax.numpy as jnp
    from bench.weights import make_params
    shapes = {"embed": {"tok": jax.ShapeDtypeStruct((5, 4), jnp.bfloat16)},
              "blocks": {"attn_ln": jax.ShapeDtypeStruct((2, 4), jnp.bfloat16),
                         "attn_wq": jax.ShapeDtypeStruct((2, 4, 4), jnp.bfloat16)},
              "final_norm": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
    a, b, c = make_params(shapes, 1), make_params(shapes, 1), make_params(shapes, 2)
    assert a["blocks"]["attn_wq"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a["blocks"]["attn_wq"], np.float32),
                                  np.asarray(b["blocks"]["attn_wq"], np.float32))
    assert not np.array_equal(np.asarray(a["embed"]["tok"], np.float32),
                              np.asarray(c["embed"]["tok"], np.float32))
    assert not np.asarray(a["blocks"]["attn_ln"], np.float32).any()
    assert not np.asarray(a["final_norm"], np.float32).any()


def test_committed_configs_and_mixes_load():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert harness.job_module(cell.job).run
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        cfg = harness.arch_config(cell.config)
        assert cfg.d_model == cell.config["hidden_size"]
    for c in bench["configs"]:
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["source"] == c["source"]


def test_the_harness_finds_everything_by_file_name(tmp_path):
    """A cell, configuration, mix, job and metric that exist only as new
    files and entries are found with no change to the harness."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    for d in ("traffic", "jobs", "metrics"):
        (tmp_path / "bench" / d).mkdir()
    (tmp_path / "bench/configs/toy.json").write_text('{"name": "toy"}')
    (tmp_path / "bench/traffic/toy-mix.json").write_text('{"job": "toyjob"}')
    (tmp_path / "bench/jobs/toyjob.py").write_text("def run(ctx):\n    return 41\n")
    (tmp_path / "bench/metrics/toy.share.py").write_text(
        "def read(run):\n    return 1.5\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "toy.share", "workloads": ["toy-cell"]},
                      {"name": "other", "workloads": ["elsewhere"]}]}))
    cell = harness.load_cell("toy-cell", root=tmp_path)
    assert cell.job == "toyjob" and cell.config == {"name": "toy"}
    assert [m["name"] for m in cell.per_layer] == ["toy.share"]
    assert harness.job_module("toyjob", root=tmp_path).run(None) == 41
    assert harness.metric_reader("toy.share", root=tmp_path)(None) == 1.5
    with pytest.raises(SystemExit):
        harness.load_cell("missing", root=tmp_path)


def test_tiny_cells_keep_the_committed_mix():
    cell = tiny_cell("xlmr-fl-select")
    committed = harness.load_cell("xlmr-fl-select")
    assert cell.mix["limits"] == committed.mix["limits"]
    assert cell.mix["selection_period"] == 1
    assert tiny_cell(("xlmr-base", "fl-period5")).mix["selection_period"] == 5
