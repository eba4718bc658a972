"""Program spans (``jax.profiler`` annotations) on the FL round and the
serve loop, read back from a profiler trace recorded on the CPU.

Every span of round ``t`` carries ``round=t`` (``fl.round`` its
``step_num``), every span of serve step ``n`` carries ``step=n``
(``serve.step`` its ``step_num``); counters ride as the spans' stats.  The
spans must not change what the program computes, nor add a transfer.
"""
import glob
from dataclasses import dataclass, field

import jax
import numpy as np
import pytest

from repro.configs.base import FLConfig, RuntimeConfig, get_arch, reduced
from repro.core import client as client_mod
from repro.core.server import FLServer
from repro.data.synthetic import FederatedTaskConfig, SyntheticFederatedData
from repro.launch.serve import Request, SlotServer, demo_store
from repro.models.model import Model

FL_LOOP = ("fl.round", "fl.select_wait", "fl.dispatch", "fl.eval")
FL_SOLVER = ("fl.select", "fl.stats_sync", "fl.solve")
FL_ALL = FL_LOOP + FL_SOLVER + ("fl.plan", "fl.sample", "fl.finalize",
                                "fl.checkpoint")
SERVE_STAGES = ("serve.admit", "serve.feed", "serve.dispatch", "serve.sync",
                "serve.bookkeep")


@dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    line: int
    stats: dict = field(compare=False)

    @property
    def id(self):
        return self.stats.get("round", self.stats.get(
            "step", self.stats.get("step_num")))

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


def _traced(tmp_path, fn):
    """fn() under the profiler; its result and the program's spans."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans, line_no = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("fl.", "serve.")):
                    start = int(ev.start_ns)
                    spans.append(Span(ev.name, start,
                                      start + int(ev.duration_ns), line_no,
                                      dict(ev.stats)))
            line_no += 1
    return out, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


# ---------------------------------------------------------------------------
# the FL round
# ---------------------------------------------------------------------------

ROUNDS = 4


@pytest.fixture(scope="module")
def fl_world():
    cfg = reduced(get_arch("xlm_roberta_base"), n_layers=2, d_model=32)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    task = FederatedTaskConfig(n_clients=8, n_classes=10,
                               vocab_size=cfg.vocab_size, seq_len=8,
                               samples_per_client=16, skew="label",
                               objective="classification")
    fl = FLConfig(n_clients=8, cohort_size=3, rounds=ROUNDS, local_steps=2,
                  lr=0.01, batch_size=4, strategy="ours", budget=1, lam=1.0,
                  seed=0)
    return model, model.init(jax.random.PRNGKey(0)), task, fl


def _fl_run(fl_world, ckpt_dir=None):
    model, params, task, fl = fl_world
    server = FLServer(model, fl, SyntheticFederatedData(task),
                      checkpoint_dir=ckpt_dir, checkpoint_every=2)
    return server.run(params)


@pytest.fixture(scope="module")
def fl_traced(fl_world, tmp_path_factory):
    _fl_run(fl_world)                       # compiled outside the trace
    ckpt = tmp_path_factory.mktemp("ckpt")
    return _traced(tmp_path_factory.mktemp("trace"),
                   lambda: _fl_run(fl_world, str(ckpt)))


def test_fl_spans_appear_with_their_round(fl_traced):
    _, spans = fl_traced
    for name in FL_ALL:
        assert _named(spans, name), name
    rounds = sorted(s.stats["step_num"] for s in _named(spans, "fl.round"))
    assert rounds == list(range(ROUNDS))
    for name in FL_ALL[1:]:
        ids = {s.id for s in _named(spans, name)}
        assert ids <= set(range(ROUNDS)), (name, ids)
    for name in ("fl.select_wait", "fl.dispatch", "fl.eval", "fl.finalize",
                 "fl.sample", "fl.plan"):
        assert {s.id for s in _named(spans, name)} == set(range(ROUNDS))
    # the probe of round t+1 rides round t's update: the last round probes
    # nothing, so select/stats_sync/solve cover every round
    for name in FL_SOLVER:
        assert {s.id for s in _named(spans, name)} == set(range(ROUNDS))
    assert {s.id for s in _named(spans, "fl.checkpoint")} == {1, 3}


def test_fl_counters(fl_traced, fl_world):
    _, spans = fl_traced
    fl = fl_world[3]
    for s in _named(spans, "fl.plan") + _named(spans, "fl.sample"):
        assert s.stats["cohort"] == fl.cohort_size
    for s in _named(spans, "fl.solve"):
        assert s.stats["clients"] == fl.cohort_size
        assert s.stats["memo_hit"] in (0, 1)
    for s in _named(spans, "fl.stats_sync"):
        assert s.stats["probed"] == fl.cohort_size
    assert all(s.stats["fallback"] == 0
               for s in _named(spans, "fl.select_wait"))
    disp = sorted(_named(spans, "fl.dispatch"), key=lambda s: s.id)
    # every round re-selects, so the next round's probe rides the update;
    # a checkpoint (after rounds 1 and 3) drains the lookahead, and the
    # next round's probe then runs on its own after the save
    assert [s.stats["fused"] for s in disp] == [1, 0, 1, 0]
    assert [s.stats["probed"] for s in disp] == [fl.cohort_size, 0,
                                                 fl.cohort_size, 0]
    assert all(s.stats["cut"] >= -1 for s in disp)


def test_fl_children_lie_inside_their_parent(fl_traced):
    _, spans = fl_traced
    rounds = {s.stats["step_num"]: s for s in _named(spans, "fl.round")}
    loop = {s.line for s in rounds.values()}
    assert len(loop) == 1
    for name in FL_LOOP[1:]:
        for s in _named(spans, name):
            assert s.line in loop and s.inside(rounds[s.id]), (name, s.id)
    selects = {s.id: s for s in _named(spans, "fl.select")}
    assert {s.line for s in selects.values()}.isdisjoint(loop)
    for name in ("fl.stats_sync", "fl.solve"):
        for s in _named(spans, name):
            assert s.inside(selects[s.id]), (name, s.id)


def test_fl_results_bit_identical_with_the_profiler(fl_traced, fl_world,
                                                   tmp_path):
    (p_on, h_on), _ = fl_traced
    p_off, h_off = _fl_run(fl_world, str(tmp_path))
    for a, b in zip(h_on.records, h_off.records):
        np.testing.assert_array_equal(a.mask_matrix, b.mask_matrix)
        np.testing.assert_array_equal(a.cohort, b.cohort)
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fl_spans_add_no_transfer(fl_world, strict_mode, tmp_path):
    """Under the profiler and REPRO_STRICT's tripwires (no implicit
    transfer, no new program), warmed rounds run as before."""
    client_mod.clear_jit_cache()
    _, h_warm = _fl_run(fl_world)

    def strict():
        with strict_mode("spans under strict", force=True):
            return _fl_run(fl_world)
    (_, h), spans = _traced(tmp_path, strict)
    assert h.summary() == h_warm.summary()
    assert len(_named(spans, "fl.round")) == ROUNDS


# ---------------------------------------------------------------------------
# the serve loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_world():
    cfg = reduced(get_arch("tinyllama_1_1b"), n_layers=3, d_model=64)
    model = Model(cfg, RuntimeConfig(remat=False, seq_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    return model, params, demo_store(model, params, users=3,
                                     layers_per_user=2, seed=0)


def _requests(cfg, users):
    rng = np.random.RandomState(1)
    return [Request(i, rng.randint(0, cfg.vocab_size,
                                   2 + i % 4).tolist(), 2 + i % 3,
                    user_id=i % users) for i in range(7)]


def _serve(serve_world, mode):
    model, params, store = serve_world
    server = SlotServer(model, params, slots=3, max_seq=16, mode=mode,
                        store=store if mode == "delta" else None)
    reqs = _requests(model.cfg, 3 if mode == "delta" else 1)
    done, stats = server.run(reqs)
    return {r.rid: list(r.generated) for r in done}, reqs, stats


@pytest.fixture(scope="module", params=["shared", "delta"])
def serve_traced(request, serve_world, tmp_path_factory):
    _serve(serve_world, request.param)      # compiled outside the trace
    return request.param, _traced(tmp_path_factory.mktemp("trace"),
                                  lambda: _serve(serve_world, request.param))


def test_serve_spans_appear_with_their_step(serve_traced):
    mode, ((_, reqs, stats), spans) = serve_traced
    steps = _named(spans, "serve.step")
    assert sorted(s.stats["step_num"] for s in steps) == list(
        range(stats["steps"]))
    assert len({s.line for s in steps}) == 1
    by_step = {s.stats["step_num"]: s for s in steps}
    for name in SERVE_STAGES:
        stage = _named(spans, name)
        assert sorted(s.id for s in stage) == list(range(stats["steps"]))
        for s in stage:
            assert s.inside(by_step[s.id]), (name, s.id)
    uploads = _named(spans, "serve.upload")
    if mode == "shared":
        assert not uploads
    else:
        admits = {a.id: a for a in _named(spans, "serve.admit")}
        assert uploads and all(u.inside(admits[u.id]) for u in uploads)
        # two layers a user: two uploads an admission
        assert len(uploads) == 2 * len(reqs)
        assert sum(a.stats["admitted"] for a in admits.values()) == 7


def test_serve_step_counters_match_the_loop(serve_traced):
    """Σ active = every slot-step the requests took (prompt + output − 1
    each); Σ prompt = those whose output the loop discarded."""
    _, ((_, reqs, stats), spans) = serve_traced
    steps = _named(spans, "serve.step")
    assert sum(s.stats["active"] for s in steps) == sum(
        len(r.prompt) + r.max_new - 1 for r in reqs)
    assert sum(s.stats["prompt"] for s in steps) == sum(
        len(r.prompt) - 1 for r in reqs)
    assert steps[0].stats["queued"] == len(reqs) - 3
    assert stats["gen_tokens"] == sum(r.max_new for r in reqs)


def test_serve_tokens_bit_identical_with_the_profiler(serve_traced,
                                                      serve_world):
    mode, ((on, _, _), _) = serve_traced
    off, _, _ = _serve(serve_world, mode)
    assert on == off
