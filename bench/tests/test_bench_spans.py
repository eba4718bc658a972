"""Program spans and device scopes (``bench.spans``): spans with their
thread and stats from a trace recorded on the CPU, device operations and
their scopes from a trace written by hand (a device plane with its
``XLA Modules`` and ``XLA Ops`` lines), the two reductions, and the four
shares, each with its value and with nothing to read."""
import threading
import time

import pytest

from bench import harness
from bench import spans as S
from bench import tracereduce as T
from bench.run import MetricInput


def _record(tmp_path):
    """The window, and in it a loop thread's round and serve spans with
    counters, and a solver thread's solve."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def solver():
        with jax.profiler.TraceAnnotation("fl.select", round=0):
            with jax.profiler.TraceAnnotation("fl.solve", round=0,
                                              memo_hit=False, clients=8):
                time.sleep(0.02)

    with jax.profiler.TraceAnnotation(T.WINDOW):
        with jax.profiler.StepTraceAnnotation("fl.round", step_num=0):
            th = threading.Thread(target=solver)
            th.start()
            with jax.profiler.TraceAnnotation("fl.select_wait", round=0):
                th.join()
            with jax.profiler.TraceAnnotation("fl.dispatch", round=0, cut=2,
                                              probed=8, fused=True):
                time.sleep(0.01)
        for n, (active, prompt) in enumerate([(8, 6), (8, 2)]):
            with jax.profiler.StepTraceAnnotation("serve.step",
                                                  step_num=n) as step:
                with jax.profiler.TraceAnnotation("serve.feed", step=n):
                    time.sleep(0.005)
                step.set_metadata(active=active, prompt=prompt, queued=3)
                with jax.profiler.TraceAnnotation("serve.sync", step=n):
                    time.sleep(0.01)
    jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    _record(d)
    return S.load(str(d)), T.reduce(T.load(str(d)))


def _named(tr, name):
    return [s for s in tr.spans if s.name == name]


def test_spans_load_with_thread_and_stats(recorded):
    tr, _ = recorded
    (rnd,) = _named(tr, "fl.round")
    (solve,) = _named(tr, "fl.solve")
    (disp,) = _named(tr, "fl.dispatch")
    assert rnd.id == 0 and solve.id == 0 and disp.id == 0
    assert disp.stats == {"round": 0, "cut": 2, "probed": 8, "fused": 1}
    assert solve.stats == {"round": 0, "memo_hit": 0, "clients": 8}
    assert solve.line != rnd.line == disp.line == tr.loop_line()
    steps = _named(tr, "serve.step")
    assert [s.stats["active"] for s in steps] == [8, 8]
    assert [s.stats["prompt"] for s in steps] == [6, 2]
    assert all(s.start >= tr.window[0] and s.end <= tr.window[1]
               for s in tr.spans)
    assert tr.ops == [] and tr.scopes == []       # no device on the CPU


def test_shares_from_spans(recorded):
    tr, _ = recorded
    w = tr.window_s
    (solve,) = _named(tr, "fl.solve")
    assert S.solve_share(tr.spans, tr.window) == pytest.approx(
        100 * solve.dur / w)
    steps, syncs = _named(tr, "serve.step"), _named(tr, "serve.sync")
    assert S.host_loop_share(tr.spans, tr.window) == pytest.approx(
        100 * (sum(s.dur for s in steps) - sum(s.dur for s in syncs)) / w)
    assert S.prompt_step_share(tr) == pytest.approx(100 * 8 / 16)
    assert S.probe_share(tr) is None                # no scoped operation


def test_the_metrics_read_the_reduced_trace(recorded):
    """The registered metrics read ``tracereduce``'s host events, which
    keep names and times: the same numbers as from the spans."""
    tr, red = recorded
    fl = MetricInput(red, {"job": "fl"}, "cpu")
    serve = MetricInput(red, {"job": "serve"}, "cpu")
    solve = harness.metric_reader("fl.solve_share")
    loop = harness.metric_reader("serve.host_loop_share")
    assert solve(fl) == pytest.approx(S.solve_share(tr.spans, tr.window))
    assert loop(serve) == pytest.approx(
        S.host_loop_share(tr.spans, tr.window))
    assert solve(serve) is None and loop(fl) is None


def test_nothing_to_read_without_spans():
    """A program without spans, as before they were added: every share
    reads nothing, and no reader raises."""
    red = T.Reduced(window=(0.0, 1.0), busy={}, ops=[],
                    host=[T.Event("np.asarray(jax.Array)", 0.1, 0.2)])
    for name, job in (("fl.solve_share", "fl"),
                      ("serve.host_loop_share", "serve")):
        assert harness.metric_reader(name)(
            MetricInput(red, {"job": job}, "cpu")) is None
    tr = S.SpanTrace(window=(0.0, 1.0), spans=[],
                     ops=[T.Event("%fusion.1 = f32[2] fusion()", 0.0, 0.5)],
                     scopes=[None])
    assert S.shares(tr) == dict.fromkeys(
        ("fl.probe_share", "fl.solve_share", "serve.host_loop_share",
         "serve.prompt_step_share"))
    assert S.device_by_scope(tr) == {S.NO_SCOPE: 0.5}
    assert S.idle_by_span(tr) == {S.NO_SPAN: pytest.approx(0.5)}
    assert S.counters(tr) == {}


def _span(name, a, b, line=0, **stats):
    return S.Span(name, a, b, line, stats)


def test_idle_by_span_names_the_innermost_loop_span():
    loop = [_span("serve.step", 0.0, 4.0), _span("serve.feed", 0.5, 1.0),
            _span("serve.sync", 2.0, 3.5), _span("serve.step", 5.0, 6.0)]
    other = [_span("fl.solve", 0.0, 9.0, line=1)]
    ops = [T.Event("a", 1.0, 2.0), T.Event("b", 3.0, 5.5)]
    tr = S.SpanTrace(window=(0.0, 7.0), spans=loop + other, ops=ops,
                     scopes=[None, None])
    assert S.idle_gaps(tr) == [(0.0, 1.0), (2.0, 3.0), (5.5, 7.0)]
    by = S.idle_by_span(tr)
    assert by == pytest.approx({"serve.step": 0.5 + 0.5,
                                "serve.feed": 0.5, "serve.sync": 1.0,
                                S.NO_SPAN: 1.0})
    assert sum(by.values()) == pytest.approx(7.0 - 3.0 - 0.5)
    assert S.idle_by_span(tr, [(2.0, 3.0)]) == {"serve.sync": 1.0}


def test_innermost_segments_of_nested_spans():
    segs = S.innermost_segments([
        _span("a", 0.0, 10.0), _span("b", 2.0, 4.0), _span("c", 5.0, 6.0),
        _span("d", 5.2, 5.4), _span("e", 11.0, 12.0)])
    assert segs == [(0.0, 2.0, "a"), (2.0, 4.0, "b"), (4.0, 5.0, "a"),
                    (5.0, 5.2, "c"), (5.2, 5.4, "d"), (5.4, 6.0, "c"),
                    (6.0, 10.0, "a"), (11.0, 12.0, "e")]


def test_device_by_scope_and_probe_share():
    ops = [T.Event("loop", 0.0, 4.0), T.Event("x", 0.5, 1.5),
           T.Event("y", 2.0, 3.0), T.Event("z", 5.0, 6.0),
           T.Event("w", 6.0, 8.0)]
    tr = S.SpanTrace(window=(0.0, 9.0), spans=[], ops=ops,
                     scopes=["local_steps", "probe", "local_steps", None,
                             "aggregate"])
    # the loop holds x and y: its time counts through them
    assert S.device_by_scope(tr) == {"aggregate": 2.0, "probe": 1.0,
                                     "local_steps": 1.0, S.NO_SCOPE: 1.0}
    assert S.probe_share(tr) == pytest.approx(100 * 1.0 / 5.0)


def test_device_by_scope_keeps_a_nested_scope_apart():
    """The decode overlay's pad and kernel (``delta_matmul``, inside the
    layer scan) are kept apart from the rest of ``decode_blocks``; the
    probe's share counts every path under ``probe``."""
    ops = [T.Event("pad", 0.0, 2.0), T.Event("kernel", 2.0, 3.0),
           T.Event("dot", 3.0, 7.0), T.Event("head", 7.0, 8.0),
           T.Event("grad_norm", 8.0, 9.0)]
    tr = S.SpanTrace(window=(0.0, 10.0), spans=[], ops=ops,
                     scopes=["decode_blocks/delta_matmul",
                             "decode_blocks/delta_matmul", "decode_blocks",
                             "lm_head", "probe/delta_matmul"])
    assert S.device_by_scope(tr) == {
        "decode_blocks": 4.0, "decode_blocks/delta_matmul": 3.0,
        "lm_head": 1.0, "probe/delta_matmul": 1.0}
    assert S.probe_share(tr) == pytest.approx(100 * 1.0 / 9.0)


def test_counters_average_each_counter(recorded):
    tr, _ = recorded
    assert S.counters(tr) == {
        "fl.dispatch": {"n": 1, "cut": 2, "fused": 1, "probed": 8},
        "fl.solve": {"n": 1, "clients": 8, "memo_hit": 0},
        "serve.step": {"n": 2, "active": 8, "prompt": 4, "queued": 3}}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jit(f)/vmap(local_steps)/while/body/dot_general", "local_steps"),
    ("jit(f)/jit(f)/probe/vmap(vmap(transpose(jvp())))/mul", "probe"),
    ("jit(f)/probe/vmap(vmap(probe))/transpose(jvp())/while/body/mul",
     "probe"),
    ("jit(g)/decode_blocks/while/body/delta_matmul/jit(base_delta_matmul_2d)"
     "/pad", "decode_blocks/delta_matmul"),
    ("jit(g)/jit(g)/decode_blocks/while/body/dot_general", "decode_blocks"),
    ("jit(g)/jit(g)/lm_head/dot_general", "lm_head"),
    ("jit(f)/jit(f)/probes/add", None),
    ("", None),
])
def test_scope_of_op_name(op_name, scope):
    assert S.scope_of(op_name) == scope


def test_hlo_scopes_from_a_compiled_program():
    """Scopes as XLA keeps them in the compiled program's metadata, under
    vmap and grad; a fusion takes its root's."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("probe"):
            g = jax.vmap(jax.grad(lambda y: jnp.sum(jnp.tanh(y @ w))))(x)
        with jax.named_scope("aggregate"):
            return jnp.sum(g, 0) * 2.0

    x = jnp.ones((3, 4, 8))
    w = jnp.ones((8, 8))
    text = jax.jit(f).lower(x, w).compile().as_text()
    table = S.hlo_scopes([text])
    module = text.split()[1].rstrip(",")
    scopes = {s for (m, _), v in table.items() if m == module
              for _, s in v}
    assert {"probe", "aggregate"} <= scopes


XSPACE = '''
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0000 duration_ps: 20000000000 }
    events { metadata_id: 3 offset_ps: 10500000000 duration_ps: 4000000000
             stats { metadata_id: 2 int64_value: 4 } }
    events { metadata_id: 2 offset_ps: 11000000000 duration_ps: 3000000000
             stats { metadata_id: 1 int64_value: 4 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.sync" } }
  event_metadata { key: 3 value { id: 3 name: "serve.step" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "step_num" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 1000000000 duration_ps: 9000000000 }
    events { metadata_id: 11 offset_ps: 14000000000 duration_ps: 4000000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 9000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 14000000000 duration_ps: 2000000000 }
    events { metadata_id: 4 offset_ps: 16000000000 duration_ps: 2000000000 }
  }
  event_metadata { key: 1 value { id: 1
                   name: "%fusion.1 = f32[2] fusion(f32[2] %x)" } }
  event_metadata { key: 2 value { id: 2
                   name: "%add.3 = f32[2] add(f32[2] %x, f32[2] %y)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.7 = f32[2] copy(f32[2] %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%mul.2 = f32[4] multiply(f32[4] %a, f32[4] %b)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_f(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_g(456)" } }
}
'''

HLO_F = '''HloModule jit_f, entry_computation_layout={()->f32[2]}

ENTRY %main (x: f32[2], y: f32[2]) -> f32[2] {
  %fusion.1 = f32[2] fusion(f32[2] %x), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/jit(f)/probe/dot_general"}
  %add.3 = f32[2] add(f32[2] %x, f32[2] %y), metadata={op_name="jit(f)/jit(f)/aggregate/add"}
  ROOT %copy.7 = f32[2] copy(f32[2] %x)
}
'''
HLO_G = ['''HloModule jit_g, entry_computation_layout={()->f32[2]}

ENTRY %main (x: f32[2], y: f32[2]) -> f32[2] {
  %add.3 = f32[2] add(f32[2] %x, f32[2] %y), metadata={op_name="jit(g)/lm_head/add"}
  ROOT %mul.2 = f32[4] multiply(f32[4] %a, f32[4] %b), metadata={op_name="jit(g)/decode_blocks/while/body/delta_matmul/mul"}
}
''', '''HloModule jit_g, entry_computation_layout={()->f32[2]}

ENTRY %main (x: f32[2], y: f32[2]) -> f32[2] {
  %add.3 = f32[2] add(f32[2] %x, f32[2] %y), metadata={op_name="jit(g)/eval/add"}
  ROOT %mul.2 = f16[4] multiply(f16[4] %q, f16[4] %b), metadata={op_name="jit(g)/eval/mul"}
}
''']


@pytest.fixture(scope="module")
def device_trace(tmp_path_factory):
    from jax.profiler import ProfileData
    d = tmp_path_factory.mktemp("xspace") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(d.parents[2])


def test_device_ops_take_their_scope(device_trace):
    """From the HLO text of the module around the operation.  Two modules
    of one name that disagree are told apart by the instruction's result
    type and opcode."""
    tr = S.load(device_trace, [HLO_F] + HLO_G)
    assert tr.window == pytest.approx((0.0, 0.02))
    assert [e.op for e in tr.ops] == ["fusion.1", "add.3", "copy.7",
                                      "add.3", "mul.2"]
    assert tr.scopes == ["probe", "aggregate", None, None,
                         "decode_blocks/delta_matmul"]
    assert S.device_by_scope(tr) == pytest.approx(
        {"probe": 0.004, "aggregate": 0.003,
         "decode_blocks/delta_matmul": 0.002, S.NO_SCOPE: 0.003})
    assert [(s.name, s.id) for s in tr.spans] == [("serve.step", 4),
                                                  ("serve.sync", 4)]
    # the device idles 0-1 ms, 5-6 ms, 10-14 ms and 18-20 ms; the loop
    # thread was in serve.step from 10.5 ms, in its serve.sync from 11 to
    # 14 ms
    assert S.idle_by_span(tr) == pytest.approx(
        {"serve.sync": 0.003, "serve.step": 0.0005,
         S.NO_SPAN: 0.001 + 0.001 + 0.0005 + 0.002})
    assert S.probe_share(tr) == pytest.approx(100 * 4 / 12)


def test_without_hlo_text_no_operation_has_a_scope(device_trace):
    tr = S.load(device_trace)
    assert tr.scopes == [None] * 5
    assert S.probe_share(tr) is None
