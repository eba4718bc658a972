#!/usr/bin/env python3
"""The program's own spans and scopes, read from a profiler trace.

The program annotates its hot path: host spans
(``jax.profiler.TraceAnnotation``) for the stages of the FL round
(``fl.*``, each with its ``round``) and of the serve loop (``serve.*``,
each with its ``step``), their counters as the spans' stats, and
``jax.named_scope`` names on the device programs (:data:`SCOPES`).  The
spans share the profiler's clock with the device's operations, so an idle
gap can be put down to the stage the loop thread was in, and the device's
time split by program phase.

* :func:`load` reads a trace: the spans with their thread (the trace's
  line) and stats, and the busiest device's operations with their scope.
  The chip's ``XLA Ops`` events carry no ``op_name`` (checked on a v5e), so
  a scope comes from the compiled programs' HLO text (:func:`hlo_scopes`,
  from an ``--xla_dump_to`` directory); an operation is found there by its
  module (the ``XLA Modules`` event around it) and its instruction, and a
  fusion takes the scope of its root, whose metadata XLA keeps.
* :func:`idle_by_span`, :func:`device_by_scope` and :func:`counters`
  reduce a :class:`SpanTrace`; :func:`probe_share`, :func:`solve_share`,
  :func:`host_loop_share` and :func:`prompt_step_share` are the per-layer
  shares.  The two that need only span names and times
  (:func:`solve_share`, :func:`host_loop_share`) also read the host events
  of ``bench.tracereduce``, which keeps no stats or scopes; they are the
  metrics ``fl.solve_share`` and ``serve.host_loop_share``.

Run as a script it measures one cell with the profiler on, as
``bench/run.py --trace 1`` does, and prints that run's result line with
``idle_by_span``, ``device_by_scope``, ``counters`` and the four shares
added, and the window's end-to-end numbers beside them (``traced``)::

    python3 bench/spans.py --workload <cell> --seed <n> [--seconds 10]

It compiles every program afresh (its own compile cache, in a temporary
directory), so that XLA writes the HLO text the scopes are read from.
"""
from __future__ import annotations

import bisect
import glob
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterable, Optional

if __package__ in (None, ""):
    ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracereduce as T  # noqa: E402

SCOPES = ("probe", "local_steps", "aggregate", "eval",
          "decode_blocks", "delta_matmul", "lm_head")
PREFIXES = ("fl.", "serve.")
LOOP_ROOTS = ("fl.round", "serve.step")
IDS = ("round", "step", "step_num")
NO_SPAN = "(no program span)"
NO_SCOPE = "(no scope)"
MODULE_LINE = "XLA Modules"


@dataclass(frozen=True)
class Span:
    """A program span: its name, interval (seconds), thread (the index of
    its line among the trace's host lines) and stats."""
    name: str
    start: float
    end: float
    line: int
    stats: dict = field(default_factory=dict, compare=False)

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def id(self) -> Optional[int]:
        """The round or step the span belongs to."""
        for k in IDS:
            if k in self.stats:
                return self.stats[k]
        return None


@dataclass
class SpanTrace:
    window: tuple[float, float]
    spans: list[Span]
    ops: list[T.Event]            # the busiest device's, clipped to the window
    scopes: list[Optional[str]]   # each op's scope path, or None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def loop_line(self) -> Optional[int]:
        """The thread that runs the round or serve loop."""
        lines = [s.line for s in self.spans if s.name in LOOP_ROOTS]
        return max(set(lines), key=lines.count) if lines else None


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

_WRAPPED = re.compile(r"^[\w.-]*\((.*)\)$")


def scope_of(op_name: str) -> Optional[str]:
    """The :data:`SCOPES` in an ``op_name`` path, outermost first and
    joined by ``/`` (``jit(f)/jit(f)/vmap(local_steps)/while/body/dot`` →
    ``local_steps``; ``jit(g)/decode_blocks/while/body/delta_matmul/pad``
    → ``decode_blocks/delta_matmul``); transformations wrap a scope's name
    (``vmap(...)``, ``transpose(jvp(...))``), and a name inside itself is
    one scope (``probe/vmap(vmap(probe))``: the scope, then the function
    of that name)."""
    found: list[str] = []
    for part in op_name.split("/"):
        while True:
            if part in SCOPES:
                if part not in found:
                    found.append(part)
                break
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
    return "/".join(found) or None


_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = (.*?) ([a-z][a-z0-9_-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def signature(text: str) -> Optional[tuple[str, str, str]]:
    """An instruction's name, result type and opcode, as an HLO line or a
    device event's name gives them."""
    m = _INSTR.match(text)
    return m.groups() if m else None


def hlo_scopes(texts: Iterable[str]) -> dict[tuple[str, str], list]:
    """From compiled modules' HLO text, each instruction's scope:
    ``{(module, instruction): [(signature, scope), ...]}``, one entry per
    module of that name that holds the instruction."""
    out: dict[tuple[str, str], list] = {}
    for text in texts:
        m = _MODULE.search(text)
        if not m:
            continue
        module = m.group(1)
        for line in text.splitlines():
            sig = signature(line)
            if sig is None:
                continue
            meta = _OP_NAME.search(line)
            out.setdefault((module, sig[0]), []).append(
                (sig, scope_of(meta.group(1)) if meta else None))
    return out


def dumped_hlo(dump_dir: str) -> list[str]:
    """The optimised modules XLA wrote under ``--xla_dump_to``."""
    return [Path(p).read_text() for p in sorted(glob.glob(
        f"{dump_dir}/**/*after_optimizations.txt", recursive=True))]


def op_scope(ev: T.Event, module: Optional[str],
             table: dict[tuple[str, str], list]) -> Optional[str]:
    """An operation's scope: its instruction in the module that ran it;
    where modules of one name disagree, the one whose instruction has the
    trace's result type and opcode."""
    cands = table.get((module, ev.op), []) if module else []
    scopes = {s for _, s in cands}
    if len(scopes) == 1:
        return scopes.pop()
    sig = signature(ev.name)
    same = {s for c, s in cands if c == sig}
    return same.pop() if len(same) == 1 else None


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats if not k.startswith("_")}


def load(log_dir: str, hlo_texts: Iterable[str] = ()) -> SpanTrace:
    """Read the one ``.xplane.pb`` under ``log_dir``: the program's spans
    (host events named ``fl.*`` or ``serve.*``) and the busiest device's
    operations inside the window, each with its scope from the compiled
    modules' text ``hlo_texts``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {log_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    window, spans, devices = None, [], {}
    line_no = 0
    for plane in data.planes:
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    e = T._event(ev)
                    if ev.name == T.WINDOW and window is None:
                        window = (e.start, e.end)
                    elif ev.name.startswith(PREFIXES):
                        spans.append(Span(ev.name, e.start, e.end, line_no,
                                          _stats(ev)))
                line_no += 1
        elif name.startswith(T.DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == T.OP_LINE:
                    ops = [T._event(ev) for ev in line.events]
                elif line.name == MODULE_LINE:
                    mods = [T._event(ev) for ev in line.events]
            devices[name] = (ops, mods)
    if window is None:
        raise ValueError(f"no {T.WINDOW!r} span in the trace")
    table = hlo_scopes(hlo_texts)
    t0, t1 = window
    busy = {d: T.busy_seconds(ops, t0, t1)
            for d, (ops, _) in devices.items()}
    main = max(busy, key=busy.get) if any(busy.values()) else None
    ops_out, scopes = [], []
    if main is not None:
        ops, mods = devices[main]
        mods.sort(key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in ops:
            s, t = max(e.start, t0), min(e.end, t1)
            if t <= s:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            module = (mods[i].name.split("(")[0]
                      if i >= 0 and e.start < mods[i].end else None)
            ops_out.append(T.Event(e.name, s, t))
            scopes.append(op_scope(e, module, table))
    return SpanTrace(window=window, spans=spans, ops=ops_out, scopes=scopes)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def innermost_segments(spans: Iterable[Span]) -> list[tuple[float, float, str]]:
    """One thread's nested spans as disjoint [start, end) segments, each
    named by the innermost span over it, in time order."""
    segs: list[tuple[float, float, str]] = []
    stack: list[Span] = []
    cur = None

    def emit(a, b, name):
        if b > a:
            segs.append((a, b, name))

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            emit(cur, top.end, top.name)
            cur = top.end
        if stack:
            emit(cur, s.start, stack[-1].name)
            s = Span(s.name, s.start, min(s.end, stack[-1].end), s.line,
                     s.stats)
        cur = s.start
        stack.append(s)
    while stack:
        top = stack.pop()
        emit(cur, top.end, top.name)
        cur = top.end
    return segs


def idle_gaps(tr: SpanTrace) -> list[tuple[float, float]]:
    """The holes in the device's busy union inside the window."""
    t0, t1 = tr.window
    edges = [t0] + [x for ab in T.union(tr.ops) for x in ab] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(tr: SpanTrace, gaps=None) -> dict[str, float]:
    """Idle seconds in the window by the innermost program span the loop
    thread was in; the rest under :data:`NO_SPAN`.  ``gaps`` restricts it
    to some of the idle intervals (default: all)."""
    gaps = idle_gaps(tr) if gaps is None else gaps
    line = tr.loop_line()
    segs = innermost_segments([s for s in tr.spans if s.line == line])
    starts = [a for a, _, _ in segs]
    out: dict[str, float] = {}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            s, t, name = segs[i]
            d = min(t, b) - max(s, a)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            i += 1
        if b - a - covered > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a - covered)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def device_by_scope(tr: SpanTrace) -> dict[str, float]:
    """Device seconds of the innermost operations in the window by their
    scope path: a top-level scope (``decode_blocks``), or a scope nested
    in one (``decode_blocks/delta_matmul``, which the top-level key then
    leaves out); the rest under :data:`NO_SCOPE`."""
    scope = {id(e): s for e, s in zip(tr.ops, tr.scopes)}
    out: dict[str, float] = {}
    for e in T.leaves(tr.ops):
        k = scope.get(id(e)) or NO_SCOPE
        out[k] = out.get(k, 0.0) + e.dur
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _seconds(events, name: str, window) -> float:
    t0, t1 = window
    return sum(max(0.0, min(e.end, t1) - max(e.start, t0))
               for e in events if e.name == name)


def probe_share(tr: SpanTrace) -> Optional[float]:
    """% of the device's operation time in the window under scope
    ``probe``; None where no operation has a scope."""
    by = device_by_scope(tr)
    total = sum(by.values())
    if total <= 0 or set(by) <= {NO_SCOPE}:
        return None
    return 100.0 * sum(v for k, v in by.items()
                       if k.split("/")[0] == "probe") / total


def solve_share(events, window) -> Optional[float]:
    """% of the window the solver thread spent in ``fl.solve``; None
    without such spans.  ``events``: spans or ``tracereduce`` events."""
    events = [e for e in events if e.name == "fl.solve"]
    if not events:
        return None
    return 100.0 * _seconds(events, "fl.solve", window) / (window[1]
                                                            - window[0])


def host_loop_share(events, window) -> Optional[float]:
    """% of the window the serve loop spent in ``serve.step`` outside
    ``serve.sync``: the host's work the device waits for; None without
    ``serve.step`` spans."""
    events = [e for e in events if e.name in ("serve.step", "serve.sync")]
    if not any(e.name == "serve.step" for e in events):
        return None
    host = (_seconds(events, "serve.step", window)
            - _seconds(events, "serve.sync", window))
    return 100.0 * host / (window[1] - window[0])


def prompt_step_share(tr: SpanTrace) -> Optional[float]:
    """% of the slot-steps that began in the window whose token was a
    prompt token with its output discarded (Σ ``prompt`` / Σ ``active`` of
    the ``serve.step`` spans); None without those counters."""
    t0, t1 = tr.window
    steps = [s.stats for s in tr.spans if s.name == "serve.step"
             and t0 <= s.start < t1 and "active" in s.stats]
    active = sum(s["active"] for s in steps)
    if not active:
        return None
    return 100.0 * sum(s["prompt"] for s in steps) / active


def counters(tr: SpanTrace) -> dict[str, dict[str, float]]:
    """For each span name with counters, over its spans that began in the
    window: their number ``n`` and each counter's mean (``serve.step``'s
    ``queued``: the mean queue; ``fl.solve``'s ``memo_hit``: the share of
    solves replayed).  The ids (``round``, ``step``) are left out."""
    t0, t1 = tr.window
    out: dict[str, dict[str, float]] = {}
    for name in sorted({s.name for s in tr.spans}):
        runs = [s.stats for s in tr.spans
                if s.name == name and t0 <= s.start < t1]
        keys = sorted({k for st in runs for k in st} - set(IDS))
        if runs and keys:
            out[name] = {"n": len(runs), **{
                k: sum(st.get(k, 0) for st in runs) / len(runs)
                for k in keys}}
    return out


def shares(tr: SpanTrace) -> dict[str, Optional[float]]:
    return {"fl.probe_share": probe_share(tr),
            "fl.solve_share": solve_share(tr.spans, tr.window),
            "serve.host_loop_share": host_loop_share(tr.spans, tr.window),
            "serve.prompt_step_share": prompt_step_share(tr)}


# ---------------------------------------------------------------------------
# the script
# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    import argparse
    import json
    import os
    import shutil
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    scratch = tempfile.mkdtemp(prefix="bench-spans-")
    dump = os.path.join(scratch, "hlo")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text")))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(scratch, "cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from bench import harness
    from bench import run as R

    class KeptTracer(R.Tracer):
        """The run's tracer, which reads the spans before the trace goes."""
        spans: Optional[SpanTrace] = None

        def stop(self):
            if self._span is None:
                return
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()
            try:
                self.result = T.reduce(T.load(self._dir))
                KeptTracer.spans = load(self._dir, dumped_hlo(dump))
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)

    class Context(R.JobContext):
        def tracing(self, start: bool = True):
            return KeptTracer(True, start)

    try:
        cell = harness.load_cell(args.workload)
        harness.use_compile_cache()
        device = R.check_device(cell.chips)
        ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=True, clock=harness.CompileClock(),
                      t_start=R.T_START)
        result = harness.job_module(cell.job).run(ctx)
        tr = result.context["trace"]
        metrics = {}
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"])(
                R.MetricInput(tr, result.context, device["kind"]))
            if v is not None:
                metrics[m["name"]] = v
        st = KeptTracer.spans
        metrics.update({k: v for k, v in shares(st).items()
                        if v is not None
                        and k.startswith(result.context["job"] + ".")})
        out = {"correct": result.correct, "metrics": metrics,
               "traced": result.metrics,
               "compiles_in_window": result.compiles_in_window,
               "device": {"kind": device["kind"], "busy_s": tr.busy_s,
                          "window_s": tr.window_s},
               "breakdown": dict(tr.breakdown(),
                                 idle_by_span=idle_by_span(st),
                                 long_idle_by_span=idle_by_span(st, [
                                     g for g in idle_gaps(st)
                                     if g[1] - g[0] >= 1e-3]),
                                 device_by_scope=device_by_scope(st),
                                 counters=counters(st)),
               "spans": {n: sum(1 for s in st.spans if s.name == n)
                         for n in sorted({s.name for s in st.spans})}}
        print(json.dumps(out, default=float), flush=True)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    main()
