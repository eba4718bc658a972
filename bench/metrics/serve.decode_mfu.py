"""The decode step's share of the chip's peak: per step the larger of the
required operations over peak FLOP/s and the required bytes over peak
bandwidth (bench.counts.decode_step_counts), summed over the steps that
started in the traced window, over the window's length."""
from bench.counts import decode_step_counts, geometry
from bench.peaks import roofline_seconds


def read(run):
    if run.trace is None or run.context.get("job") != "serve":
        return None
    g = geometry(run.context["config"])
    layers = run.context["layers"]
    least = sum(roofline_seconds(*decode_step_counts(g, act, layers),
                                 run.device_kind)[0]
                for _, act in run.context["steps"])
    return 100.0 * least / run.trace.window_s
