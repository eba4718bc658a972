"""``delta_matmul`` (base + per-slot delta products) kernel time against
its roofline: per product the larger of required operations over peak and
required bytes over bandwidth, the base weight read once and only the
active users' delta rows (bench.counts.delta_matmul_counts), over the
kernel's summed device time in the traced window."""
from bench.counts import delta_matmul_counts, geometry
from bench.peaks import roofline_seconds

KERNEL = "base_delta_matmul_2d"


def read(run):
    if run.trace is None or run.context.get("job") != "serve":
        return None
    layers = run.context["layers"]
    t = run.trace.kernel_seconds(KERNEL)
    if layers is None or t <= 0:
        return None
    g = geometry(run.context["config"])
    least = sum(roofline_seconds(f, b, run.device_kind)[0]
                for _, act in run.context["steps"]
                for f, b in delta_matmul_counts(g, act, layers))
    return 100.0 * least / t
