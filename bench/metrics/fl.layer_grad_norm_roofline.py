"""``layer_grad_norm`` (the selection probe's per-layer Σg²) kernel time
against its roofline: every layer's gradient read once per probed client
(bench.counts.layer_grad_norm_bytes) at HBM peak, over the kernel's summed
device time in the traced window.  Nothing to read without the kernel."""
from bench.counts import geometry, layer_grad_norm_bytes
from bench.peaks import roofline_seconds

KERNEL = "layer_sq_norms_2d"


def read(run):
    if run.trace is None or run.context.get("job") != "fl":
        return None
    t = run.trace.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    g = geometry(run.context["config"])
    nbytes = sum(layer_grad_norm_bytes(g, r["probed"])
                 for r in run.context["rounds"])
    least, _ = roofline_seconds(nbytes / g.param_bytes * 2, nbytes,
                                run.device_kind)
    return 100.0 * least / t
