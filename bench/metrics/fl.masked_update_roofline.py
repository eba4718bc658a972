"""``masked_update`` kernel time against its roofline: the bytes the τ
masked SGD steps of the selected layers require
(bench.counts.masked_update_bytes) at HBM peak, over the kernel's summed
device time in the traced window.  Nothing to read without the kernel."""
from bench.counts import geometry, masked_update_bytes
from bench.peaks import roofline_seconds

KERNEL = "masked_sgd_update_2d"


def read(run):
    if run.trace is None or run.context.get("job") != "fl":
        return None
    t = run.trace.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    g = geometry(run.context["config"])
    tau = int(run.context["fl"]["local_steps"])
    nbytes = sum(masked_update_bytes(g, r["masks"], tau)
                 for r in run.context["rounds"])
    least, _ = roofline_seconds(nbytes / 3.0 / g.param_bytes * 2, nbytes,
                                run.device_kind)
    return 100.0 * least / t
