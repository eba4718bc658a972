"""The whole round's share of the chip's bf16 peak: the operations the
rounds completed in the traced window require (bench.counts.fl_round_flops)
over the window's length times the peak."""
from bench.counts import fl_round_flops, geometry
from bench.peaks import peaks


def read(run):
    if run.trace is None or run.context.get("job") != "fl":
        return None
    fl = run.context["fl"]
    g = geometry(run.context["config"])
    flops = sum(fl_round_flops(g, r["masks"], r["probed"],
                               tau=int(fl["local_steps"]),
                               batch=int(fl["batch_size"]),
                               seq=int(fl["seq_len"]),
                               eval_rows=int(fl["test_samples"]))
                for r in run.context["rounds"])
    return 100.0 * flops / (run.trace.window_s
                            * peaks(run.device_kind)["flops_bf16"])
