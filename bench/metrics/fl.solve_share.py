"""Share of the FL window the solver thread spent in the (P1) solve: the
program's ``fl.solve`` spans (the strategy's solve or its memo hit,
``FLServer.select_round``), summed inside the traced window, over the
window.  Nothing to read without those spans."""
from bench.spans import solve_share


def read(run):
    if run.trace is None or run.context.get("job") != "fl":
        return None
    return solve_share(run.trace.host, run.trace.window)
