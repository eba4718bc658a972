"""Share of the serve window the loop thread spent on host work the device
waits for: inside the program's ``serve.step`` spans but outside their
``serve.sync`` (the argmax wait), summed inside the traced window, over
the window.  Nothing to read without those spans."""
from bench.spans import host_loop_share


def read(run):
    if run.trace is None or run.context.get("job") != "serve":
        return None
    return host_loop_share(run.trace.host, run.trace.window)
