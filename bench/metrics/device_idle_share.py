"""Share of the traced window in which no operation ran on the device
(%), averaged over the chips used."""
from bench import trace


def read(run):
    if run.trace is None or run.trace_window is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    ops = run.trace.ops[:run.n_devices]
    busy = sum(trace.busy_s(o, lo, hi) for o in ops) / len(ops)
    return 100.0 * (1.0 - busy / (hi - lo))
