"""Mean device time, in ms, of one execution of the engine's prefill
program (``serving/engine.py`` ``_prefill_fn``; a cohort start and a
refill each run it once), from the trace."""
from bench import trace

PROGRAM = r"_prefill_fn"


def read(run):
    if run.trace is None or run.trace_window is None or not run.trace.modules:
        return None
    lo, hi = run.trace_window
    n, total = trace.totals(trace.within(run.trace.modules[0], lo, hi),
                            PROGRAM)
    return 1e3 * total / n if n else None
