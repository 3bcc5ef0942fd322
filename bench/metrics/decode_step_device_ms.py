"""Device time, in ms, of the engine's paged decode-segment program
(``serving/engine.py`` ``_decode_chunk_paged_fn``) per decode step it
took, from the trace and the steps the polls report."""
from bench import trace

PROGRAM = r"_decode_chunk_paged_fn"


def read(run):
    if run.trace is None or run.trace_window is None or not run.trace.modules:
        return None
    lo, hi = run.trace_window
    n, total = trace.totals(trace.within(run.trace.modules[0], lo, hi),
                            PROGRAM)
    steps = sum(p[2] for p in run.rec.polls)
    return 1e3 * total / steps if n and steps else None
