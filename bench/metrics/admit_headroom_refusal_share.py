"""Share of the admission refusals in the traced window that the
executor's headroom gate made (%): a live cohort shares one decode
position, so a request whose output cap exceeds ``n_max - t`` cannot
join it (``EngineContinuousExecutor.refusal``), counted on the
program's ``repro:admit`` spans."""
from bench import program_trace


def read(run):
    spans = program_trace.window_spans(run, "admit")
    if not spans:
        return None
    counts = program_trace.outcomes(spans)
    refused = program_trace.refusals(counts)
    return 100.0 * counts.get("headroom", 0) / refused if refused else None
