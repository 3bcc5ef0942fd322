"""Refusals per admission: at each boundary of the traced window, every
queued request the runtime considers is either admitted or refused by
one gate (``ContinuousRuntime._try_admit``); the program counts both on
its ``repro:admit`` spans.  Σ refusals / Σ admitted."""
from bench import program_trace


def read(run):
    spans = program_trace.window_spans(run, "admit")
    if not spans:
        return None
    counts = program_trace.outcomes(spans)
    admitted = counts.get(program_trace.ADMITTED, 0)
    return program_trace.refusals(counts) / admitted if admitted else None
