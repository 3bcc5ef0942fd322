"""Mean host time, in ms, of the runtime's admission at a segment
boundary (``serving/runtime.py`` ``ContinuousRuntime.run``: aging and
drops, degradation, ``_try_admit`` with every ``validate()``, and
preemption), from the program's ``repro:admit`` spans in the traced
window."""
from bench import program_trace


def read(run):
    spans = program_trace.window_spans(run, "admit")
    if not spans:
        return None
    return 1e3 * sum(s.dur_s for s in spans) / len(spans)
