"""Mean host time, in ms, the engine takes to launch one decode segment
(``serving/engine.py`` ``ServingEngine.generate_chunked``, from entry
until the jitted segment call returns: weights, lease top-up, the block
table's re-ship and the dispatch), from the program's
``repro:segment.launch`` spans in the traced window."""
from bench import program_trace


def read(run):
    spans = program_trace.window_spans(run, "segment.launch")
    if not spans:
        return None
    return 1e3 * sum(s.dur_s for s in spans) / len(spans)
