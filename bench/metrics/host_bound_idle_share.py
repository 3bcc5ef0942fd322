"""Share of the traced window in which no operation ran on the device
while the host's innermost span was one of the program's (%), averaged
over the chips used: the part of ``device_idle_share`` under the
program's host work.  The rest is idle time with no work at all (the
harness's ``wait``) or under the harness's own spans, those nested in
the program's included (``step``, ``segment``, ``poll``, ``prefill``).

The benchmark's cache read of a finished row runs inside the program's
``repro:release`` (``BenchEngine.release_slots``) and is counted here
until the harness opens a span of its own around it (``bench:kv_read``):
one gap of 13-31 ms a run on the v5e, 10-18% of the reading."""
from bench import program_trace


def read(run):
    program = program_trace.of_run(run)
    if not program:
        return None
    lo, hi = run.trace_window
    ops = run.trace.ops[:run.n_devices]
    idle = sum(program_trace.host_bound_idle_s(o, run.trace.spans, program,
                                               lo, hi) for o in ops)
    return 100.0 * idle / len(ops) / (hi - lo)
