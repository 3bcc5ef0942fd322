"""The program's own spans in a trace (``bench/program_trace.py``): the
prefix the program uses, a v5e-recorded trace from before the program
had spans, hand-made intervals, and a CPU-recorded trace of the reduced
cell's traced run through the whole harness."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, program_trace, trace  # noqa: E402
from bench.test_bench_harness import PEAKS, SEED, tiny_root  # noqa: E402

SMALL = Path(__file__).resolve().parent / "testdata" / "small_trace.xplane.pb"
P = program_trace.PREFIX
NEW_METRICS = ("admit_host_ms", "admit_refusals_per_admit",
               "admit_headroom_refusal_share", "segment_launch_host_ms",
               "host_bound_idle_share")


def test_prefix_is_the_programs():
    from repro.core.metrics import SPAN_PREFIX
    assert P == SPAN_PREFIX


def test_trace_without_program_spans():
    assert program_trace.load(str(SMALL)) == []
    assert [e.name for e in trace.load(str(SMALL)).spans] == \
        ["window", "step", "step"]


def test_report_on_the_recorded_trace(tmp_path):
    """The report's reduction on a v5e trace with a window span and no
    program spans: all idle time is under the harness's spans or none."""
    import shutil
    shutil.copy(SMALL, tmp_path / "small.xplane.pb")
    got = program_trace.report(str(tmp_path))
    assert got["window_s"] == pytest.approx(3.400891e-3)
    assert got["admit_outcomes"] == {} and got["spans"] == {}
    assert not any(k.startswith(P) for k in got["idle_s_by_span"])
    assert sum(got["idle_s_by_span"].values()) == \
        pytest.approx(got["device_idle_s"])
    assert [n for n, _ in got["idle_gaps"]][:3] == ["step"] * 3
    assert got["segment_launch"]["launches"] == 0


def _hand_made():
    """A boundary holding the program's step, around the harness's step
    and its wait, and a segment launch holding its lease top-up inside
    the harness's step."""
    bench = [trace.Event("window", 0.0, 10.0), trace.Event("step", 1.0, 6.0),
             trace.Event("wait", 1.0, 2.0)]
    program = [program_trace.Span(P + "boundary", 0.5, 7.0, {"n": 0}),
               program_trace.Span(P + "step", 0.9, 6.2),
               program_trace.Span(P + "segment.launch", 4.0, 2.0),
               program_trace.Span(P + "segment.lease_topup", 4.0, 1.0)]
    ops = [trace.Event("op", 3.5, 0.7), trace.Event("op", 5.5, 3.5)]
    return bench, program, ops


def test_innermost_span_of_either_set_by_hand():
    bench, program, _ = _hand_made()
    parts = program_trace.innermost(bench, program, 0.0, 10.0)
    assert [(pytest.approx(a), pytest.approx(b), n) for a, b, n in parts] \
        == [(0.0, 0.5, "outside"), (0.5, 0.9, P + "boundary"),
            (0.9, 1.0, P + "step"), (1.0, 3.0, "wait"), (3.0, 4.0, "step"),
            (4.0, 5.0, P + "segment.lease_topup"),
            (5.0, 6.0, P + "segment.launch"),
            (6.0, 7.0, "step"), (7.0, 7.1, P + "step"),
            (7.1, 7.5, P + "boundary"), (7.5, 10.0, "outside")]


def test_host_bound_idle_by_hand():
    bench, program, ops = _hand_made()
    # idle: [0, 3.5], [4.2, 5.5], [9, 10]; under a program span:
    # [0.5, 1] and [4.2, 5.5]; under the wait, the harness's step and
    # no span at all: the rest
    assert program_trace.host_bound_idle_s(ops, bench, program, 0.0, 10.0) \
        == pytest.approx(0.5 + 1.3)
    assert program_trace.host_bound_idle_s(ops, bench, [], 0.0, 10.0) == 0
    by_span = program_trace.idle_by_span(ops, bench, program, 0.0, 10.0)
    assert by_span == pytest.approx({
        "outside": 0.5 + 1.0, P + "boundary": 0.4, P + "step": 0.1,
        "wait": 2.0, "step": 0.5, P + "segment.lease_topup": 0.8,
        P + "segment.launch": 0.5})
    gaps = program_trace.longest_gaps(ops, bench, program, 0.0, 10.0)
    assert [n for n, _ in gaps] == ["wait", P + "segment.lease_topup",
                                    "outside"]
    assert [s for _, s in gaps] == pytest.approx([3.5, 1.3, 1.0])


def test_launch_pairing_by_hand():
    launches = [program_trace.Span(P + "segment.launch", t, 0.1)
                for t in (1.0, 3.0)]
    ok = program_trace.launch_precedes_module(
        launches, [trace.Event("m", 1.5, 1.0), trace.Event("m", 3.2, 1.0)])
    assert ok["without_launch"] == ok["sharing_a_launch"] == 0
    assert ok["lag_s_min"] == pytest.approx(0.2)
    bad = program_trace.launch_precedes_module(
        launches, [trace.Event("m", 0.5, 1.0), trace.Event("m", 3.2, 1.0),
                   trace.Event("m", 3.5, 1.0)])
    assert bad["without_launch"] == 1 and bad["sharing_a_launch"] == 1


def test_outcomes_sum_over_admit_spans():
    admits = [program_trace.Span(P + "admit", 0.0, 1.0,
                                 {"admitted": 1, "headroom": 3}),
              program_trace.Span(P + "admit", 2.0, 1.0,
                                 {"slots": 1, "headroom": 1})]
    counts = program_trace.outcomes(admits)
    assert counts == {"admitted": 1, "headroom": 4, "slots": 1}
    assert program_trace.refusals(counts) == 5


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The reduced cell's traced run on the CPU, its run record and the
    program's spans of its trace."""
    root = tiny_root(tmp_path_factory.mktemp("checkout"))
    seen = {}
    line = harness.run_cell(
        "tiny.mix", SEED, 3.0, True, root=root, require_tpu=False,
        peaks=PEAKS, log=lambda *a: None,
        inspect=lambda result, *_: seen.update(result=result))
    path = trace.find(str(root / harness.TRACE_DIR))
    return line, seen["result"], program_trace.load(path)


def _inside(inner, outer):
    return outer.start_s <= inner.start_s and inner.end_s <= outer.end_s


def test_cpu_trace_holds_program_spans_with_args(traced):
    _, _, spans = traced
    names = {s.name for s in spans}
    assert {P + n for n in ("boundary", "admit", "step", "refill",
                            "release", "prefill.prepare", "segment.launch",
                            "segment.lease_topup", "poll.fetch")} <= names
    assert not {P + "segment", P + "poll"} & names
    ns = [s.args["n"] for s in spans if s.name == P + "boundary"]
    assert ns == sorted(set(ns))
    admits = [s for s in spans if s.name == P + "admit"]
    counts = program_trace.outcomes(admits)
    assert counts["admitted"] > 0
    assert set(counts) <= {"admitted", "quarantined", "backoff", "deadline",
                           "no_pool", "slots", "headroom", "pages",
                           "infeasible"}
    assert all(s.args["rows"] > 0 for s in spans if s.name == P + "refill")


def test_admit_and_step_nest_in_one_boundary_per_executor_step(traced):
    _, result, spans = traced
    boundaries = [s for s in spans if s.name == P + "boundary"]
    # the trace stops inside the executor step of the boundary that
    # finds the window over: that boundary is not recorded, its admit is
    for name in ("admit", "step"):
        inner = [s for s in spans if s.name == P + name
                 and s.end_s <= boundaries[-1].end_s]
        assert all(sum(_inside(s, b) for b in boundaries) == 1
                   for s in inner)
    steps = [s for s in spans if s.name == P + "step"]
    assert all(sum(_inside(s, b) for s in steps) == 1 for b in boundaries)
    # the harness's span around each executor step lies in one program
    # step: one boundary per executor step
    ex_steps = [e for e in result.trace.spans if e.name == "step"]
    assert len(ex_steps) == len(boundaries)
    assert all(sum(_inside(e, s) for s in steps) == 1 for e in ex_steps)
    for child, parent in (("segment.launch", "step"),
                          ("segment.lease_topup", "segment.launch"),
                          ("poll.fetch", "step"), ("release", "step")):
        outer = [s for s in spans if s.name == P + parent]
        assert all(any(_inside(s, o) for o in outer)
                   for s in spans if s.name == P + child
                   and result.trace_window[0] <= s.start_s)


def test_new_metrics_read_the_cpu_trace_but_report_only_a_device(traced):
    """Without the device's plane the new readers stay silent, as the
    device readers do; the reductions they make read the CPU trace."""
    line, result, spans = traced
    assert not set(NEW_METRICS) & set(line["metrics"])
    for name in NEW_METRICS:
        assert result.cell.reader(name).read(result) is None
    lo, hi = result.trace_window
    admits = [s for s in spans if s.name == P + "admit"
              and lo <= s.start_s <= hi]
    assert admits and all(s.dur_s > 0 for s in admits)
    ops = [trace.Event("op", lo, (hi - lo) / 2)]
    idle = program_trace.host_bound_idle_s(ops, result.trace.spans, spans,
                                           lo, hi)
    assert 0 < idle < (hi - lo) / 2
