"""The trace reduction, on a small trace recorded on one TPU v5e
(``testdata/small_trace.xplane.pb``: two steps, each a small jitted
program and one W8A16 ``quant_matmul`` kernel call, inside a ``window``
span), and on hand-made intervals."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import flops, trace  # noqa: E402

SMALL = Path(__file__).resolve().parent / "testdata" / "small_trace.xplane.pb"
QMM = r"^%quant_matmul(\.\d+)? = "


@pytest.fixture(scope="module")
def small():
    return trace.load(str(SMALL))


def test_planes_lines_and_spans(small):
    assert small.n_devices == 1
    assert [e.name.split("(")[0] for e in small.modules[0]] == \
        ["jit_prog", "jit__lambda", "jit_prog", "jit__lambda"]
    assert len(small.ops[0]) == 16
    assert [e.name for e in small.spans] == ["window", "step", "step"]
    lo, hi = trace.window(small, "window")
    assert hi - lo == pytest.approx(3.400891e-3)


def test_kernel_time(small):
    n, total = trace.totals(small.ops[0], QMM)
    assert n == 2 and total == pytest.approx(2 * 532e-9)
    n, total = trace.totals(small.modules[0], r"^jit__lambda\(")
    assert n == 2 and total == pytest.approx((870 + 864) * 1e-9)


def test_kernel_calls_priced_from_the_trace(small):
    calls = [flops.quant_matmul_from_hlo(e.name)
             for e in trace.matching(small.ops[0], QMM)]
    # x (8, 256) bf16 @ int8 (256, 256) with f32 scales, bf16 out
    assert calls == [(2 * 8 * 256 * 256,
                      256 * 256 + 4 * 256 + 2 * 8 * 256 + 2 * 8 * 256,
                      False)] * 2


def test_busy_and_idle(small):
    ops = small.ops[0]
    # the operations of this trace do not overlap: busy is their sum
    assert trace.busy_s(ops, 0.0, 1.0) == \
        pytest.approx(sum(e.dur_s for e in ops))
    lo, hi = 0.0478, 0.0481
    gaps = trace.idle_gaps(ops, lo, hi)
    assert gaps[0] == pytest.approx((0.0478, 0.047882282))
    idle = sum(b - a for a, b in gaps)
    assert idle + trace.busy_s(ops, lo, hi) == pytest.approx(hi - lo)


def test_short_names_and_containers(small):
    names = [trace.short_name(e.name) for e in small.ops[0]]
    assert names[:3] == ["copy-start tuple", "copy-done bf16[8,256]",
                         "fusion bf16[]"]
    assert "quant_matmul.1 bf16[8,256]" in names
    assert not any(trace.is_container(e.name) for e in small.ops[0])
    assert trace.is_container(
        "%while.52 = (bf16[3]{0}, s32[]{:T(128)}) while((bf16[3]{0}, "
        "s32[]) %t), condition=%c, body=%b")


def test_union_clip_and_gaps_by_hand():
    ev = [trace.Event("a", 0.0, 2.0), trace.Event("b", 1.0, 2.0),
          trace.Event("c", 5.0, 1.0)]
    assert trace.union([(e.start_s, e.end_s) for e in ev]) == \
        [(0.0, 3.0), (5.0, 6.0)]
    assert trace.busy_s(ev, 1.0, 5.5) == pytest.approx(2.5)
    assert trace.idle_gaps(ev, -1.0, 7.0) == \
        [(-1.0, 0.0), (3.0, 5.0), (6.0, 7.0)]
    assert trace.by_name(ev + [trace.Event("c", 9.0, 4.0)]) == \
        {"c": 5.0, "a": 2.0, "b": 2.0}


def test_host_doing_picks_the_innermost_span():
    tr = trace.Trace(spans=[trace.Event("window", 0.0, 10.0),
                            trace.Event("step", 1.0, 3.0),
                            trace.Event("poll", 3.0, 0.5)])
    assert trace.host_doing(tr, 3.2) == "poll"
    assert trace.host_doing(tr, 2.0, skip=("window",)) == "step"
    assert trace.host_doing(tr, 6.0, skip=("window",)) == "outside"
