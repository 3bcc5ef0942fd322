"""Share of its roofline the ``quant_matmul`` kernel
(``kernels/quant_matmul.py``) reached over the traced window (%): the
least time the chip could take for every execution in the trace, priced
from that execution's own operand shapes (``bench/flops.py``), over the
kernel's device time."""
from bench import flops, trace

KERNEL = r"^%quant_matmul(\.\d+)? = "


def read(run):
    if run.trace is None or run.trace_window is None or not run.trace.ops:
        return None
    lo, hi = run.trace_window
    pk = run.peaks
    floor_s, total = 0.0, 0.0
    for e in trace.matching(trace.within(run.trace.ops[0], lo, hi), KERNEL):
        ops, nbytes, int8 = flops.quant_matmul_from_hlo(e.name)
        peak = pk["int8_ops"] if int8 else pk["bf16_flops"]
        floor_s += flops.roofline_s(ops, nbytes, peak, pk["hbm_bytes_per_s"])
        total += e.dur_s
    return 100.0 * floor_s / total if total else None
