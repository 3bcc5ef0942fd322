"""Model FLOP utilisation of the whole serving step (%): the FLOPs the
window's work needed at its real prompt lengths, with no padding
(``bench/flops.py``: the prefill of each admitted prompt, and each
output token's decode step), over the window's length times the chips'
bfloat16 peak."""


def read(run):
    done = sum(p[3] for p in run.rec.polls if p[0] <= run.seconds) \
        + sum(p[1] for p in run.rec.prefills if p[0] <= run.seconds)
    peak = run.peaks["bf16_flops"] * run.n_devices
    return 100.0 * done / (run.seconds * peak)
