"""Mean host time, in ms, between one executor step returning and the
next starting while the executor holds work: the runtime's admission
boundary (aging, ``validate()``, placement)."""


def read(run):
    st = run.window_steps()
    gaps = [b.start - a.end for a, b in zip(st, st[1:]) if a.busy and b.busy]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
