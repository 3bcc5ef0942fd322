"""Seconds from the process's start to the first due request: weights,
engine, arena, compile (or compile-cache load) and warm-up."""


def read(run):
    return run.setup_s
