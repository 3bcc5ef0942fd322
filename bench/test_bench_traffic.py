"""The traffic generator: a frozen schedule per seed, the same work in
the same order on every seed with other prompt tokens, and bursts that
pack their factor."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import traffic  # noqa: E402

PAPER = {"prompt_tokens": [128, 256, 512], "output_tokens": [128, 256, 512],
         "tau_s": [0.5, 2.0], "accuracy": [0.0, 1.0], "path_loss": 1e-3,
         "bursts": None}
BURSTS = dict(PAPER, output_tokens=[128],
              bursts={"first_s": 3.0, "period_s": 10.0, "length_s": 2.0,
                      "factor": 3.0})
SEED = 2 ** 31 + 1234


def _key(sched):
    return [(d.rid, d.due_s, d.s, d.n, d.tau, d.a, d.h, d.prompt.tolist())
            for d in sched]


@pytest.mark.parametrize("mix", [PAPER, BURSTS], ids=["steady", "bursty"])
def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, 2.0, 30.0, SEED, vocab=1000)
    b = traffic.schedule(mix, 2.0, 30.0, SEED, vocab=1000)
    assert len(a) > 0 and _key(a) == _key(b)


@pytest.mark.parametrize("mix", [PAPER, BURSTS], ids=["steady", "bursty"])
def test_other_seed_other_schedule_same_work(mix):
    a = traffic.schedule(mix, 2.0, 30.0, SEED, vocab=1000)
    b = traffic.schedule(mix, 2.0, 30.0, SEED + 1, vocab=1000)
    # another seed: other prompts, token for token
    assert all(x.prompt.tolist() != y.prompt.tolist() for x, y in zip(a, b))
    # the same work, due at the same times, in the same order
    strip = lambda s: [k[:-1] for k in _key(s)]  # noqa: E731
    assert len(a) > 0 and strip(a) == strip(b)


def test_fixed_order_keeps_the_schedule_and_draws_the_tokens():
    a = traffic.schedule(PAPER, 2.0, 30.0, SEED, vocab=1000)
    b = traffic.schedule(PAPER, 2.0, 30.0, 7, vocab=1000)
    assert [(d.due_s, d.s, d.n) for d in a] == \
        [(d.due_s, d.s, d.n) for d in b]
    # the fixed order still mixes the levels: not sorted by length
    assert [d.s for d in a] != sorted(d.s for d in a)
    assert Counter(d.n for d in a) == {128: 20, 256: 20, 512: 20}
    gaps = np.diff([0.0] + [d.due_s for d in a])
    assert gaps.min() > 0 and gaps.max() > 3 * gaps.min()


def test_schedule_fits_window_and_marginals():
    sched = traffic.schedule(PAPER, 3.0, 51.0, SEED, vocab=250880)
    assert len(sched) == 153
    due = [d.due_s for d in sched]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 51.0
    assert Counter(d.s for d in sched) == {128: 51, 256: 51, 512: 51}
    assert all(len(d.prompt) == d.s for d in sched)
    assert all(1 <= d.prompt.min() and d.prompt.max() < 250880
               for d in sched)
    assert all(0.5 <= d.tau <= 2.0 and 0 <= d.a <= 1 for d in sched)


def test_bursts_pack_their_factor():
    sched = traffic.schedule(BURSTS, 1.0, 51.0, SEED, vocab=1000)
    # integrated rate: 51 s at 1/s plus 2 extra per 2-s burst (5 bursts)
    assert len(sched) == 51 + 5 * 2 * 2
    inside = sum(1 for d in sched
                 if d.due_s >= 3.0 and (d.due_s - 3.0) % 10.0 < 2.0)
    # 10 s of bursts at factor 3 against 41 s at 1: the arrivals per
    # second inside the bursts are well above twice those outside
    assert inside / 10.0 > 2 * (len(sched) - inside) / 41.0


def test_rejects_empty_rate():
    with pytest.raises(ValueError):
        traffic.schedule(PAPER, 0.0, 10.0, 1, vocab=100)
