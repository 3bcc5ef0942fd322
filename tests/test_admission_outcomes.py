"""Admission outcomes: at every segment boundary each queued request the
runtime considers is counted once on the boundary's ``admit`` span —
``admitted`` (by admission or by preemption) or the first gate that
refused it — and the executor says which of its gates refused
(``ContinuousExecutor.refusal``: slots, then headroom, then pages)."""
from __future__ import annotations

import pytest

from conftest import reduced_cfg
from repro.core.environment import paper_env
from repro.core.policy import Decision, SchedulerPolicy
from repro.core.request import Request, RequestGenerator
from repro.serving import runtime as runtime_mod
from repro.serving.kv_arena import KVArena
from repro.serving.runtime import (AnalyticContinuousExecutor,
                                   ContinuousRuntime,
                                   EngineContinuousExecutor)
from test_preemption import MENV, _two_pool_cexec

ENV = paper_env("bloom-3b", "W8A16")
GATES = {"admitted", "quarantined", "backoff", "deadline", "no_pool",
         "slots", "headroom", "pages", "infeasible"}


def _req(rid, n=8, tau=50.0):
    return Request(rid=rid, s=4, n=n, tau=tau, a=0.0, h=1.0)


@pytest.fixture(scope="module")
def eng():
    from repro.serving.engine import ServingEngine
    return ServingEngine(reduced_cfg("bloom-3b"), batch_capacity=2,
                         s_max=16, n_max=8, eos_id=-1)


def test_executor_names_the_gate_that_refuses(eng):
    """A live cohort at t > 0 against a request with n > n_max - t is a
    headroom refusal; a full pool is a slots refusal (checked first); a
    fresh cohort has the whole n_max; an unknown model has no pool."""
    ex = EngineContinuousExecutor(eng, seed=0)
    ex.bind(ENV)
    assert ex.refusal(None, _req(0, n=8)) is None       # fresh cohort
    assert ex.refusal("other-model", _req(0)) == "no_pool"
    ex.place(None, _req(0, n=8))
    ex.step(ENV, 5)                                     # cohort at t = 5
    assert ex._pools[None]["t"] == 5
    assert ex.refusal(None, _req(1, n=8)) == "headroom"  # 8 > 8 - 5
    assert not ex.accepts(None, _req(1, n=8))
    assert ex.refusal(None, _req(1, n=3)) is None
    ex.place(None, _req(1, n=3))
    assert ex.refusal(None, _req(2, n=3)) == "slots"
    assert ex.refusal(None, _req(2, n=8)) == "slots"    # slots first


def test_starved_arena_is_a_pages_refusal(eng):
    """An arena too small for a second row's pages refuses on pages,
    while the pool has a slot and the headroom."""
    arena = KVArena.for_engines([eng], block_tokens=8, shrink=0.5)
    ex = EngineContinuousExecutor(eng, seed=0, arena=arena)
    ex.bind(ENV)
    ex.place(None, _req(0, n=8))
    ex.step(ENV, 1)
    r = _req(1, n=7)
    assert ex.free_slots(None) > 0 and ex._pools[None]["t"] == 1
    assert ex.refusal(None, r) == "pages"
    assert ex.arena_blocked(None, r) and not ex.accepts(None, r)


class _RejectAll(SchedulerPolicy):
    name = "reject-all-stub"

    def schedule(self, env, queue):
        return Decision.single([])

    def validate(self, env, decision):
        return not decision.selected


class _Recording(ContinuousRuntime):
    """Records, at every boundary, the size of the queue ``_try_admit``
    considered and how many it admitted itself."""

    def _try_admit(self, queue, trace, degraded=False):
        admitted, refused = super()._try_admit(queue, trace, degraded)
        assert set(refused) == {r.rid for r in queue} - \
            {r.rid for r in admitted}
        self.seen.append((len(queue), len(admitted)))
        return admitted, refused


class _Span:
    def __init__(self, name, args):
        self.name, self.args = name, args

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def spans(monkeypatch):
    """Stands in for the runtime's ``span``: keeps every span it opens,
    with the arguments it was opened or tagged with."""
    seen = []

    def span(name, **args):
        seen.append(_Span(name, dict(args)))
        return seen[-1]

    monkeypatch.setattr(runtime_mod, "span", span)
    return seen


def _run(executor, policy="dftsp", gen=None, env=ENV, run_kw=None, **kw):
    rt = _Recording(env, policy, executor, **kw)
    rt.seen = []
    m = rt.run(gen=gen or RequestGenerator(rate=8, seed=0, lengths=(4, 8)),
               n_epochs=3, warmup_epochs=0, **(run_kw or {}))
    return rt, m


def _assert_conserved(rt, m, spans):
    """Each boundary's ``admit`` span counts every request ``_try_admit``
    considered once, and the ``admitted`` counts add up to every
    admission of the run, preemption's included.  Returns the counts
    summed over the run and the boundaries at which preemption admitted
    a request admission had refused."""
    admits = [s.args for s in spans if s.name == "admit"]
    assert len(admits) == len(rt.seen)
    total, preempted_in = {}, 0
    for (considered, by_admission), outcomes in zip(rt.seen, admits):
        assert set(outcomes) <= GATES
        assert all(v > 0 for v in outcomes.values())
        assert sum(outcomes.values()) == considered
        assert outcomes.get("admitted", 0) >= by_admission
        preempted_in += outcomes.get("admitted", 0) > by_admission
        for k, v in outcomes.items():
            total[k] = total.get(k, 0) + v
    assert total.get("admitted", 0) == sum(m.batch_sizes)
    return total, preempted_in


@pytest.mark.parametrize("case", ["slots", "infeasible", "deadline"])
def test_outcomes_are_conserved_at_every_boundary(case, spans):
    kw = {}
    policy = "dftsp"
    if case == "infeasible":
        policy = _RejectAll()
    if case == "deadline":
        kw = dict(admission="edf", deadline_gated=True)
    gen = RequestGenerator(rate=8, seed=0, lengths=(128, 256, 512),
                           tau_range=(0.05, 2.0))
    rt, m = _run(AnalyticContinuousExecutor(capacity=2), policy, gen=gen,
                 k=64, **kw)
    total, preempted_in = _assert_conserved(rt, m, spans)
    assert total.get(case, 0) > 0 and preempted_in == 0
    if case == "infeasible":
        assert "admitted" not in total and m.served == 0


def test_engine_run_counts_headroom_and_pages(eng, spans):
    """Through the engine path with a starved arena, rows arriving at a
    live cohort are turned away by headroom or pages, and every boundary
    still conserves."""
    arena = KVArena.for_engines([eng], block_tokens=8, shrink=0.5)
    ex = EngineContinuousExecutor(eng, seed=0, arena=arena)
    rt, m = _run(ex, k=2, gen=RequestGenerator(rate=12, seed=1,
                                               lengths=(4, 8),
                                               tau_range=(20.0, 40.0)))
    total, _ = _assert_conserved(rt, m, spans)
    assert total["admitted"] > 0
    assert total.get("headroom", 0) > 0
    assert total.get("pages", 0) + total.get("slots", 0) > 0


def test_preemption_admissions_leave_their_refusal(spans):
    """A request admission refused for slots and preemption then
    admitted at the same boundary counts once, as admitted."""
    gen = RequestGenerator(rate=30, seed=0, tau_range=(0.5, 6.0),
                           priorities=(0, 1, 2))
    rt, m = _run(AnalyticContinuousExecutor(capacity=4), gen=gen, k=64,
                 preemption=True)
    total, preempted_in = _assert_conserved(rt, m, spans)
    assert m.preempted > 0 and preempted_in > 0
    assert total.get("slots", 0) > 0


def test_cross_pool_preemption_admissions_are_conserved(spans):
    """The same on the engine path, where the shared arena's pages bind
    and victims come from the other pool."""
    cexec, _ = _two_pool_cexec()

    def tagger(arrivals):
        for i, r in enumerate(arrivals):
            r.model_id = "bloom-3b" if i % 2 == 0 else "bloom-7b1"
        return arrivals

    rt, m = _run(cexec, "multi-dftsp", env=MENV, k=2, preemption=True,
                 max_preemptions=2, backoff_boundaries=1,
                 gen=RequestGenerator(rate=10, seed=3, lengths=(4, 8),
                                      tau_range=(0.5, 8.0),
                                      priorities=(0, 1, 2)),
                 run_kw=dict(tag_arrivals=tagger))
    total, preempted_in = _assert_conserved(rt, m, spans)
    assert m.preempted > 0 and preempted_in > 0
    assert total["admitted"] > 0
