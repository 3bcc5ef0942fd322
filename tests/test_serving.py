"""Serving engine + end-to-end DFTSP-driven serving.

Includes the decode-loop contract tests: the fused device-resident
``lax.while_loop`` path (``generate``) must match the legacy host-driven
loop (``generate_reference``) bit for bit, with exactly ONE host→device
and ONE device→host transfer per batch.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.environment import paper_env
from repro.core.request import RequestGenerator
from repro.serving.engine import ServingEngine
from repro.serving.runtime import EngineExecutor, EpochRuntime


def assert_same_generation(a, b):
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    assert a.batch == b.batch


@pytest.fixture(scope="module")
def engine():
    cfg = reduced_cfg("bloom-3b")
    return ServingEngine(cfg, batch_capacity=4, s_max=32, n_max=8)


def test_generate_shapes(engine):
    res = engine.generate([[1, 2, 3], [4, 5, 6, 7]], n_tokens=[5, 8])
    assert res.tokens.shape == (2, 8)
    assert res.lengths[0] <= 5 and res.lengths[1] <= 8
    assert res.batch == 2


def test_generate_respects_caps(engine):
    res = engine.generate([[1, 2, 3]], n_tokens=[3])
    assert res.lengths[0] <= 3
    assert np.all(res.tokens[0, 3:] == 0)


def test_generate_deterministic(engine):
    a = engine.generate([[5, 6, 7]], n_tokens=[6])
    b = engine.generate([[5, 6, 7]], n_tokens=[6])
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_quantized_engine_runs():
    cfg = reduced_cfg("bloom-3b")
    eng = ServingEngine(cfg, batch_capacity=2, s_max=16, n_max=4,
                        quant_bits=8)
    res = eng.generate([[1, 2, 3]], n_tokens=[4])
    assert res.tokens.shape == (1, 4)


def test_pad_prompts_right_aligned(engine):
    out = engine.pad_prompts([[7, 8, 9]])
    assert out.shape == (4, 32)
    assert list(out[0, -3:]) == [7, 8, 9]
    assert out[0, :-3].sum() == 0


def test_engine_runtime_end_to_end(engine):
    env = paper_env("bloom-3b", "W8A16")
    trace = EpochRuntime(env, "dftsp", EngineExecutor(engine, seed=0)).run(
        rate=5, n_epochs=3, seed=0, warmup_epochs=0)
    assert trace.epochs == 3
    assert trace.served >= 0
    assert len(trace.batches) == 3
    # real data plane => per-epoch wall-clock is measured and aggregated
    assert trace.wall_s > 0
    assert trace.wall_s == pytest.approx(
        sum(t.wall_s for t in trace.traces if t.counted))
    if trace.generated_tokens:
        assert trace.tokens_per_s > 0
        assert any(t.tokens_per_s > 0 for t in trace.traces)


# -- fused decode-loop contract ---------------------------------------------


def test_fused_matches_reference_edge_cases(engine):
    """cap=0 rows, pad-token prompts and padding-only rows (fewer prompts
    than batch_capacity) all decode bit-identically to the legacy loop."""
    prompts = [[1, 2, 3], [0, 0], [7]]       # slot 4 stays padding-only
    caps = [5, 0, 8]
    a = engine.generate(prompts, n_tokens=caps)
    b = engine.generate_reference(prompts, n_tokens=caps)
    assert_same_generation(a, b)
    assert a.lengths[1] == 0                 # cap=0 row emits nothing
    assert np.all(a.tokens[1] == 0)


def test_fused_matches_reference_empty_batch(engine):
    a = engine.generate([], n_tokens=[])
    b = engine.generate_reference([], n_tokens=[])
    assert_same_generation(a, b)
    assert a.tokens.shape == (0, engine.n_max)


@pytest.mark.parametrize("bits", [0, 8, 4])
def test_fused_matches_reference_all_precisions(engine, bits):
    """Equivalence holds for every bit-width the engine caches — the
    quant_bits override routes both paths through the same weight tree."""
    prompts = [[5, 6, 7], [1, 2], [9, 9, 9, 9]]
    a = engine.generate(prompts, n_tokens=[8, 3, 6], quant_bits=bits)
    b = engine.generate_reference(prompts, n_tokens=[8, 3, 6],
                                  quant_bits=bits)
    assert_same_generation(a, b)
    assert a.lengths.max() >= 1


def test_fused_immediate_eos(engine):
    """A row whose FIRST sampled token is EOS emits exactly one token in
    both paths (the EOS itself, as the legacy loop always did)."""
    ref = engine.generate_reference([[9, 8, 7]], n_tokens=[6])
    tok0 = int(ref.tokens[0, 0])
    eng2 = ServingEngine(engine.cfg, params=engine._raw_params,
                         batch_capacity=4, s_max=32, n_max=8, eos_id=tok0)
    a = eng2.generate([[9, 8, 7]], n_tokens=[6])
    b = eng2.generate_reference([[9, 8, 7]], n_tokens=[6])
    assert_same_generation(a, b)
    assert a.lengths[0] == 1
    assert a.tokens[0, 0] == tok0
    assert np.all(a.tokens[0, 1:] == 0)


def test_fused_generate_single_host_sync(engine, monkeypatch):
    """The one-transfer-per-batch contract, probed at the real transfer
    points: fused generate makes exactly ONE device_put (prompts + caps)
    and ONE device_get (tokens + lengths); the reference loop pays one
    blocking device_get per decoded token on top."""
    counts = {"get": 0, "put": 0}
    real_get, real_put = jax.device_get, jax.device_put

    def counting_get(x):
        counts["get"] += 1
        return real_get(x)

    def counting_put(x):
        counts["put"] += 1
        return real_put(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(jax, "device_put", counting_put)

    engine.generate([[1, 2, 3], [4, 5, 6]], n_tokens=[5, 5])
    assert counts == {"get": 1, "put": 1}

    counts.update(get=0, put=0)
    ref = engine.generate_reference([[1, 2, 3], [4, 5, 6]], n_tokens=[5, 5])
    # first token + one argmax sync per decode step
    assert counts["get"] == 1 + int(ref.lengths.max())
    assert counts["get"] > 1


def test_params_for_caches_each_precision():
    cfg = reduced_cfg("bloom-3b")
    eng = ServingEngine(cfg, batch_capacity=2, s_max=16, n_max=4)
    p16 = eng.params_for(16)
    assert p16 is eng.params_for(0)          # 16 == full precision
    assert eng.params_for(8) is eng.params_for(8)      # quantized once
    assert set(eng._params_cache) == {0, 8}
    r8 = eng.generate([[1, 2, 3]], n_tokens=[4], quant_bits=8)
    r16 = eng.generate([[1, 2, 3]], n_tokens=[4], quant_bits=16)
    assert r8.tokens.shape == r16.tokens.shape == (1, 4)
    assert eng.precisions_served == {0, 8}


def test_engine_serves_decided_precisions_in_one_run():
    """quant=auto on a strict-accuracy workload mixes W16A16 and W8A16
    epochs; the engine must execute both precisions via the weight
    cache (acceptance criterion for quantization-as-control)."""
    cfg = reduced_cfg("bloom-3b")
    eng = ServingEngine(cfg, batch_capacity=8, s_max=16, n_max=4)
    env = paper_env("bloom-3b", "W8A16")
    gen = RequestGenerator(rate=30, seed=0, acc_range=(0.9, 1.0))
    m = EpochRuntime(env, "dftsp:quant=auto",
                     EngineExecutor(eng, seed=0)).run(
        n_epochs=8, seed=0, gen=gen, warmup_epochs=0)
    assert m.served > 0
    assert len(m.served_by_method) >= 2          # adaptive method mix
    assert len(eng.precisions_served) >= 2       # distinct weight bits
    assert set(eng.precisions_served) <= set(eng._params_cache)


def test_engine_from_quantized_tree_serves_only_that_precision():
    """A tree quantized ahead of time is served as is, with no fp master
    kept: same tokens as an engine that quantizes its fp master, and a
    request for any other precision is refused."""
    from repro.quant.ptq import quantize_tree
    base = ServingEngine(reduced_cfg("bloom-3b"), batch_capacity=2,
                         s_max=16, n_max=6, quant_bits=8)
    eng = ServingEngine(base.cfg, params=quantize_tree(base._raw_params, 8),
                        batch_capacity=2, s_max=16, n_max=6)
    assert eng._raw_params is None and eng.default_bits == 8
    prompts = [[5, 6, 7], [9, 10, 11, 12]]
    assert_same_generation(base.generate(prompts, n_tokens=[4, 6]),
                           eng.generate(prompts, n_tokens=[4, 6]))
    with pytest.raises(ValueError, match="no full-precision master"):
        eng.params_for(0)
    with pytest.raises(ValueError, match="quantized at"):
        ServingEngine(base.cfg, params=quantize_tree(base._raw_params, 8),
                      quant_bits=4)
