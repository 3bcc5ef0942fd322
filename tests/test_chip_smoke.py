"""The chip smoke's phases, rehearsed on the CPU at a reduced width with
the kernels interpreted, and its refusal to run anywhere but on a TPU
inside a checkout.  The full-width run is ``python chip_smoke.py`` on
the chip."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

from conftest import reduced_cfg
from repro.config import get_arch
from repro.launch import compile_cache
from repro.models.api import build_model

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

CFG = reduced_cfg("bloom-3b")             # 2 layers, d_model 128, dh 80
FUSED = get_arch("bloom-7b1").scaled(d_model=256, n_heads=4, n_kv_heads=4)
SERVE = dict(batch=8, s_max=32, n_max=16, k=4)


def _within(errs):
    assert errs and all(e <= tol for e, tol in errs.values()), errs


def test_quant_matmul_check_interpreted():
    _within(chip_smoke.check_quant_matmul([(8, 128, 512), (40, 512, 128)]))


def test_flash_decode_check_interpreted():
    _within(chip_smoke.check_flash_decode(2, CFG.n_heads, CFG.n_kv_heads,
                                          CFG.d_head, 64, 16))


def test_fused_decode_check_interpreted():
    _within(chip_smoke.check_fused_decode(FUSED, 2, 64, 16))


def test_serve_phases_agree_across_precisions():
    """fp, W8A16 and W8A16 through the decode kernels serve the same
    frozen stream: same admissions, conservation (asserted inside
    ``serve_phase``), and kernel logits within the smoke's tolerance."""
    params = jax.jit(build_model(CFG).init)(jax.random.key(0))
    traffic = chip_smoke.smoke_traffic(0)
    runs = {}
    for name, bits, uk in (("fp", 0, False), ("w8", 8, False),
                           ("w8k", 8, True)):
        summary, engine, arena, out = chip_smoke.serve_phase(
            CFG, params, bits, traffic, use_kernel=uk, **SERVE)
        runs[name] = (summary, out)
    assert runs["fp"][0]["requests_sent"] == len(traffic.requests)
    assert runs["fp"][0]["served"] >= 1
    assert runs["w8"][0] == runs["w8k"][0]
    report = chip_smoke.kernel_vs_xla_logits(engine, arena, 8)
    assert report["first_step_logits_err"] <= chip_smoke.LOGITS_TOL
    assert 0 <= chip_smoke.token_agreement(runs["w8k"][1],
                                           runs["w8"][1]) <= 1


def test_main_refuses_cpu_backend(capsys):
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""                       # no result line
    assert "TPU" in err


def test_main_refuses_outside_checkout(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(chip_smoke, "SRC", tmp_path / "src")
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "checkout" in err


def test_compile_cache_dir(monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins; unset, the cache is the
    checkout's fixed ``.jax_cache``."""
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.CACHE_ENV)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir() == str(root / ".jax_cache")
