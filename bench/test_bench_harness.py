"""CPU rehearsals of the harness: one cell at a reduced width through the
whole run and its last line, the refusals, a cell found from added
files alone, and the check failing the precision control, a token
altered where it is produced and a cache entry altered where it is
written.

On the CPU the serving program dequantizes its int8 weights at load
(its interpreted backend), so the run exercises the path, the records
and the check, and times nothing that means anything."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

SEED = 2 ** 31 + 77
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# the reduced cell: 2 layers of width 128, 16 rows of 32 prompt and 32
# output positions
TINY_CONFIG = {"n_layer": 2, "hidden_size": 128, "n_head": 2,
               "vocab_size": 1024,
               "serving": {"batch_capacity": 4, "s_max": 32, "n_max": 32,
                           "k": 8, "block_tokens": 16, "policy": "dftsp"}}
TINY_TRAFFIC = {"prompt_tokens": [8, 16, 32], "output_tokens": [8, 16, 32],
                "tau_s": [20.0, 40.0], "accuracy": [0.0, 0.1],
                "path_loss": 1e-3, "bursts": None}
# readings at this size (control test below): the program's widest gap
# stays under 0.01 of a logit and its keys and values within 0.014 of
# the reference's; the W4A16 control's read 0.2 and 0.4
TINY_LOAD = {"rate_per_s": 6.0, "drain_s": 30.0, "check_requests": 6,
             "max_logit_gap": 0.02, "kv_rel_err": 0.05}


def tiny_root(tmp: Path) -> Path:
    """A checkout holding the real harness plus one reduced cell,
    ``tiny.mix``, made of added files only."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "reduced bloom",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["n_layer", "hidden_size"],
                            "why": "CPU rehearsal"})
    spec["workloads"].append({"name": "tiny.mix", "config": "tiny",
                              "traffic": "tiny", "chips": 1,
                              "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.mix")
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    cfg = json.loads((ROOT / "bench/configs/bloom-3b-w8a16.json").read_text())
    cfg.update(TINY_CONFIG, name="tiny")
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (tmp / "bench/traffic/tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    (tmp / "bench/cells/tiny.mix.json").write_text(json.dumps(TINY_LOAD))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("checkout"))


def run(root, trace=False, **kw):
    return harness.run_cell("tiny.mix", SEED, 3.0, trace, root=root,
                            require_tpu=False, peaks=PEAKS,
                            log=lambda *a: None, **kw)


@pytest.fixture(scope="module")
def served(root):
    seen = {}

    def inspect(result, outputs, prompts):
        seen.update(result=result, outputs=outputs, prompts=prompts)

    seen["line"] = run(root, inspect=inspect)
    return seen


def test_tiny_cell_last_line(served):
    line = served["line"]
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check_info", "checks"]
    assert line["correct"] is True
    assert line["attempted"] == 18 and 0 <= line["failed"] < 18
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["platform"] == "cpu"
    assert set(line["checks"]) == {"max_logit_gap", "kv_rel_err"}
    for c in line["checks"].values():
        assert 0 <= c["value"] <= c["limit"]
    assert line["check_info"]["tokens"] >= 50
    assert 0 < line["check_info"]["kv_rel_err_first"] \
        <= line["checks"]["kv_rel_err"]["value"]


def test_tiny_cell_traced_reports_per_layer(root):
    line = run(root, trace=True)
    host = {"admit_wait_p95_ms", "boundary_host_ms", "batch_occupancy",
            "kv_pages_in_use_share", "mfu"}
    # the CPU trace has no device plane: the device metrics stay silent
    assert set(line["metrics"]) == host
    assert line["device"]["window_s"] > 0 and "breakdown" in line


def test_control_fails_the_limit(root, served):
    """The precision control: the program with its W4A16 tier switched on
    (the weights' next precision down) in place of W8A16, through the
    whole run and its check."""
    line = run(root, control="w4a16")
    assert line["correct"] is False
    for name in ("max_logit_gap", "kv_rel_err"):
        program = served["line"]["checks"][name]["value"]
        control = line["checks"][name]["value"]
        assert program <= TINY_LOAD[name] < control
        assert control >= 3 * program


def test_altered_token_is_incorrect(root):
    def alter(outputs):
        rid = max(outputs, key=lambda r: len(outputs[r]))
        out = np.array(outputs[rid])
        out[len(out) // 2] = (out[len(out) // 2] + 1) % 1024
        outputs[rid] = out

    line = run(root, tamper=alter)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        TINY_LOAD["max_logit_gap"]


def test_altered_cache_is_incorrect(root):
    """One value of one key, at the last prompt position of each row,
    altered in the cache where the program wrote it."""
    s_max = TINY_CONFIG["serving"]["s_max"]

    def alter(kv):
        for rid, got in kv.items():
            k = np.array(got["k"])
            k[0, s_max - 1, 0, 0] += 4.0
            kv[rid] = dict(got, k=k)

    line = run(root, tamper_kv=alter)
    assert line["correct"] is False
    assert line["checks"]["kv_rel_err"]["value"] > TINY_LOAD["kv_rel_err"]
    assert line["checks"]["max_logit_gap"]["value"] <= \
        TINY_LOAD["max_logit_gap"]


def test_refuses_without_a_tpu(capsys):
    from bench import run as run_mod
    rc = run_mod.main(["--workload", "bloom-3b.paper-mix", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_unknown_device_kind_is_an_error(root):
    with pytest.raises(harness.UnknownDevice):
        harness.peaks_for("TPU v99")
    with pytest.raises(harness.UnknownDevice):
        harness.run_cell("tiny.mix", SEED, 1.0, False, root=root,
                         require_tpu=False)


def test_added_cell_is_found_by_name(root):
    cell = harness.load_cell("tiny.mix", root)
    assert cell.config["n_layer"] == 2
    assert cell.traffic == TINY_TRAFFIC and cell.load == TINY_LOAD
    assert [m["name"] for m in cell.end_to_end] == \
        ["ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    assert "mfu" in [m["name"] for m in cell.per_layer]
    with pytest.raises(KeyError):
        harness.load_cell("tiny.other", root)


def test_no_result_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "bloom-3b.paper-mix", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
