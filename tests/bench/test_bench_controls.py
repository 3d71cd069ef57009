"""The check that decides ``correct`` fails what it must, at a test's size
on the CPU: the harness runs a whole cell with its look for a chip skipped
and the timed path broken underneath, and the control (the reference in
float8) fails the committed limits."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import no_disk_cache, tiny, tiny_serve  # noqa: E402

import harness  # noqa: E402
import run  # noqa: E402


def _run(w, fault, monkeypatch):
    no_disk_cache(monkeypatch)
    res = run.run_cell(w["name"], 2**32 + 5, 0.3, False, w=w,
                       require_chip=False, fault=fault,
                       t_start=harness.now())
    return res["line"]["correct"], {c["name"]: c["value"]
                                    for c in res["checks"]}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_step_fault_is_not_correct(fault, monkeypatch):
    correct, _ = _run(tiny("qwen2-train-wasi"), fault, monkeypatch)
    assert not correct


def test_served_token_altered_is_not_correct(monkeypatch):
    correct, checks = _run(tiny_serve(), "alter_token", monkeypatch)
    assert not correct
    assert checks["requests_unfinished"] == 0


def test_train_control_fails_a_limit():
    w = tiny("qwen2-train-wasi")
    tr = harness.driver("train")
    ref = tr.reference_numbers(w["cfg"], w["mix"], 3)
    ctl = tr.reference_numbers(w["cfg"], w["mix"], 3, prec="fp8")
    checks = tr.compare(ctl, ref, w["limits"], skip_share=w["mix"]["skip_share"])
    assert not all(c["ok"] for c in checks)


def test_serve_control_fails_its_limit(monkeypatch):
    no_disk_cache(monkeypatch)
    # at the cell's logit scale: random weights give logits of deviation
    # 0.02 * sqrt(hidden), 0.6 at the cell's 896 and 0.16 at width 64
    w = tiny_serve(hidden=256)
    sv = harness.driver("serve_open_loop")
    res = sv.run(w, 9, 0.3, None, harness.now())
    gap, n = sv.reference_gaps(w["cfg"], 9, res["picked"],
                               w["mix"]["engine"]["max_cache"], prec="fp8",
                               control=True)
    assert n >= w["mix"]["check"]["min_tokens"]
    assert gap > w["limits"]["served_logit_gap"]
