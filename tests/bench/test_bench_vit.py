"""The ``vit-finetune-wasi`` cell at a test's size on the CPU: the
program's train step against ``reference/vit.py`` inside the committed
limits, the planted faults and the float8 control failing them, and its
device-time scopes read from the compiled step."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import no_disk_cache, synthetic_trace, tiny_vit  # noqa: E402

import flops  # noqa: E402
import harness  # noqa: E402
import model  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402


def _run(fault, monkeypatch):
    no_disk_cache(monkeypatch)
    w = tiny_vit()
    res = run.run_cell(w["name"], 2**32 + 9, 0.3, False, w=w,
                       require_chip=False, fault=fault,
                       t_start=harness.now())
    return res["line"]["correct"], {c["name"]: c for c in res["checks"]}


def test_vit_program_agrees_with_its_reference(monkeypatch):
    correct, checks = _run(None, monkeypatch)
    assert correct
    # float32 on the CPU on both sides: far inside the chip's limits
    for name in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        assert checks[name]["value"] < checks[name]["limit"] / 100, name


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_vit_step_fault_is_not_correct(fault, monkeypatch):
    correct, _ = _run(fault, monkeypatch)
    assert not correct


def test_vit_control_fails_a_limit():
    w = tiny_vit()
    tr = harness.driver("train")
    ref = tr.reference_numbers(w["cfg"], w["mix"], 5)
    ctl = tr.reference_numbers(w["cfg"], w["mix"], 5, prec="fp8")
    checks = tr.compare(ctl, ref, w["limits"], skip_share=w["mix"]["skip_share"])
    assert not all(c["ok"] for c in checks)


def test_vit_flops_match_hand_count():
    cfg = tiny_vit()["cfg"]
    # 16 patches of 8 x 8 x 3 = 192 values, 17 tokens, width 64, 4 heads
    # of 16, MLP 128 at rank 21 (project mode: the forward runs the factors)
    layer = (4 * 2 * 64 * 64 + 2 * 2 * 4 * 16 * 17
             + 2 * 21 * (64 + 128) * 2)
    image = 2 * 16 * 192 * 64 + 17 * 2 * layer + 2 * 64 * 10
    assert model.family(cfg).image_flops(cfg) == image
    assert flops.train_step_flops(cfg, 4, 17) == 3 * 4 * image


def test_vit_readers_on_the_compiled_names(monkeypatch):
    monkeypatch.setattr(scopes, "_last", {})
    w = tiny_vit()
    names, info = scopes.step_map(w["cfg"], w["mix"])
    assert not info["recompiled"]
    count = {}
    for path in names.values():
        b = scopes.scope_of(path, scopes.table(w["cfg"]))
        count[b] = count.get(b, 0) + 1
    for s in ("wasi.asi_upkeep", "wasi.matmul", "wasi.grad", "attn.core",
              "train.optimizer"):
        assert count.get(s), s
    # the ViT head carries no lm.* scope
    assert not any(count.get(s) for s in ("lm.embed", "lm.head", "lm.xent"))
    ctx = {"trace": synthetic_trace(names), "cfg": w["cfg"], "mix": w["mix"]}
    for name in ("attention_share.train", "asi_upkeep_share.train",
                 "lowrank_share.train"):
        reader = harness.load_module(harness.HERE / "metrics" / f"{name}.py")
        assert 0 < reader.read(ctx) < 100, name
