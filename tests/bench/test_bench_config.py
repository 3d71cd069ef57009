"""A configuration brings what is particular to its model as data: its
layer pattern, sub-configurations, ``ModelConfig`` fields, draw rules,
scope names, layer parts and reference. A hybrid Mamba-2 + attention
configuration kept here, at a test's size, joins the harness without an
edit under ``benchmarks/chip/``; the qwen2 file builds as it always has."""
import copy
import math
import sys
from pathlib import Path
from types import ModuleType

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import synthetic_trace, tiny  # noqa: E402

import flops  # noqa: E402
import harness  # noqa: E402
import model  # noqa: E402
import scopes  # noqa: E402

H, SSM = 64, {"d_state": 16, "expand": 2, "d_conv": 4, "head_dim": 16,
              "chunk": 32}
DT = (0.001, 0.1)          # dt drawn log-uniformly in this range
A = (1.0, 16.0)            # -A drawn uniformly in this range


def hybrid() -> dict:
    """A train cell of a [mamba2, mamba2, dense] model at width 64: the
    tiny qwen2 cell's files, with the pattern, the SSM, Mamba's draw
    rules, a scope, the Mamba-2 layer's parts and a reference named."""
    w = tiny("qwen2-train-wasi", H)
    cfg = w["cfg"]
    cfg["name"] = "hybrid-test"
    cfg["model"]["num_hidden_layers"] = 3
    cfg["program"].update(
        groups=[{"pattern": ["mamba2", "mamba2", "dense"], "repeat": 1}],
        ssm=dict(SSM), parts={"mamba2": ["mamba2"]},
        fields={"rope_theta": 0.0})
    cfg["init"] = {
        "A_log": {"rule": "log_uniform", "range": list(A)},
        "dt_bias": {"rule": "inv_softplus_log_uniform", "range": list(DT)},
        "D": {"rule": "ones"}}
    cfg["scopes"] = ["ssm.scan"]
    cfg["reference"] = "hybrid_test"

    def site(i, o, r):
        return {"in": i, "out": o, "rank": r, "bias": False}

    # 2 * expand * H = 256 out of in_proj; 2 * d_state + H*expand/head_dim
    # = 40 out of bcdt_proj; ranks a quarter of the smaller side, aligned
    # up to 8, at least 8 (rank_frac 0.25, rank_align 8)
    cfg["plan"]["sites"].update({
        "ssm/in_proj": site(H, 256, 16), "ssm/bcdt_proj": site(H, 40, 16),
        "ssm/out_proj": site(128, H, 16)})
    return w


def test_hybrid_builds_with_its_pattern_and_ssm():
    w = hybrid()
    mcfg = model.model_config(w["cfg"])
    assert [g.pattern for g in mcfg.groups] == [("mamba2", "mamba2", "dense")]
    assert mcfg.ssm.head_dim == 16 and mcfg.ssm.chunk == 32
    assert mcfg.rope_theta == 0.0          # from program.fields: no RoPE
    plan = model.install_plan(w["cfg"], mcfg, batch=2, seq=64)
    assert plan.spec("ssm/in_proj").rank == 16
    t = model.params_template(w["cfg"], mcfg)
    assert t["groups"][0][0]["mixer"]["A_log"].shape == (1, 8)
    assert "attn" in t["groups"][0][2]


def test_hybrid_draws_by_its_init_rules():
    w = hybrid()
    mcfg = model.model_config(w["cfg"])
    p = model.draw_params(2**33 + 1, w["cfg"], mcfg)
    for layer in (0, 1):
        mx = p["groups"][0][layer]["mixer"]
        a = np.exp(np.asarray(mx["A_log"]))
        assert A[0] <= a.min() and a.max() <= A[1] and a.std() > 0
        dt = np.log1p(np.exp(np.asarray(mx["dt_bias"])))     # softplus
        assert DT[0] * 0.999 <= dt.min() and dt.max() <= DT[1] * 1.001
        assert np.all(np.asarray(mx["D"]) == 1)
    # a leaf no rule names keeps the default: L ~ N(0, 1/K)
    L = np.asarray(p["groups"][0][2]["attn"]["wq"]["L"])
    assert 0.5 < L.std() * math.sqrt(L.shape[-1]) < 2


def test_init_rule_is_the_longest_matching_path_tail():
    init = {"bias": {"rule": "zeros"}, "head/w": {"rule": "ones"},
            "w": {"rule": "normal", "std": 1.0}}
    assert model.rule_for(init, "blocks/ln1/bias") == init["bias"]
    assert model.rule_for(init, "head/w") == init["head/w"]
    assert model.rule_for(init, "patch/w") == init["w"]
    assert model.rule_for(init, "mixer/dt_bias") is None
    with pytest.raises(ValueError, match="unknown init rule"):
        model._by_rule(None, {"rule": "uniformish"}, (2,), np.float32)


def test_groups_must_sum_to_the_depth_and_fields_and_family_must_exist():
    w = hybrid()
    bad = copy.deepcopy(w["cfg"])
    bad["model"]["num_hidden_layers"] = 4
    with pytest.raises(ValueError, match="hold 3 layers"):
        model.model_config(bad)
    bad = copy.deepcopy(w["cfg"])
    bad["program"]["fields"] = {"rope_thetta": 0.0}
    with pytest.raises(ValueError, match="rope_thetta"):
        model.model_config(bad)
    bad = copy.deepcopy(w["cfg"])
    bad["program"]["family"] = "lmm"
    with pytest.raises(ValueError, match="unknown program.family 'lmm'"):
        model.params_template(bad, model.model_config(w["cfg"]))


def test_hybrid_lowers_through_the_driver_and_attributes_its_scopes(
        monkeypatch):
    """The program sets no ``ssm.*`` scope yet: the declared one is in the
    cell's table, reads 0 from the compiled step, and takes the time of
    an op whose path carries it."""
    monkeypatch.setattr(scopes, "_last", {})
    w = hybrid()
    table = scopes.table(w["cfg"])
    assert table == scopes.SCOPES + ("ssm.scan",)
    names, info = scopes.step_map(w["cfg"], w["mix"])
    assert info["instructions"] == len(names) > 1000
    counted = {}
    for path in names.values():
        b = scopes.scope_of(path, table)
        counted[b] = counted.get(b, 0) + 1
    for s in ("wasi.asi_upkeep", "wasi.matmul", "wasi.grad", "attn.core",
              "lm.head", "train.optimizer"):
        assert counted.get(s), s
    names = dict(names, **{"ssm_op.1": "jit(step)/jvp(ssm.scan)/dot",
                           "ssm_op.2": "jit(step)/ssm.scan/wasi.grad/dot"})
    t = synthetic_trace(names)
    secs = scopes.attribute(t, names, scopes.PROGRAM, scopes=table)
    assert secs["ssm.scan"] == pytest.approx(10e-9)
    assert secs["wasi.grad"] > 0 and secs["unmapped"] == 0
    monkeypatch.setattr(scopes, "step_map", lambda cfg, mix: (names, info))
    pct = scopes.shares({"trace": t, "cfg": w["cfg"], "mix": w["mix"]})
    assert pct["ssm.scan"] == pytest.approx(100 / len(names))


def test_hybrid_flops_match_hand_count():
    cfg = hybrid()["cfg"]
    ctx = 10.0
    attn = (2 * 16 * (64 + 64) + 2 * 8 * (64 + 32) * 2 + 2 * 16 * (64 + 64)
            + 2 * 2 * 4 * 16 * ctx)
    mlp = 2 * 16 * (64 + 128) * 2 + 2 * 16 * (128 + 64)
    # in_proj, bcdt_proj, out_proj; SSD at Q 32, N 16, H*P 128
    mamba = (2 * 16 * (64 + 256) + 2 * 16 * (64 + 40) + 2 * 16 * (128 + 64)
             + 2 * 32 * 16 + 2 * 32 * 128 + 4 * 128 * 16)
    head = 2 * 64 * 256
    assert flops.lm_token_flops(cfg, ctx) == 2 * mamba + attn + mlp + head
    # the configuration's parts are read: a kind the table lacks counts
    cfg["program"]["groups"][0]["pattern"][2] = "dense_twice"
    with pytest.raises(KeyError, match="dense_twice"):
        flops.lm_token_flops(cfg, ctx)
    cfg["program"]["parts"]["dense_twice"] = ["attention", "mlp", "mlp"]
    assert flops.lm_token_flops(cfg, ctx) == \
        2 * mamba + attn + 2 * mlp + head
    cfg["program"]["parts"]["dense_twice"] = ["attention", "expert"]
    with pytest.raises(KeyError, match="expert"):
        flops.lm_token_flops(cfg, ctx)


def test_hybrid_reference_is_the_module_its_file_names(monkeypatch):
    w = hybrid()
    stub = ModuleType("reference.hybrid_test")
    seen = {}

    def train_reference(prec, cfg, opt, params, states, batches):
        seen.update(prec=prec, cfg=cfg["name"], batches=len(batches))
        return {"losses": [0.0]}

    stub.train_reference = train_reference
    monkeypatch.setitem(sys.modules, "reference.hybrid_test", stub)
    tr = harness.driver("train")
    assert tr.reference(w["cfg"]) is stub
    assert tr.reference_numbers(w["cfg"], w["mix"], 7, prec="bf16") == \
        {"losses": [0.0]}
    assert seen == {"prec": "bf16", "cfg": "hybrid-test",
                    "batches": w["mix"]["check_steps"]}


def _model_config_as_built_before(cfg):
    """``model.model_config`` as it read the qwen2 file before the
    configuration keys existed."""
    from repro.config import (AsiConfig, LayerGroup, ModelConfig,
                              WasiConfig)

    m, prog, w = cfg["model"], cfg["program"], cfg["wasi"]
    wasi = WasiConfig(**{k: v for k, v in w.items() if k != "asi"},
                      asi=AsiConfig(**w["asi"]))
    return ModelConfig(
        name=cfg["name"], family=prog["family"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        head_dim=m["head_dim"], qkv_bias=m["attention_bias"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        mlp_act=prog["mlp_act"], norm=prog["norm"],
        groups=(LayerGroup(pattern=(prog["block"],),
                           repeat=m["num_hidden_layers"]),),
        wasi=wasi, dtype=prog["dtype"], remat=prog["remat"])


def test_qwen2_model_config_is_as_before():
    cfg = harness.cell(harness.load_spec(), "qwen2-train-wasi")["cfg"]
    assert "reference" not in cfg and "init" not in cfg
    assert model.model_config(cfg) == _model_config_as_built_before(cfg)


def test_qwen2_numbers_are_the_same_with_its_reference_named():
    w = tiny("qwen2-train-wasi")
    tr = harness.driver("train")
    named = dict(w["cfg"], reference="qwen2")
    a = tr.reference_numbers(w["cfg"], w["mix"], 2**32 + 3)
    b = tr.reference_numbers(named, w["mix"], 2**32 + 3)
    assert a["losses"] == b["losses"]
    for k in ("grad", "change"):
        assert a[k].keys() == b[k].keys()
        for leaf in a[k]:
            assert np.array_equal(a[k][leaf], b[k][leaf]), (k, leaf)
