"""Device time by named scope (``scopes.py``): the compiled text's parser,
the attribution of a trace's ops, the guard against an unmapped trace, and
the four readers end to end on the tiny train cell's compiled names."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import synthetic_trace, tiny  # noqa: E402

import harness  # noqa: E402
import scopes  # noqa: E402
from trace_reduce import CONTAINERS, reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "probe.xplane.pb"
READERS = {"asi_upkeep_share.train": ("wasi.asi_upkeep",),
           "lowrank_share.train": ("wasi.matmul", "wasi.grad"),
           "attention_share.train": ("attn.core",),
           "head_share.train": ("lm.embed", "lm.head", "lm.xent")}


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setattr(scopes, "_last", {})


def test_scopes_are_the_programs():
    """Every scope the program sets is read: it is in the base list or in
    some configuration file's ``scopes``; and every base scope is one the
    program sets."""
    import json

    from repro.utils import scopes as program

    declared = set(scopes.SCOPES)
    for f in (harness.HERE / "configs").glob("*.json"):
        declared |= set(json.loads(f.read_text()).get("scopes", ()))
    assert set(program.ALL) <= declared
    assert set(scopes.SCOPES) <= set(program.ALL)


def test_parser_gives_the_innermost_scope():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("attn.core"):
            y = jnp.exp(x)
            with jax.named_scope("wasi.matmul"):
                y = y @ y.T
        return jnp.tanh(y).sum()

    text = jax.jit(jax.grad(f)).lower(jnp.ones((4, 4))).compile().as_text()
    names = scopes.names_of(text)
    assert names and all(n and " " not in n for n in names)
    by_op = {}
    for path in names.values():
        by_op.setdefault(path.rsplit("/", 1)[-1], set()).add(
            scopes.scope_of(path))
    assert by_op["exp"] == {"attn.core"}
    assert by_op["dot_general"] == {"wasi.matmul"}
    assert by_op["tanh"] == {"other"}
    assert scopes.scope_of("jit(step)/transpose(jvp(lm.head))/dot_general") \
        == "lm.head"
    assert scopes.scope_of("a/wasi.grad/b/wasi.asi_upkeep/c") \
        == "wasi.asi_upkeep"
    assert scopes.scope_of("") == "other"


def test_attribution_on_the_recorded_trace():
    t = reduce(str(FIXTURE))
    program = r"^jit_decode_probe\("
    names = {"lowrank_matmul_fused.1": "jit(p)/wasi.matmul/custom-call",
             "convert_reduce_fusion": "jit(p)/attn.core/reduce_sum",
             "copy": "jit(p)/copy", "copy.1": ""}
    # cut the window inside the first program, so clipping counts
    first = min(t.modules, key=lambda m: m.start)
    t.window = (first.start + (first.end - first.start) // 2, t.window[1])
    got = scopes.attribute(t, names, program)
    lo, hi = t.window
    want = {}
    for o in t.ops_in("", program):
        if o.name.split(" ")[1:2] in CONTAINERS:
            continue
        name = o.label.split(" = ")[0].lstrip("%")
        b = scopes.scope_of(names[name]) if name in names else "unmapped"
        want[b] = want.get(b, 0.0) + max(0, min(o.end, hi)
                                         - max(o.start, lo)) * 1e-9
    clipped = sum(max(0, min(o.end, hi) - max(o.start, lo))
                  for o in t.ops_in("", program)) * 1e-9
    assert sum(got.values()) == pytest.approx(clipped)
    assert sum(got.values()) < t.op_time("", program)[0]
    for b in set(got) | set(want):
        assert got[b] == pytest.approx(want.get(b, 0.0)), b
    assert got["wasi.matmul"] > 0 and got["attn.core"] > 0
    assert got["other"] > 0 and got["unmapped"] > 0


def test_reader_is_silent_when_more_than_1pct_is_unmapped(monkeypatch):
    names = {f"fusion.{i}": "jit(step)/jvp(step)/attn.core/exp"
             for i in range(100)}
    monkeypatch.setattr(scopes, "step_map",
                        lambda cfg, mix: (names, {"compile_s": 0.0}))
    reader = _reader("attention_share.train")
    ctx = {"trace": synthetic_trace(names, extra=1), "cfg": {}, "mix": {}}
    assert reader.read(ctx) == pytest.approx(100 * 100 / 101)
    ctx = {"trace": synthetic_trace(names, extra=2), "cfg": {}, "mix": {}}
    assert reader.read(ctx) is None


def test_reader_is_silent_on_a_program_without_scopes(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.utils.scopes", None)
    monkeypatch.setattr(scopes, "step_map", lambda cfg, mix: 1 / 0)
    t = synthetic_trace({"fusion.1": ""})
    for name in READERS:
        assert _reader(name).read({"trace": t, "cfg": {}, "mix": {}}) is None


def test_readers_on_the_tiny_cells_compiled_names(capsys):
    w = tiny("qwen2-train-wasi")
    names, info = scopes.step_map(w["cfg"], w["mix"])
    assert info["instructions"] == len(names) > 1000
    assert not info["recompiled"]
    t = synthetic_trace(names)
    ctx = {"trace": t, "cfg": w["cfg"], "mix": w["mix"], "cell": w}
    count = {}
    for path in names.values():
        b = scopes.scope_of(path)
        count[b] = count.get(b, 0) + 1
    assert all(count.get(s) for s in scopes.SCOPES)
    for name, buckets in READERS.items():
        got = _reader(name).read(ctx)
        assert got == pytest.approx(
            100 * sum(count[b] for b in buckets) / len(names)), name
        assert 0 < got < 100
    table = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("scope_shares ")]
    assert len(table) == 1
    import json

    row = json.loads(table[0].split(" ", 1)[1])
    assert set(row["percent"]) == set(scopes.SCOPES) | {"other", "unmapped"}
    assert sum(row["percent"].values()) == pytest.approx(100)
    assert row["seconds"]["unmapped"] == 0
    assert row["total_s"] == pytest.approx(len(names) * 10e-9)


def test_map_recompiles_when_the_cached_executable_has_no_scopes(
        monkeypatch):
    """The persistent cache's key leaves metadata out, so a shared cache
    can hand back the executable of a build without scopes: the map is
    then built from a fresh compile, and the cache is switched back on."""
    import jax
    from types import SimpleNamespace

    texts = iter(['  %add.1 = f32[] add(), metadata={op_name="jit(step)/add"}',
                  '  ROOT %add.1 = f32[] add(), '
                  'metadata={op_name="jit(step)/attn.core/add"}'])
    caches = []

    def lowered(cfg, mix):
        caches.append(jax.config.jax_enable_compilation_cache)
        text = next(texts)
        return SimpleNamespace(compile=lambda: SimpleNamespace(
            as_text=lambda: text))

    monkeypatch.setattr(scopes, "lowered_step", lowered)
    monkeypatch.setattr(scopes, "_maps", {})
    names, info = scopes.step_map({"cell": "test"}, {})
    assert names == {"add.1": "jit(step)/attn.core/add"}
    assert info["recompiled"] and not info["cache_hit"]
    assert caches == [True, False]
    assert jax.config.jax_enable_compilation_cache
    assert scopes.step_map({"cell": "test"}, {}) == (names, info)
