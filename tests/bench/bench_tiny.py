"""Shared by the benchmark's CPU tests: the benchmark's own directory on
the import path, and its cells cut to a size a test holds."""
import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))



def tiny_model(h: int) -> tuple[dict, dict]:
    """Sizes and pinned plan of a two-layer model of width ``h`` with the
    cell's shape: 4 query heads and 2 KV heads of h/4, MLP 2h, vocabulary
    256, ranks a quarter of each linear's smaller side."""
    m = dict(hidden_size=h, intermediate_size=2 * h, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=h // 4,
             vocab_size=256)
    kv = h // 2

    def site(i, o, bias=False):
        return {"in": i, "out": o, "rank": min(i, o) // 4, "bias": bias}

    plan = {"padded_vocab": 256, "sites": {
        "attn/wq": site(h, h, True), "attn/wk": site(h, kv, True),
        "attn/wv": site(h, kv, True), "attn/wo": site(h, h),
        "mlp/gate": site(h, 2 * h), "mlp/up": site(h, 2 * h),
        "mlp/down": site(2 * h, h)}}
    return m, plan


def _shrink(w: dict, hidden: int) -> dict:
    cfg = w["cfg"]
    sizes, cfg["plan"] = tiny_model(hidden)
    cfg["model"].update(sizes)
    cfg["wasi"]["rank_align"] = 8
    return w


def tiny(name: str, hidden: int = 64) -> dict:
    """Cell ``name`` as the harness reads it, at a test's size: the same
    files, with the model two layers of width ``hidden`` and the traffic
    cut to match. Limits stay as committed."""
    import harness

    w = _shrink(copy.deepcopy(harness.cell(harness.load_spec(), name)),
                hidden)
    w["mix"].update(batch=2, seq=64, pool=4)
    return w


def tiny_vit(hidden: int = 64) -> dict:
    """The ``vit-finetune-wasi`` cell as the harness reads it, at a test's
    size: two layers of width ``hidden`` with 4 heads and an MLP of 2h,
    32 x 32 images of 16 patches, 10 classes, 4 images a step; the rank
    of each MLP site a third of ``hidden`` (rank_frac 0.33). Limits stay
    as committed."""
    import harness

    w = copy.deepcopy(harness.cell(harness.load_spec(), "vit-finetune-wasi"))
    cfg, h, k = w["cfg"], hidden, round(0.33 * hidden)
    cfg["model"].update(hidden_size=h, intermediate_size=2 * h,
                        num_hidden_layers=2, num_attention_heads=4,
                        image_size=32, patch_size=8)
    cfg["program"]["n_classes"] = 10
    cfg["init"]["head/w"] = {"rule": "normal", "std": h ** -0.5}

    def site(i, o, rank=0, bias=False):
        return {"in": i, "out": o, "rank": rank, "bias": bias}

    cfg["plan"] = {"sites": {
        "attn/wq": site(h, h, bias=True), "attn/wk": site(h, h, bias=True),
        "attn/wv": site(h, h, bias=True), "attn/wo": site(h, h),
        "mlp/up": site(h, 2 * h, k), "mlp/down": site(2 * h, h, k)}}
    w["mix"].update(batch=4, seq=17, pool=4)
    return w


# An open-loop serving mix at a test's size. The benchmark holds no serving
# cell until its traffic rests on a published trace; these tests keep the
# serving driver and its check sound for the cell that will use them.
SERVE_MIX = {
    "kind": "serve_open_loop", "rate_rps": 20.0, "base_seed": 20241,
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.8, "min": 8,
               "max": 100},
    "output": {"dist": "lognormal", "median": 10, "sigma": 0.6, "min": 4,
               "max": 40},
    "engine": {"max_slots": 4, "page_size": 8, "prefill_chunk": 32,
               "max_cache": 160, "prefix_cache": True},
    "drain_s": 20, "trace_start_s": 20, "trace_seconds": 3,
    "check": {"sample": 6, "min_tokens": 10},
}
# the served-token limit that readings at qwen2-0.5b width on a v5e gave
# (PERF.md)
SERVE_LIMITS = {"served_logit_gap": 0.22}


def tiny_serve(hidden: int = 64) -> dict:
    """A one-chip serving cell of the qwen2-0.5b configuration at a test's
    size, as ``harness.cell`` would return it."""
    import json

    import harness

    spec = harness.load_spec()
    conf = {c["name"]: c for c in spec["configs"]}["qwen2-0.5b"]
    w = {"name": "serve-test", "config": "qwen2-0.5b", "chips": 1,
         "cfg": json.loads((harness.ROOT / conf["file"]).read_text()),
         "mix": copy.deepcopy(SERVE_MIX), "limits": dict(SERVE_LIMITS),
         "end_to_end": [], "per_layer": []}
    return _shrink(w, hidden)


def synthetic_trace(names: dict, extra: int = 0):
    """One ``jit_step`` program running one 10 ns op per mapped name, plus
    ``extra`` ops of names outside the map, a loop op over all of them, and
    an op of another program."""
    from trace_reduce import Event, Trace, op_key

    t = Trace(devices=1)
    ops, at = [], 100
    labels = [f"%{n} = f32[2]{{0}} fusion(f32[2]{{0}} %p)" for n in names]
    labels += [f"%stranger.{i} = f32[2]{{0}} add(f32[2]{{0}} %p)"
               for i in range(extra)]
    for lab in labels:
        ops.append(Event(op_key(lab), at, at + 10, lab))
        at += 10
    loop = "%while.9 = (f32[2]{0}) while((f32[2]{0}) %t), body=%b"
    ops.append(Event(op_key(loop), 100, at, loop))
    t.ops = ops + [Event("%x add", at + 20, at + 30, "%x = f32[] add()")]
    t.modules = [Event("jit_step(1)", 90, at + 5),
                 Event("jit_other(2)", at + 15, at + 35)]
    t.spans = [Event("bench.train_dispatch", 95, at + 40)]
    t.window = (95, at + 40)
    return t


def no_disk_cache(monkeypatch) -> None:
    """Keep JAX's persistent compile cache off in runs a test makes."""
    import harness

    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
