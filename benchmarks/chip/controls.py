#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers over
many seeds, the control's, and the planted faults', in one process.

    python3 benchmarks/chip/controls.py --workload <cell> --seeds 1,2,3 \\
        [--what program,control:fp8,unchanged,half_batch] [--seconds 10]

Training cells need no measured window: each seed drives the program's
step through the checked steps (with a fault planted where asked:
``unchanged``, a step that returns its state; ``half_batch``, a step
whose mean is over half the rows, the other half replaced by the first)
and compares it with the float32 reference; ``control:<fp8|bf16>`` puts
the reference computed in that precision (fp8: e4m3 forward, e5m2
backward, per-tensor scaled) in the program's place. Serving cells run a
short window at the cell's own load, then read the served tokens' widest
gap under the float32 reference and, for ``control``, the widest gap of
the tokens that the float8 reference puts first at the same positions.

The benchmark's own runs never run this. Prints one JSON line per seed
and reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def train_readings(w: dict, seed: int, what: list[str]) -> dict:
    import gc

    import jax

    cfg, mix = w["cfg"], w["mix"]
    tr = harness.driver(mix["kind"])
    out = {}
    ref = tr.reference_numbers(cfg, mix, seed)
    for kind in what:
        if kind.startswith("control"):
            # control:<precision>, the reference in the program's place
            got = tr.reference_numbers(cfg, mix, seed,
                                       prec=kind.partition(":")[2] or "fp8")
        else:
            fault = None if kind == "program" else kind
            _, state, step = tr.build(cfg, mix, seed, fault)
            pool = tr.draw_pool(seed, cfg, mix)
            state, got = tr.program_numbers(cfg, mix, seed, state, step, pool)
            jax.block_until_ready(state)
            del state, step, pool
            gc.collect()
        checks = tr.compare(got, ref, w["limits"], skip_share=mix["skip_share"])
        out[kind] = {c["name"]: c["value"] for c in checks}
    return out


def serve_readings(w: dict, seed: int, what: list[str], seconds: float):
    sv = harness.driver(w["mix"]["kind"])
    out = {}
    for kind in ("program", "alter_token"):
        if kind not in what:
            continue
        res = sv.run(w, seed, seconds, None, harness.now(),
                     fault=None if kind == "program" else kind)
        out[kind] = {c["name"]: c["value"] for c in res["checks"]}
        out[kind]["tpot_p95_ms"] = res["e2e"]["tpot_p95_ms"]
        if kind == "program" and "control" in what:
            gap, n = sv.reference_gaps(w["cfg"], seed, res["picked"],
                                       w["mix"]["engine"]["max_cache"],
                                       prec="fp8", control=True)
            out["control"] = {"served_logit_gap": gap, "tokens": n}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    w = harness.cell(harness.load_spec(), args.workload)
    harness.require_chip(w["chips"])
    harness.use_compile_cache()
    what = args.what.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if w["mix"]["kind"] == "train":
            r = train_readings(w, seed, what)
        else:
            r = serve_readings(w, seed, what, args.seconds)
        print(json.dumps({"seed": seed, "s": time.perf_counter() - t0, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
