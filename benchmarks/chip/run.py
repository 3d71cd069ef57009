#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout. The cell's traffic
file names its driver (``drivers/<kind>.py``), which builds the program's
system under test on weights drawn from ``--seed``, warms every shape the
cell uses (that is the set-up), measures for ``--seconds``, then checks
what the timed path produced against a plain reference. ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` runs the same window
under the JAX profiler and reports its per-layer metrics, each read by
``metrics/<name>.py`` from the trace and the counters.

Exits non-zero, printing no result, when JAX finds no accelerator, fewer
chips than the cell asks for, or a chip missing from ``peaks.json``. The
compared numbers and their limits are the last lines of stderr; the last
line of stdout is the JSON result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
# the TPU library writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402

TRACE_DIR = harness.ROOT / ".bench_trace"


def per_layer(w: dict, res: dict, trace) -> dict:
    """Every per-layer metric of the cell that its reader finds."""
    import flops

    ctx = {"cell": w, "cfg": w["cfg"], "mix": w["mix"], "result": res,
           "trace": trace, "peak": flops.peaks(res["device"]["kind"])}
    out = {}
    for m in w["per_layer"]:
        reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             w: dict | None = None, require_chip: bool = True,
             fault: str | None = None, t_start: float = T_START) -> dict:
    """One run of cell ``name`` (or of the cell ``w`` as ``harness.cell``
    returns it): the result line's dict and the checks."""
    import trace_reduce

    w = w or harness.cell(harness.load_spec(), name)
    if require_chip:
        device = harness.require_chip(w["chips"])
    else:
        device = harness.device_info(w["chips"])
    harness.use_compile_cache()
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    res = harness.driver(w["mix"]["kind"]).run(
        w, seed, seconds, trace_dir, t_start, fault=fault)
    res["device"] = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    out = {"correct": all(c["ok"] for c in res["checks"]),
           "attempted": res["attempted"], "failed": res["failed"],
           "device": res["device"]}
    if trace:
        t = trace_reduce.reduce(harness.xplane_file(trace_dir), w["chips"])
        out["metrics"] = per_layer(w, res, t)
        out["device"].update(busy_s=t.busy_s, window_s=t.window_s)
        out["breakdown"] = {"device_ops": t.top_ops(10),
                            "idle_gaps": t.top_gaps(10)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        out["metrics"] = {m["name"]: {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
                          for m in w["end_to_end"]}
    res["line"] = out
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"info": res.get("info"), "counters": res["counters"]},
                     default=str), file=sys.stderr)
    harness.emit(res["line"], res["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
