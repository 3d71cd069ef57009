#!/usr/bin/env python3
"""Find the highest open-loop rate a serving cell sustains (its knee).

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 4,8,12 \\
        --seconds 30 --seed 7

One engine, built and warmed once, serves the cell's mix at each rate in
turn. A rate is sustained when the backlog (requests due and not yet
finished) at the window's end is no longer than at its middle and every
due request finishes in the drain. Prints one JSON line per rate. The
cell's traffic file then fixes its rate as a number; the benchmark's own
runs never search for one.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def backlog(out, t0: float, t: float) -> int:
    """Requests due by ``t`` (window time) and not finished by then."""
    n = 0
    for r, h, _ in out:
        q = h._req
        done = q.finished_at and q.finished_at - t0 <= t
        if r["due"] <= t and not done:
            n += 1
    return n


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    w = harness.cell(harness.load_spec(), args.workload)
    harness.require_chip(w["chips"])
    harness.use_compile_cache()
    sv = harness.driver(w["mix"]["kind"])
    cfg, mix = w["cfg"], w["mix"]
    vocab = cfg["model"]["vocab_size"]
    engine = sv.build(cfg, mix, args.seed)
    sv.warm(engine, mix, vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        reqs = sv.schedule(m, args.seconds, args.seed, vocab)
        prof = harness.Profiler(None, 0, 0)
        t0, window_s, out, i = sv.open_loop(engine, reqs, args.seconds, prof)
        sv.drain(engine, reqs, out, i, t0, mix["drain_s"])
        end = harness.now()
        ttft, tpot, failed = sv.latencies(out, t0, end)
        mid, last = (backlog(out, t0, args.seconds / 2),
                     backlog(out, t0, args.seconds))
        print(json.dumps({
            "rate_rps": rate, "requests": len(out), "failed": failed,
            "backlog_mid": mid, "backlog_end": last,
            "sustained": failed == 0 and last <= mid,
            "ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p95_ms": harness.percentile(ttft, 95),
            "tpot_p50_ms": harness.percentile(tpot, 50),
            "tpot_p95_ms": harness.percentile(tpot, 95),
            "drain_s": end - t0 - window_s}), flush=True)
        while engine.busy:          # a rate past the knee leaves work behind
            engine.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
