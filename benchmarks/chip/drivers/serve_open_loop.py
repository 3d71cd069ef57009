"""Open-loop serving traffic: requests arrive on a schedule fixed in the
traffic file, whatever the engine does, and each is timed from when it
was due.

The schedule's sizes and gaps are drawn once from the file's own
``base_seed``: every ``--seed`` serves the same multiset of prompt
lengths, output lengths and inter-arrival gaps, in an order and with token
contents drawn from the seed. Sampling is greedy, with no end-of-sequence
token, so every request emits exactly its ``max_new`` tokens.

Set-up builds the program's ``ServeEngine`` (paged, chunked prefill) on
weights drawn from the seed and warms every program the mix can reach:
the decode step and one chunk-prefill per page-history bucket. The window
then submits each request when due and ticks the engine; after it closes
the engine drains what is due, up to ``drain_s`` seconds.

``correct`` compares a sample of the finished requests, drawn from the
seed and holding the longest, with the plain reference: for every served
token, the gap by which its reference logit lies below the reference's
best at that position.
"""
from __future__ import annotations

import gc
import time

import harness
import model


def pages_needed(n: int, page: int) -> int:
    return -(-n // page)


def lengths(spec: dict, n: int, rng):
    """``n`` lengths from a clipped lognormal (or uniform) description."""
    import numpy as np

    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list[dict]:
    """The requests due in ``[0, seconds)``: due time, prompt, max_new.

    ``rate_rps * seconds`` requests: Poisson gaps scaled to fill the window
    exactly, so every seed offers the same load."""
    import numpy as np

    base = np.random.default_rng(mix["base_seed"])
    n = max(1, round(mix["rate_rps"] * seconds))
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    plens = lengths(mix["prompt"], n, base)
    olens = lengths(mix["output"], n, base)
    rng = harness.np_rng(seed, 4)
    order = rng.permutation(n)
    g = gaps[rng.permutation(n)]
    due = np.cumsum(g) - g[0]
    return [{"due": float(due[j]),
             "prompt": rng.integers(0, vocab, int(plens[order[j]])).tolist(),
             "max_new": int(olens[order[j]])} for j in range(n)]


def warm_lengths(mix: dict) -> list[int]:
    """One prompt length per chunk-prefill history bucket the mix reaches
    (the engine pads each chunk's page table to a power of two)."""
    e = mix["engine"]
    chunk, page = e["prefill_chunk"], e["page_size"]
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    buckets = {}
    for n in range(lo, hi + 1):
        ends = list(range(chunk, n, chunk)) + [n]
        for end in ends:
            b = 1 << (pages_needed(end, page) - 1).bit_length()
            buckets.setdefault(b, n)
    return sorted(set(buckets.values()))


def build(cfg: dict, mix: dict, seed: int):
    from repro.serve import ServeEngine

    mcfg = model.model_config(cfg)
    plan = model.install_plan(cfg, mcfg)
    params = model.draw_params(seed, cfg, mcfg)
    e = mix["engine"]
    return ServeEngine(params, mcfg, plan=plan, max_slots=e["max_slots"],
                       max_cache=e["max_cache"], paged=True,
                       page_size=e["page_size"],
                       prefill_chunk=e["prefill_chunk"],
                       prefix_cache=e["prefix_cache"])


def warm(engine, mix: dict, vocab: int) -> None:
    import numpy as np

    rng = np.random.default_rng(0)
    for n in warm_lengths(mix):
        engine.submit(rng.integers(0, vocab, n).tolist(), max_new=2)
    engine.run()
    engine.reset_stats()


def open_loop(engine, reqs: list[dict], seconds: float, prof):
    """Submit each request when due and tick the engine until the window
    closes. Returns its start and length, (request, handle, submit
    lateness) triples, and the index of the next request not yet sent."""
    out = []
    i = 0
    t0 = harness.now()
    while True:
        if prof.poll(harness.now() - t0):
            prof.stats[prof.done] = dict(engine.stats)
        now = harness.now() - t0
        if now >= seconds:
            break
        while i < len(reqs) and reqs[i]["due"] <= now:
            with harness.span("bench.submit"):
                h = engine.submit(reqs[i]["prompt"],
                                  max_new=reqs[i]["max_new"])
            out.append((reqs[i], h, harness.now() - t0 - reqs[i]["due"]))
            i += 1
        if engine.busy:
            with harness.span("bench.engine_tick"):
                engine.step()
        else:
            nxt = reqs[i]["due"] if i < len(reqs) else seconds
            with harness.span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    window_s = harness.now() - t0
    if prof.on:
        prof.stop()
        prof.stats[1] = dict(engine.stats)
    return t0, window_s, out, i


def drain(engine, reqs, out, i, t0, drain_s):
    while i < len(reqs):
        with harness.span("bench.submit"):
            h = engine.submit(reqs[i]["prompt"], max_new=reqs[i]["max_new"])
        out.append((reqs[i], h, harness.now() - t0 - reqs[i]["due"]))
        i += 1
    stop = harness.now() + drain_s
    while engine.busy and harness.now() < stop:
        with harness.span("bench.drain"):
            engine.step()


def latencies(out, t0: float, end: float):
    """TTFT and TPOT of every due request, in ms; one that did not finish
    counts with the time until ``end`` (and as failed)."""
    ttft, tpot, failed = [], [], 0
    for r, h, _ in out:
        q = h._req
        due = t0 + r["due"]
        done = len(q.generated) == r["max_new"] and q.first_token_at > 0
        if not done:
            failed += 1
            ttft.append((end - due) * 1e3)
            tpot.append((end - due) * 1e3)
            continue
        ttft.append((q.first_token_at - due) * 1e3)
        tpot.append((q.last_token_at - q.first_token_at) * 1e3
                    / (len(q.generated) - 1))
    return ttft, tpot, failed


def sample(out, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest included."""
    done = [(r, h._req) for r, h, _ in out]
    done = [(r, q) for r, q in done if len(q.generated) == r["max_new"]]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda j: len(done[j][0]["prompt"])
                  + len(done[j][1].generated))
    rng = harness.np_rng(seed, 5)
    rest = [j for j in range(len(done)) if j != longest]
    pick = [longest] + list(rng.permutation(rest)[:k - 1])
    return [done[j] for j in pick]


def reference_gaps(cfg: dict, seed: int, picked, pad_to: int,
                   prec: str = "f32", control: bool = False):
    """Widest gap, over every served token of the picked requests, between
    the reference's best logit and the served token's. With ``control``
    the token judged at each position is the one ``prec`` puts first
    instead of the served one."""
    import jax
    import jax.numpy as jnp

    from reference import qwen2

    mcfg = model.model_config(cfg)
    params = model.draw_params(seed, cfg, mcfg)
    fn = jax.jit(lambda p, t: qwen2.hidden("f32", cfg, p, t)[0])
    fn_c = jax.jit(lambda p, t: qwen2.hidden(prec, cfg, p, t)[0])
    worst, n = 0.0, 0
    for r, q in picked:
        prompt, served = r["prompt"], list(q.generated)
        seq = prompt + served[:-1]
        # padded at the end to one length, so one program serves every
        # request: causal attention never reads the padding
        toks = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)[None]
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = qwen2.ein("f32", "nd,vd->nv", fn(params, toks)[0, rows],
                        params["embed"]["w"])
        if control:
            c = qwen2.ein(prec, "nd,vd->nv", fn_c(params, toks)[0, rows],
                          params["embed"]["w"])
            judged = jnp.argmax(c, -1)
        else:
            judged = jnp.asarray(served)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(
            ref, judged[:, None], -1)[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        n += len(served)
    return worst, n


def run(w: dict, seed: int, seconds: float, trace_dir, t_start: float,
        fault: str | None = None) -> dict:
    cfg, mix = w["cfg"], w["mix"]
    vocab = cfg["model"]["vocab_size"]
    engine = build(cfg, mix, seed)
    if fault == "alter_token":
        emit = engine._emit_token
        engine._emit_token = lambda req, tok, t: emit(
            req, (tok + 1) % vocab if len(req.generated) == 3 else tok, t)
    warm(engine, mix, vocab)
    reqs = schedule(mix, seconds, seed, vocab)
    counter = harness.CompileCounter()
    setup_s = harness.now() - t_start

    s0 = dict(engine.stats)
    prof = harness.Profiler(trace_dir, mix["trace_start_s"],
                            mix["trace_seconds"])
    # engine counters when the trace started and when it stopped
    prof.stats = [s0, s0]
    counter.active = True
    t0, window_s, out, i = open_loop(engine, reqs, seconds, prof)
    counter.active = False
    s1 = dict(engine.stats)
    drain(engine, reqs, out, i, t0, mix["drain_s"])
    end = harness.now()
    peak = harness.memory_peak_bytes(w["chips"])
    ttft, tpot, failed = latencies(out, t0, end)
    lateness = max((late for _, _, late in out), default=0.0)
    picked = sample(out, seed, mix["check"]["sample"])
    del engine
    gc.collect()

    gap, n_tok = reference_gaps(cfg, seed, picked, mix["engine"]["max_cache"])
    checks = [harness.check("served_logit_gap", gap,
                            w["limits"]["served_logit_gap"]),
              harness.check("requests_unfinished", float(failed), 0.0),
              harness.check("tokens_compared_short",
                            float(n_tok < mix["check"]["min_tokens"]), 0.0)]
    window = {k: s1[k] - s0[k] for k in s0
              if isinstance(s0[k], (int, float))}
    traced = {k: prof.stats[1][k] - prof.stats[0][k] for k in s0
              if isinstance(s0[k], (int, float))}
    return {
        "attempted": len(out), "failed": failed, "checks": checks,
        "memory_peak_bytes": peak, "window_s": window_s, "setup_s": setup_s,
        "counters": dict(window, compiles_in_window=counter.count,
                         traced=traced, traced_s=prof.traced_s,
                         requests=len(out),
                         prompt_tokens=sum(len(r["prompt"]) for r, _, _ in out),
                         served_tokens=sum(r["max_new"] for r, _, _ in out)),
        "requests": [(r["due"], len(r["prompt"]), r["max_new"])
                     for r, _, _ in out],
        "e2e": {"tpot_p95_ms": harness.percentile(tpot, 95),
                "peak_hbm_mib": peak / 2**20, "setup_s": setup_s},
        "picked": picked,
        # TTFT's tail over a window's hundred requests spreads too widely
        # to bound (PERF.md); it is printed, not reported
        "info": {"ttft_p50_ms": harness.percentile(ttft, 50),
                 "ttft_p95_ms": harness.percentile(ttft, 95),
                 "tpot_p50_ms": harness.percentile(tpot, 50),
                 "max_submit_lateness_s": lateness,
                 "tokens_compared": n_tok, "drain_s": end - t0 - window_s},
    }
