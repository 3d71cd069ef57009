"""Serving launcher: thin CLI over the streaming continuous-batching engine.

``python -m repro.launch.serve --arch qwen2-0.5b --tokens 32 --batch 4``
``python -m repro.launch.serve --ckpt /tmp/ckpt --stream --temperature 0.8
  --top-k 8 --seed 1 --sched priority``

Prefill is token-parallel — ONE forward over the whole prompt writes every
layer's decode caches (models/lm.py::lm_prefill); decode is a jit'd
single-token step over all serve slots at per-slot positions, with
per-request temperature/top-k/top-p sampling fused into the step so only
sampled int32 tokens ever leave the device (serve/sampling.py). WASI
inference benefit: every linear runs in the rank-K subspace through the
fused lowrank kernel (paper C_inference / S_inference — measured by
benchmarks/tab2_latency.py). The engine itself (pluggable scheduler,
bucketed prefill, slot recycling, streaming handles) lives in
repro/serve/; the request lifecycle is documented in docs/serving.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro import api
from repro.launch.cache import use_compile_cache
from repro.models.lm import init_lm, init_lm_cache, lm_decode_step, lm_prefill
from repro.serve import SCHEDULERS, EventKind, SamplingParams, ServeEngine


@functools.lru_cache(maxsize=8)
def _jitted_steps(cfg):
    """Per-config jitted prefill/decode, cached so repeated generate()
    calls (warmup-then-time benchmarks, test reference loops) reuse the
    compiled executables instead of retracing fresh lambdas each call."""
    prefill = jax.jit(
        lambda pr, t, c: lm_prefill(pr, t, cfg, caches=c, last_only=True))
    step = jax.jit(
        lambda pr, tok, c, pos: lm_decode_step(pr, tok, c, pos, cfg))
    return prefill, step


def generate(params, cfg, prompt, max_cache: int, n_new: int, *, greedy=True,
             key=None):
    """prompt (B, P) -> (B, P + n_new). Lockstep batch: one token-parallel
    prefill (no per-token Python loop), then a jit'd decode step.

    This is the PRE-REDESIGN greedy path (host argmax over returned
    logits), kept as the bitwise oracle the streaming engine's
    temperature-0 rows are tested against."""
    b, p = prompt.shape
    caches = init_lm_cache(cfg, b, max_cache, dtype=jnp.dtype(cfg.dtype))
    prefill, step = _jitted_steps(cfg)

    logits, caches = prefill(params, prompt, caches)
    logits = logits[:, 0]
    out = [prompt]
    for j in range(n_new):
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(nxt)
        if j < n_new - 1:  # the last token needs no further forward
            logits, caches = step(params, nxt, caches, p + j)
    return jnp.concatenate(out, axis=1)


def _stream(engine, handles) -> None:
    """Drive the engine to completion, printing tokens as they arrive
    (one line per engine tick batch) and a TTFT/TPOT line per request."""
    cursors = [0] * len(handles)
    while engine.busy:
        engine.step()
        for i, h in enumerate(handles):
            events = h.events
            for ev in events[cursors[i]:]:
                if ev.kind is EventKind.TOKEN:
                    print(f"[stream] rid={ev.rid} token={ev.token}",
                          flush=True)
                else:
                    print(f"[stream] rid={ev.rid} {ev.kind.value}"
                          + (f" ({ev.reason})" if ev.reason else ""))
            cursors[i] = len(events)
    bad = []
    for h in handles:
        ttft = h.ttft_s
        tpot = h.tpot_s
        print(f"[stream] rid={h.rid} status={h.status.value} "
              f"new={len(h.generated)} "
              f"ttft_ms={ttft * 1e3 if ttft else float('nan'):.2f} "
              f"tpot_ms={tpot * 1e3 if tpot else float('nan'):.3f}")
        if h.finished and not (ttft and ttft > 0):
            bad.append(h.rid)
    if bad:
        raise SystemExit(f"finished requests with no TTFT: {bad}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to submit")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--max-slots", type=int, default=0,
                    help="serve slots (0 => min(batch, 4)); fewer slots than "
                         "requests exercises queueing + slot recycling")
    ap.add_argument("--wasi", default=None)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default="",
                    help="serve from a plan-bearing checkpoint dir (the "
                         "manifest's SubspacePlan replaces --arch/--wasi)")
    ap.add_argument("--quant", default="", choices=["", "int8"],
                    help="deploy-quantize the weights before serving "
                         "(per-channel absmax int8 factors; a checkpoint "
                         "that is already quant-stamped needs no flag)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (token-for-token the legacy engine); "
                         "> 0 samples device-side in the fused decode step")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="sampling seed (default: stable per-request rid)")
    ap.add_argument("--sched", default="fcfs", choices=sorted(SCHEDULERS),
                    help="admission policy (serve/scheduler.py)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool with prefix sharing + chunked "
                         "prefill (serve/kvpool.py); needs full causal "
                         "attention in every layer")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV positions per page (paged mode)")
    ap.add_argument("--total-pages", type=int, default=0,
                    help="pool size (0 => dense-equivalent capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="tokens per chunked-prefill tick (0 => largest "
                         "bucket)")
    ap.add_argument("--prefill-every", type=int, default=1,
                    help="run chunked prefill every Nth tick while decodes "
                         "are active (higher => lower decode TPOT tax, "
                         "slower long-prompt TTFT)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request radix prefix reuse")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: draft this many "
                         "tokens per tick through a cheap subspace view "
                         "of the SAME weights, verify them in one "
                         "batched forward (0 = off; docs/serving.md)")
    ap.add_argument("--draft", default="int8",
                    help="draft source for --spec-k: 'int8' (packed "
                         "factors) or 'rank:<frac>' (leading slice of "
                         "each site's L/R, e.g. rank:0.5)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated plus "
                         "per-request TTFT/TPOT, instead of the batch "
                         "summary only")
    ap.add_argument("--adapters", default="",
                    help="AdapterStore directory (launch/finetune_user.py "
                         "writes it): requests carry per-tenant factored "
                         "deltas hot-swapped from this store")
    ap.add_argument("--tenants", default="",
                    help="comma-separated tenant ids cycled across the "
                         "submitted requests ('' entries = bare base); "
                         "requires --adapters")
    ap.add_argument("--adapter-slots", type=int, default=4,
                    help="device-resident adapter LRU capacity "
                         "(tenant churn past it swaps bank rows, never "
                         "re-jits)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard the dense slot pool across an N-device "
                         "mesh (weights replicate, KV slots shard on the "
                         "batch axis; 0 = single device). Excludes "
                         "--paged/--spec-k/--adapters")
    args = ap.parse_args()
    use_compile_cache()

    key = jax.random.PRNGKey(0)
    slots = args.max_slots or min(args.batch, 4)
    max_cache = args.prompt_len + args.tokens + 1
    if args.tenants and not args.adapters:
        raise SystemExit("--tenants needs --adapters DIR")
    tenants = ([t or None for t in args.tenants.split(",")]
               if args.tenants else [None])
    paged_kw = {}
    if args.adapters:
        paged_kw.update(adapters=args.adapters,
                        adapter_slots=args.adapter_slots)
    if args.spec_k:
        paged_kw.update(spec_k=args.spec_k, draft=args.draft)
    if args.paged:
        paged_kw.update(paged=True, page_size=args.page_size,
                        total_pages=args.total_pages or None,
                        prefill_chunk=args.prefill_chunk or None,
                        prefill_every=args.prefill_every,
                        prefix_cache=not args.no_prefix_cache)
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        paged_kw.update(mesh=make_host_mesh(args.mesh))
    if args.ckpt:
        params, plan, _ = api.convert.load_checkpoint(args.ckpt)
        if plan is None:
            raise SystemExit(f"checkpoint at {args.ckpt} carries no plan")
        if args.quant and not plan.is_quantized:
            plan = plan.quantized(args.quant)
            params = api.convert.quantize(params, plan)
        engine = ServeEngine(params, plan=plan, max_slots=slots,
                             max_cache=max_cache, scheduler=args.sched,
                             **paged_kw)
        cfg = engine.cfg
    else:
        cfg = configs.get(args.arch) if args.full \
            else configs.get_smoke(args.arch)
        if args.wasi is not None:
            cfg = cfg.replace(
                wasi=dataclasses.replace(cfg.wasi, method=args.wasi))
        plan = api.install(api.resolve(cfg))
        params = init_lm(key, cfg, jnp.dtype(cfg.dtype))
        if args.quant:
            api.uninstall(cfg)          # the engine installs the quant view
            plan = plan.quantized(args.quant)
            params = api.convert.quantize(params, plan)
        engine = ServeEngine(params, plan=plan, max_slots=slots,
                             max_cache=max_cache, scheduler=args.sched,
                             **paged_kw)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    handles = [engine.submit(list(map(int, prompts[i])), max_new=args.tokens,
                             sampling=sp, tenant=tenants[i % len(tenants)])
               for i in range(args.batch)]
    for i, h in enumerate(handles):
        print(f"[serve] rid={h.rid} tenant={tenants[i % len(tenants)]}")
    if args.stream:
        _stream(engine, handles)
    else:
        engine.run()
    dt = time.time() - t0
    s = engine.summary()
    qtag = " quant=int8" if engine.quantized else ""
    stag = "" if sp.is_greedy else (f" T={sp.temperature}"
                                    f" top_k={sp.top_k} top_p={sp.top_p}")
    ptag = ""
    if s["paged"]:
        ptag = (f" paged pg={s['page_size']} pages={s['total_pages']} "
                f"chunks={s['prefill_chunks']} "
                f"prefix_hits={s['prefix_hit_tokens']}")
    if args.mesh:
        ptag += (f" mesh={s['mesh_devices']}dev "
                 f"({s['slots_per_device']} slots, "
                 f"{s['cache_bytes_per_device'] / 2**20:.2f}MiB KV each)")
    print(f"[serve] arch={cfg.name} wasi={cfg.wasi.method}{qtag}{stag} "
          f"sched={s['scheduler']} slots={slots} requests={args.batch} "
          f"wall={dt:.2f}s weights={s['weight_mib']:.2f}MiB "
          f"kv={s['cache_bytes'] / 2**20:.2f}MiB{ptag}")
    print(f"[serve] prefill {s['prefill_tokens']} tok "
          f"({s['prefill_tok_s']:.1f} tok/s, one forward per admission "
          f"group) | decode {s['decode_tokens']} tok "
          f"({s['decode_tok_s']:.1f} tok/s) | "
          f"{s['requests_s']:.2f} req/s")
    if args.adapters:
        t = s["tenancy"]
        print(f"[serve] tenancy resident={','.join(t['resident']) or '-'} "
              f"capacity={t['capacity']} swaps={t['swaps']} "
              f"evictions={t['evictions']} hits={t['hits']} "
              f"bank={t['bank_bytes'] / 2**20:.2f}MiB "
              f"store_tenants={t['store_tenants']}")
    if args.spec_k:
        print(f"[serve] spec k={s['spec_k']} draft={s['draft_source']} "
              f"acceptance_rate={s['acceptance_rate']:.3f} "
              f"tokens_per_verify={s['tokens_per_verify']:.2f} "
              f"verify_steps={s['spec_steps']} "
              f"drafted={s['spec_draft_tokens']} "
              f"accepted={s['spec_accepted_tokens']}")
    print("[serve] sample:", handles[0].tokens)


if __name__ == "__main__":
    main()
