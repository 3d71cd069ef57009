#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU, at qwen2-0.5b's published width.

    python3 chip_smoke.py                # one chip: train, kernel, serve
    python3 chip_smoke.py --four-chips   # four chips: DP train and mesh
                                         # serving, each against one device

qwen2-0.5b runs whole (24 layers, d_model 896, d_ff 4864, vocab 151936,
bf16) from random weights made from ``--seed``, through the entry points a
user calls: ``launch.train.build`` + ``train.loop.train_loop`` for training
and ``serve.ServeEngine`` for serving.

One chip:
  * train: a few steps under ``wasi`` (Tucker-residual custom VJP, plain
    einsums) and ``wsi`` (factored, no ASI state: the fused low-rank
    forward and sketch-saving backward). Every loss must be finite, and
    the compiled ``wsi`` step must launch Pallas kernels.
  * kernel: the fused low-rank forward at an MLP site's shape against the
    einsum pair at ``highest`` matmul precision.
  * serve: more requests than slots, so slots are recycled through the
    donated KV cache. Every request must finish with its tokens, the
    decode step must launch the fused kernel, a second identical pass must
    repeat the first, and every greedy token must be one the plain
    cache-free forward ranks at or near its top.

Four chips: the data-parallel train step on a 4-device mesh against the
same steps on one device, under ``wasi`` and ``wsi`` (whose DP step must
launch Pallas kernels), and the mesh ``ServeEngine`` against the
single-device engine, with the state and KV slots checked to lie on all
four devices.

Exits non-zero, printing no result, when JAX finds no TPU (for example
under ``JAX_PLATFORMS=cpu``) or any check fails. The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time
from pathlib import Path

# the TPU library writes its logs to a fixed path under /tmp unless told
# otherwise; set before JAX loads it
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ARCH = "qwen2-0.5b"
# Output of the fused forward is rounded to bf16, whose spacing at the top
# of the range is 2**-7 of the largest value; 1e-2 of max|ref| allows that
# plus f32 reassociation. A wrong tile or a misread block is O(1).
KERNEL_REL_BOUND = 1e-2
# A greedy token must sit within this many standard deviations of the
# reference's top logit at its position. bf16 rounding moves a logit by
# a few hundredths of a deviation; a token read through a corrupted cache
# is a near-random one, about 4.5 deviations below the top at this vocab.
GREEDY_GAP_SIGMAS = 0.5
# DP vs one device: per-replica ASI state and the pmean's reassociation
# make the trajectories differ slightly (tests/test_mesh_parity.py).
DP_LOSS_ATOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"[chip_smoke] FAIL: {msg}")


def mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


# -- train ------------------------------------------------------------------

def train_phase(method: str, *, batch: int, seq: int, steps: int, seed: int,
                mesh=None) -> dict:
    """``steps`` steps of ``method`` through train_loop. Returns losses and
    timings; fails on a non-finite loss or a step that cannot fit."""
    import jax
    import numpy as np

    from repro import api
    from repro.config import TrainConfig
    from repro.launch.train import build
    from repro.train.loop import train_loop
    from repro.train.step import dp_batch_sharding

    tag = f"train[{method}{'/mesh' if mesh is not None else ''}]"
    tcfg = TrainConfig(steps=steps, checkpoint_every=0, seed=seed)
    t0 = time.perf_counter()
    cfg, _, state, step, data = build(ARCH, smoke=False, batch=batch,
                                      seq=seq, wasi=method, tcfg=tcfg,
                                      mesh=mesh)
    jax.block_until_ready(state)
    build_s = time.perf_counter() - t0
    bsh = dp_batch_sharding(mesh) if mesh is not None else None
    first = data.batch(0)
    if bsh is not None:
        first = jax.device_put(first, bsh)

    # compile rehearsal: does the step fit the chip at this batch x seq?
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=0).lower(state, first).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    limit = int(jax.devices()[0].memory_stats()["bytes_limit"])
    log(f"{tag} B={batch} S={seq} build={build_s:.2f}s "
        f"compile={compile_s:.2f}s memory_analysis: "
        f"args={mib(ma.argument_size_in_bytes)} "
        f"out={mib(ma.output_size_in_bytes)} "
        f"temp={mib(ma.temp_size_in_bytes)} "
        f"alias={mib(ma.alias_size_in_bytes)} "
        f"peak={mib(ma.peak_memory_in_bytes)} limit={mib(limit)}")
    need = max(ma.peak_memory_in_bytes,
               ma.argument_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    if need > limit:
        fail(f"{tag} needs {mib(need)} of {mib(limit)} per device")
    kernels = compiled.as_text().count("tpu_custom_call")
    del compiled

    placement = audit_state(state, mesh) if mesh is not None else None
    t0 = time.perf_counter()
    state, hist = train_loop(state, step, data.batch, tcfg, log_every=1,
                             batch_sharding=bsh,
                             log_fn=lambda m: log(f"{tag} {m}"))
    jax.block_until_ready(state)
    loop_s = time.perf_counter() - t0
    if mesh is not None:
        audit_state(state, mesh)
    losses = [h["loss"] for h in hist]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        fail(f"{tag} losses {losses}: expected {steps} finite values")
    step_s = statistics.median(h["sec"] for h in hist[1:])
    # train_loop's step time includes drawing the synthetic batch, which
    # runs on the device too: time that alone
    gen = []
    for i in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(data.batch(steps + i))
        gen.append(time.perf_counter() - t0)
    log(f"{tag} losses={losses} first_step={hist[0]['sec']:.3f}s "
        f"median_step={step_s:.4f}s "
        f"median_batch_draw={statistics.median(gen):.4f}s "
        f"tok_s={batch * seq / step_s:.0f} loop={loop_s:.2f}s "
        f"tpu_custom_call={kernels} "
        f"peak_bytes_in_use={jax.devices()[0].memory_stats()['peak_bytes_in_use']}")
    api.uninstall(cfg)
    return {"losses": losses, "kernels": kernels, "placement": placement}


def audit_state(state, mesh) -> dict:
    """Every train-state leaf must live on all mesh devices: replicated
    weights, and per-replica buffers split one shard per device."""
    import jax

    n = mesh.devices.size
    replicated = sharded = 0
    for leaf in jax.tree.leaves(state):
        devs = leaf.sharding.device_set
        if len(devs) != n:
            fail(f"state leaf {leaf.shape} on {len(devs)} of {n} devices")
        if leaf.sharding.is_fully_replicated:
            replicated += 1
        else:
            shards = leaf.addressable_shards
            if len({s.device for s in shards}) != n:
                fail(f"state leaf {leaf.shape} has shards on fewer than "
                     f"{n} devices")
            sharded += 1
    return {"replicated": replicated, "sharded": sharded}


# -- kernel -----------------------------------------------------------------

def kernel_phase(seed: int) -> None:
    """Fused low-rank forward at mlp/up's shape, prefill-sized and
    decode-sized M, against the f32 einsum pair at highest precision."""
    import jax
    import jax.numpy as jnp

    import repro.configs as configs
    from repro import api
    from repro.kernels import ops

    site = api.resolve(configs.get(ARCH)).spec("mlp/up")
    i, o, k = site.in_dim, site.out_dim, site.rank

    @jax.jit
    def reference(x, r, l):
        with jax.default_matmul_precision("highest"):
            f = jnp.float32
            return (x.astype(f) @ r.astype(f).T) @ l.astype(f).T

    for m in (2048, 8):
        kx, kr, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(kx, (m, i), jnp.bfloat16)
        r = (jax.random.normal(kr, (k, i)) * i ** -0.5).astype(jnp.bfloat16)
        l = (jax.random.normal(kl, (o, k)) * k ** -0.5).astype(jnp.bfloat16)
        y = ops.lowrank_matmul_fused(x, r, l).astype(jnp.float32)
        ref = reference(x, r, l)
        err = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
        log(f"kernel lowrank_fused M={m} I={i} K={k} O={o} bf16: "
            f"max|err|/max|ref|={err:.3e} bound={KERNEL_REL_BOUND:.0e}")
        if not err <= KERNEL_REL_BOUND:
            fail(f"fused low-rank forward off by {err:.3e} at M={m}")


# -- serve ------------------------------------------------------------------

def serve_world(seed: int):
    import jax
    import jax.numpy as jnp

    import repro.configs as configs
    from repro import api
    from repro.models.lm import init_lm

    cfg = configs.get(ARCH)
    api.uninstall(cfg)
    plan = api.install(api.resolve(cfg))
    params = init_lm(jax.random.PRNGKey(seed), cfg, jnp.dtype(cfg.dtype))
    return cfg, plan, params


def serve_requests(engine, prompts, new: int) -> tuple[list, float]:
    import numpy as np

    t0 = time.perf_counter()
    handles = [engine.submit(list(map(int, p)), max_new=new)
               for p in prompts]
    engine.run()
    wall = time.perf_counter() - t0
    vocab = engine.cfg.vocab_size
    for h in handles:
        g = np.asarray(h.generated)
        if not h.finished or len(g) != new:
            fail(f"request {h.rid} status={h.status} made {len(g)} of "
                 f"{new} tokens")
        if g.min() < 0 or g.max() >= vocab:
            fail(f"request {h.rid} token out of range [0, {vocab})")
    return [list(h.generated) for h in handles], wall


def greedy_gap(params, cfg, prompts, generated) -> float:
    """Largest gap, in standard deviations of the logits, between the
    reference's top logit and the logit of the token the engine chose.
    The reference is one cache-free forward over prompt + generated
    (teacher-forced, so one rounding flip does not derail the rest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.lm import lm_forward

    seqs = jnp.asarray(np.concatenate([prompts, np.asarray(generated)], 1),
                       jnp.int32)
    p = prompts.shape[1]

    @jax.jit
    def gaps(params, seqs):
        z = lm_forward(params, seqs[:, :-1], cfg)[0][:, p - 1:]
        z = z.astype(jnp.float32)
        chosen = jnp.take_along_axis(z, seqs[:, p:, None], axis=-1)[..., 0]
        return (z.max(-1) - chosen) / z.std(-1)

    return float(jnp.max(gaps(params, seqs)))


def serve_phase(seed: int, *, requests: int, prompt_len: int, new: int,
                slots: int) -> None:
    import jax
    import numpy as np

    from repro.launch.serve import generate
    from repro.serve import ServeEngine

    cfg, plan, params = serve_world(seed)
    max_cache = prompt_len + new + 1
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (requests, prompt_len), 0,
        cfg.vocab_size))
    engine = ServeEngine(params, plan=plan, max_slots=slots,
                         max_cache=max_cache)
    toks1, wall1 = serve_requests(engine, prompts, new)
    engine.reset_stats()
    toks2, wall2 = serve_requests(engine, prompts, new)
    s = engine.summary()
    log(f"serve requests={requests} slots={slots} prompt={prompt_len} "
        f"new={new} first_pass={wall1:.2f}s (compiles included) "
        f"second_pass={wall2:.3f}s prefill_tok_s={s['prefill_tok_s']:.1f} "
        f"decode_tok_s={s['decode_tok_s']:.1f} "
        f"weights={mib(s['weight_bytes'])} kv={mib(s['cache_bytes'])}")
    if toks2 != toks1:
        fail("a second pass over the same prompts through recycled slots "
             "gave different greedy tokens")
    kernels = engine.compiled_decode().as_text().count("tpu_custom_call")
    log(f"serve decode step tpu_custom_call={kernels}")
    if not kernels:
        fail("the compiled decode step launches no Pallas kernel")
    ref = np.asarray(generate(params, cfg, jax.numpy.asarray(prompts),
                              max_cache, new))[:, prompt_len:]
    match = float(np.mean(ref == np.asarray(toks1)))
    gap = greedy_gap(params, cfg, prompts, toks1)
    log(f"serve greedy tokens vs launch.serve.generate: match_rate="
        f"{match:.3f}; engine rid0={toks1[0]}; generate rid0="
        f"{ref[0].tolist()}")
    log(f"serve worst greedy gap to the reference top logit: {gap:.3f} "
        f"sigma (bound {GREEDY_GAP_SIGMAS})")
    if not gap <= GREEDY_GAP_SIGMAS:
        fail(f"engine picked a token {gap:.3f} sigma below the top logit")
    log(f"serve peak_bytes_in_use="
        f"{jax.devices()[0].memory_stats()['peak_bytes_in_use']}")


# -- four chips -------------------------------------------------------------

def four_chip_phase(seed: int, *, batch: int, seq: int, steps: int) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine

    mesh = make_host_mesh(4)
    for method in ("wasi", "wsi"):
        one = train_phase(method, batch=batch, seq=seq, steps=steps,
                          seed=seed)
        gc.collect()
        dp = train_phase(method, batch=batch, seq=seq, steps=steps,
                         seed=seed, mesh=mesh)
        gc.collect()
        diff = np.abs(np.asarray(dp["losses"]) - np.asarray(one["losses"]))
        log(f"dp train[{method}]: one-device losses={one['losses']} "
            f"4-device losses={dp['losses']} max|diff|={diff.max():.4f} "
            f"(atol {DP_LOSS_ATOL}); state leaves {dp['placement']}")
        if not diff.max() <= DP_LOSS_ATOL:
            fail(f"DP {method} loss trajectory left the one-device one by "
                 f"{diff.max()}")
    if not dp["kernels"]:
        fail("the compiled DP wsi train step launches no Pallas kernel")

    cfg, plan, params = serve_world(seed)
    prompt_len, new, slots = 64, 16, 4
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (8, prompt_len), 0, cfg.vocab_size))
    kw = dict(plan=plan, max_slots=slots, max_cache=prompt_len + new + 1)
    single = ServeEngine(params, **kw)
    toks1, wall1 = serve_requests(single, prompts, new)
    meshed = ServeEngine(params, mesh=mesh, **kw)
    toks4, wall4 = serve_requests(meshed, prompts, new)
    meshed.check_invariants()     # every KV leaf: one slot shard per device
    s = meshed.summary()
    per_dev = [d.memory_stats()["bytes_in_use"] for d in mesh.devices.flat]
    match = float(np.mean(np.asarray(toks1) == np.asarray(toks4)))
    gap = greedy_gap(params, cfg, prompts, toks4)
    log(f"mesh serve: {s['mesh_devices']} devices x "
        f"{s['slots_per_device']} slots, KV "
        f"{mib(s['cache_bytes_per_device'])} per device; bytes_in_use per "
        f"device={per_dev}; single={wall1:.2f}s mesh={wall4:.2f}s "
        f"(compiles included)")
    log(f"mesh serve greedy tokens vs single device: match_rate={match:.3f}; "
        f"worst gap to the reference top logit {gap:.3f} sigma "
        f"(bound {GREEDY_GAP_SIGMAS})")
    if not gap <= GREEDY_GAP_SIGMAS:
        fail(f"mesh engine picked a token {gap:.3f} sigma below the top")


# -- main -------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device DP train and mesh serve "
                         "paths, each against one device")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    t_start = time.perf_counter()

    import jax

    need = 4 if args.four_chips else 1
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < need:
        fail(f"{need} chips needed, {len(devices)} visible")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.kernels import ops
    from repro.launch.cache import use_compile_cache

    if ops.INTERPRET:
        fail("repro.kernels.ops.INTERPRET is True on a TPU: kernels would "
             "take their CPU routes")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
        f"cache: {use_compile_cache()}")

    if args.four_chips:
        four_chip_phase(args.seed, batch=args.batch, seq=args.seq,
                        steps=args.steps)
    else:
        runs = {}
        for method in ("wasi", "wsi"):
            t0 = time.perf_counter()
            runs[method] = train_phase(method, batch=args.batch,
                                       seq=args.seq, steps=args.steps,
                                       seed=args.seed)
            gc.collect()
            log(f"phase train[{method}] {time.perf_counter() - t0:.2f}s")
        if not runs["wsi"]["kernels"]:
            fail("the compiled wsi train step launches no Pallas kernel")
        t0 = time.perf_counter()
        kernel_phase(args.seed)
        log(f"phase kernel {time.perf_counter() - t0:.2f}s")
        t0 = time.perf_counter()
        serve_phase(args.seed, requests=4, prompt_len=64, new=16, slots=2)
        log(f"phase serve {time.perf_counter() - t0:.2f}s")

    log(f"total {time.perf_counter() - t_start:.2f}s "
        f"peak_bytes_in_use={devices[0].memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
