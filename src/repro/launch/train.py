"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU host it trains SMOKE configs end-to-end (the full configs are
exercised by dryrun.py); on a real TPU slice the same entry point runs the
full config — the launcher only switches mesh construction and config
resolution.

Demonstrates the full production loop: mesh + sharded state, checkpoint /
restart (kill it mid-run and relaunch), WASI maintenance, deterministic
data, straggler/heartbeat hooks.

Data comes from the registry (``--data synthetic`` | ``--data
text:<glob>``): text runs stream shard files through the tokenize/pack/
prefetch pipeline with checkpointable reader state — kill a text run
mid-stream, relaunch, and the token stream continues exactly where the
checkpoint left off (``--verify-replay`` proves it on resume by diffing
against a fast-forwarded fresh stream).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro import api
from repro.checkpoint import CheckpointManager, latest_step, restore_extra
from repro.config import TrainConfig
from repro.data.registry import make_dataset
from repro.launch.cache import use_compile_cache
from repro.train.loop import READER_EXTRA, train_loop
from repro.train.step import (
    dp_batch_sharding,
    dp_state_shardings,
    make_train_state,
    make_train_step,
)


def build(arch: str, *, smoke: bool, batch: int, seq: int, wasi: str | None,
          tcfg: TrainConfig, mesh=None, data: str = "synthetic",
          tokenizer: str = "byte"):
    """``mesh`` (a 1-D DP mesh, launch.mesh.make_host_mesh) switches the
    returned step to the shard_map data-parallel path with factor-only
    gradient collectives; the state is built per-replica-aware
    (dp_degree) and pre-placed, and the plan carries its sharding stamp.

    ``data`` is a registry spec; a text dataset's tokenizer may need more
    vocab rows than the smoke config carries, so the config's vocab is
    widened BEFORE plan resolution."""
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    if wasi is not None:
        cfg = cfg.replace(wasi=dataclasses.replace(cfg.wasi, method=wasi))
    dataset = make_dataset(data, cfg, batch=batch, seq=seq, seed=tcfg.seed,
                           **({"tokenizer": tokenizer}
                              if data.startswith("text") else {}))
    dvocab = getattr(dataset, "vocab_size", 0)
    if dvocab and dvocab > cfg.vocab_size:
        cfg = cfg.replace(vocab_size=dvocab)
    # resolve the subspace plan ONCE (with the training activation-shape
    # hint) and install it — every linear below reads this plan
    plan = api.resolve(cfg, batch=batch, seq=seq)
    if mesh is not None:
        plan = plan.with_sharding()
    plan = api.install(plan)
    key = jax.random.PRNGKey(tcfg.seed)
    dtype = jnp.dtype(cfg.dtype)
    if cfg.family == "encdec":
        from repro.models.encdec import encdec_loss, init_encdec, init_encdec_states
        params = init_encdec(key, cfg, dtype)
        asi = init_encdec_states(key, cfg, batch, seq, dtype) \
            if cfg.wasi.compress_acts else None
        loss_fn = encdec_loss
    else:
        from repro.models.lm import init_lm, init_lm_states, lm_loss
        params = init_lm(key, cfg, dtype)
        asi = init_lm_states(key, cfg, batch, seq, dtype) \
            if cfg.wasi.compress_acts else None
        loss_fn = lm_loss
    dp = mesh.devices.size if mesh is not None else 0
    state = make_train_state(key, params, cfg, tcfg, asi_states=asi,
                             dp_degree=dp)
    step = make_train_step(loss_fn, cfg, tcfg, mesh=mesh)
    if mesh is not None:
        if batch % dp:
            raise ValueError(f"--batch {batch} must divide across the "
                             f"{dp}-device mesh")
        state = jax.device_put(state, dp_state_shardings(state, mesh))
    return cfg, plan, state, step, dataset


def verify_replay(dataset, ckpt_dir: str, *, n_check: int = 2,
                  log_fn=print) -> None:
    """Prove resume determinism against the LATEST published checkpoint:
    restore the saved reader state into a fresh stream and assert its next
    batches are elementwise identical to a fresh stream fast-forwarded by
    the checkpoint's step count — the stream an uninterrupted run would be
    consuming."""
    step0 = latest_step(ckpt_dir)
    if step0 is None:
        raise SystemExit("--verify-replay: no published checkpoint in "
                         f"{ckpt_dir}")
    reader = restore_extra(ckpt_dir, step0, READER_EXTRA)
    if reader is None:
        raise SystemExit(f"--verify-replay: checkpoint step {step0} "
                         "carries no reader state (synthetic run?)")
    ref = dataset.stream()
    for _ in range(step0):
        ref.next_batch()
    resumed = dataset.stream()
    resumed.load_state(reader)
    for _ in range(n_check):
        a, b = ref.next_batch(), resumed.next_batch()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    log_fn(f"[train] replay verified: resumed token stream is elementwise "
           f"identical to an uninterrupted run ({n_check} batches checked "
           f"after fast-forwarding {step0} steps)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--wasi", default=None, help="none|wasi|asi|wsi")
    ap.add_argument("--full", action="store_true",
                    help="full (assigned) config instead of smoke")
    ap.add_argument("--data", default="synthetic",
                    help="dataset spec via data/registry.py: 'synthetic' or "
                         "'text:<shard glob>' (streamed, packed, prefetched)")
    ap.add_argument("--tokenizer", default="byte",
                    help="text tokenizer: 'byte' or 'bpe:<vocab.json>'")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="prefetch depth of the background host->device "
                         "pipeline (text data)")
    ap.add_argument("--verify-replay", action="store_true",
                    help="on resume, assert the restored reader state "
                         "replays the exact token stream, then train")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--memprof", action="store_true",
                    help="log measured memory columns (utils/memprof.py)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="data-parallel over an N-device mesh (factor-only "
                         "gradient collectives; N=0 single device)")
    ap.add_argument("--print-plan", action="store_true",
                    help="print the resolved SubspacePlan and exit")
    args = ap.parse_args()
    use_compile_cache()

    tcfg = TrainConfig(optimizer=args.optimizer, lr=args.lr, steps=args.steps,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir or "/tmp/repro_ckpt")
    if args.print_plan:
        # plan resolution is pure config math — skip model/optimizer init
        cfg = configs.get_smoke(args.arch) if not args.full \
            else configs.get(args.arch)
        if args.wasi is not None:
            cfg = cfg.replace(
                wasi=dataclasses.replace(cfg.wasi, method=args.wasi))
        print(api.resolve(cfg, batch=args.batch, seq=args.seq).summary())
        return
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(args.mesh)
    cfg, plan, state, step, data = build(args.arch, smoke=not args.full,
                                         batch=args.batch, seq=args.seq,
                                         wasi=args.wasi, tcfg=tcfg, mesh=mesh,
                                         data=args.data,
                                         tokenizer=args.tokenizer)
    print(f"[train] arch={cfg.name} wasi={cfg.wasi.method} "
          f"data={args.data} "
          f"params={sum(x.size for x in jax.tree.leaves(state.params)):,}")
    batch_sharding = None
    if mesh is not None:
        batch_sharding = dp_batch_sharding(mesh)
        # MEASURED per-step collective bytes of the compiled DP step — the
        # factor-only communication story as an observation, not a formula
        from repro.distributed.collectives import measured_collective_bytes
        cb = measured_collective_bytes(
            step, state, jax.device_put(data.batch(0), batch_sharding))
        print(f"[train] mesh={mesh.devices.size}dev per-step collective "
              f"bytes: total={cb['total']:,} "
              f"(all-reduce={cb['all-reduce']:,} over {cb['count']} ops)")
    # plan-bearing checkpoints: the manifest carries the resolved plan, so
    # the checkpoint restores for serving / dense export with no config
    ckpt = CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints,
                             plan=plan, label="train_state") \
        if args.ckpt_dir else None
    streaming = hasattr(data, "iterator")
    if streaming:
        if args.verify_replay:
            if ckpt is None:
                raise SystemExit("--verify-replay needs --ckpt-dir")
            verify_replay(data, tcfg.checkpoint_dir)
        feed = data.iterator(sharding=batch_sharding,
                             prefetch=args.prefetch)
    else:
        if args.verify_replay:
            raise SystemExit("--verify-replay only applies to streamed "
                             "(text) data — synthetic batches are a pure "
                             "function of (seed, step)")
        feed = lambda s: data.batch(s)
    try:
        state, hist = train_loop(
            state, step, feed, tcfg, ckpt=ckpt, memprof=args.memprof,
            batch_sharding=None if streaming else batch_sharding)
    finally:
        if streaming:
            feed.close()
    if hist:
        print(f"[train] done: loss {hist[0]['loss']:.4f} -> "
              f"{hist[-1]['loss']:.4f}")
    else:
        print(f"[train] already trained to step {int(state.step)}")
    if streaming and hist:
        s = feed.stats()
        print(f"[train] input pipeline: {s['tok_s']:,.0f} tok/s "
              f"stall_frac={s['stall_frac']:.3f} over {s['batches']} batches")
    if args.memprof:
        print(f"[train] live-bytes watermark: "
              f"{hist[-1]['mem_live_peak_mib']:.1f} MiB")


if __name__ == "__main__":
    main()
