"""Simulated multi-device parity: the mesh train/serve hot paths against
the single-device oracles.

Runs only under XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
conftest `multidevice` marker skips otherwise — tier-1 stays on 1 device).
Pins the ISSUE's acceptance criteria: DP loss trajectory within tolerance,
factor-only gradient collectives measurably below dense, PowerSGD
error-feedback parity, and bitwise-equal mesh serving (f32 and int8).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro import api
from repro.config import TrainConfig

pytestmark = pytest.mark.multidevice

KEY = jax.random.PRNGKey(0)
B, S = 8, 32
N_DEV = 8


@pytest.fixture(scope="module")
def mesh8():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"{len(jax.devices())} devices < {N_DEV}")
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(N_DEV)


def _train_world(method=None, powersgd_rank=0):
    from repro.data.synthetic import SyntheticLM
    from repro.models.lm import init_lm, init_lm_states, lm_loss

    cfg = configs.get_smoke("qwen2-0.5b")
    if method is not None:
        cfg = cfg.replace(wasi=dataclasses.replace(cfg.wasi, method=method))
    tcfg = TrainConfig(optimizer="sgd", lr=0.3, momentum=0.9, steps=8,
                       clip_norm=2.0, checkpoint_every=0,
                       powersgd_rank=powersgd_rank)
    params = init_lm(KEY, cfg)
    asi = init_lm_states(KEY, cfg, B, S) if cfg.wasi.compress_acts else None
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=S, global_batch=B,
                       seed=1)
    return cfg, tcfg, params, asi, lm_loss, data


def _dp_state_and_step(mesh, cfg, tcfg, params, asi, loss_fn):
    from repro.train.step import (
        dp_batch_sharding,
        dp_state_shardings,
        make_train_state,
        make_train_step,
    )

    state = make_train_state(KEY, params, cfg, tcfg, asi_states=asi,
                             dp_degree=N_DEV)
    state = jax.device_put(state, dp_state_shardings(state, mesh))
    step = make_train_step(loss_fn, cfg, tcfg, mesh=mesh)
    return state, step, dp_batch_sharding(mesh)


# ---------------------------------------------------------------------------
# (a) DP train step vs single-device loss trajectory
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,psgd", [("wasi", 0), ("none", 8)])
def test_dp_loss_trajectory_matches_single_device(mesh8, method, psgd):
    """6+ steps of the shard_map DP step track the single-device oracle.

    Not bitwise: pmean of 8 per-shard gradient blocks reassociates the f32
    sums the single-device batch reduction performs in one pass, and ASI
    warm-starts evolve per-replica. The trajectories must still agree to
    ~1e-2 at every step — divergence (e.g. a desynced replica) shows up
    orders of magnitude above that within a step or two."""
    from repro.train.step import make_train_state, make_train_step

    cfg, tcfg, params, asi, loss_fn, data = _train_world(method, psgd)
    s1 = make_train_state(KEY, params, cfg, tcfg, asi_states=asi)
    step1 = jax.jit(make_train_step(loss_fn, cfg, tcfg))
    ref = []
    for i in range(6):
        s1, m = step1(s1, data.batch(i))
        ref.append(float(m["loss"]))

    s8, dstep, bsh = _dp_state_and_step(mesh8, cfg, tcfg, params, asi,
                                        loss_fn)
    dstep = jax.jit(dstep)
    got = []
    for i in range(6):
        s8, m = dstep(s8, jax.device_put(data.batch(i), bsh))
        got.append(float(m["loss"]))
    np.testing.assert_allclose(got, ref, rtol=0, atol=0.05)
    assert ref[-1] < ref[0], "oracle did not learn — world broken"
    assert got[-1] < got[0], "DP step did not learn"


def test_dp_factor_collective_bytes_below_dense(mesh8):
    """Acceptance criterion: MEASURED per-step gradient-collective bytes of
    the factored smoke LM strictly below the dense all-reduce bytes — read
    from the compiled post-SPMD HLO, not computed from shapes."""
    from repro.distributed.collectives import measured_collective_bytes

    def bytes_for(method, psgd=0):
        cfg, tcfg, params, asi, loss_fn, data = _train_world(method, psgd)
        state, step, bsh = _dp_state_and_step(mesh8, cfg, tcfg, params, asi,
                                              loss_fn)
        return measured_collective_bytes(
            step, state, jax.device_put(data.batch(0), bsh))

    factor = bytes_for("wasi")
    dense = bytes_for("none")
    psgd = bytes_for("none", psgd=8)
    assert factor["all-reduce"] > 0, "factored step emitted no collectives"
    assert dense["all-reduce"] > 0
    assert factor["total"] < dense["total"], (factor, dense)
    assert psgd["total"] < dense["total"], (psgd, dense)


# ---------------------------------------------------------------------------
# (b) factor-only all-reduce == dense-grad all-reduce for factored sites
# ---------------------------------------------------------------------------

def test_factor_allreduce_equals_dense_allreduce(mesh8):
    """For a factored site the DP mean commutes with the factor->dense
    expansion dW = dL @ R + L @ dR: all-reducing rank-K dL/dR (K(O+I)
    bytes) then expanding equals expanding per-replica and all-reducing
    the O*I dense grad. The reduced factors themselves equal the
    arithmetic mean exactly — it IS the same mean, just smaller."""
    from repro.distributed.collectives import shard_map

    O, K, I = 48, 8, 40
    rng = np.random.default_rng(0)
    dL = jnp.asarray(rng.standard_normal((N_DEV, O, K)), jnp.float32)
    dR = jnp.asarray(rng.standard_normal((N_DEV, K, I)), jnp.float32)
    L = jnp.asarray(rng.standard_normal((O, K)), jnp.float32)
    R = jnp.asarray(rng.standard_normal((K, I)), jnp.float32)

    def factors(dl, dr):
        return (jax.lax.pmean(dl[0], "data"), jax.lax.pmean(dr[0], "data"))

    def dense(dl, dr):
        return jax.lax.pmean(dl[0] @ R + L @ dr[0], "data")

    sm = dict(mesh=mesh8, in_specs=(P("data"), P("data")), out_specs=P(),
              check_vma=False)
    dl_m, dr_m = shard_map(factors, **sm)(dL, dR)
    dw_dense = shard_map(dense, **sm)(dL, dR)

    # the all-reduced factors are EXACTLY the arithmetic mean
    np.testing.assert_array_equal(np.asarray(dl_m),
                                  np.mean(np.asarray(dL), axis=0))
    np.testing.assert_array_equal(np.asarray(dr_m),
                                  np.mean(np.asarray(dR), axis=0))
    # expansion commutes with the mean (bitwise up to f32 reassociation of
    # the K-dim contraction with the 8-way sum)
    dw_factor = np.asarray(dl_m @ R + L @ dr_m)
    np.testing.assert_allclose(dw_factor, np.asarray(dw_dense),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# (c) PowerSGD per-replica error feedback vs single-device oracle
# ---------------------------------------------------------------------------

def test_powersgd_dp_matches_single_device_oracle(mesh8):
    """The DP PowerSGD round (pmean'd P/Q factors, per-replica error)
    transmits the same decompressed sequence as the single-device
    compress_decompress oracle fed the mean gradient, and the mean of the
    per-replica errors tracks the oracle's error accumulator — over
    multiple steps, so error feedback itself is what's being compared."""
    from repro.core.powersgd import (
        PowerSGDState,
        compress_decompress,
        powersgd_init,
    )
    from repro.distributed.collectives import shard_map

    O, I, rank, steps = 72, 64, 4, 5
    rng = np.random.default_rng(1)
    grads = jnp.asarray(rng.standard_normal((steps, N_DEV, O, I)),
                        jnp.float32)
    key = jax.random.PRNGKey(3)
    oracle = powersgd_init(key, (O, I), rank)
    dp = powersgd_init(key, (O, I), rank, local_copies=N_DEV)

    def local(g, q, err):
        st = PowerSGDState(q=q, error=err[0])
        dec, ns = compress_decompress(
            g[0], st, lambda x: jax.lax.pmean(x, "data"))
        return dec, ns.q, ns.error[None]

    round_fn = shard_map(
        local, mesh=mesh8,
        in_specs=(P("data"), P(), P("data")),
        out_specs=(P(), P(), P("data")), check_vma=False)

    q, err = dp.q, dp.error
    for t in range(steps):
        dec, q, err = round_fn(grads[t], q, err)
        odec, oracle = compress_decompress(grads[t].mean(axis=0), oracle)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(odec),
                                   rtol=0, atol=1e-4, err_msg=f"step {t}")
    np.testing.assert_allclose(np.asarray(err).mean(axis=0),
                               np.asarray(oracle.error), rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(q), np.asarray(oracle.q),
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# (d) mesh ServeEngine bitwise vs single-device dense engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_mesh_engine_greedy_bitwise_equals_single_device(mesh8, quant):
    from repro.models.lm import init_lm
    from repro.serve import ServeEngine

    cfg = configs.get_smoke("qwen2-0.5b")
    api.uninstall(cfg)
    plan = api.resolve(cfg)
    params = init_lm(KEY, cfg, jnp.dtype(cfg.dtype))
    if quant:
        plan = plan.quantized("int8")
        params = api.convert.quantize(params, plan)
    try:
        kw = dict(plan=plan, max_slots=N_DEV, max_cache=32,
                  buckets=(4, 8, 16))
        rng = np.random.default_rng(2)
        prompts = [list(map(int, rng.integers(0, cfg.vocab_size, n)))
                   for n in (3, 7, 5, 11, 4, 9)]

        dense = ServeEngine(params, cfg, **kw)
        hd = [dense.submit(p, max_new=6) for p in prompts]
        dense.run()

        meshed = ServeEngine(params, cfg, mesh=mesh8, **kw)
        hm = [meshed.submit(p, max_new=6) for p in prompts]
        meshed.run()
        meshed.check_invariants()  # cache still sharded over all 8 devices

        for a, b, p in zip(hd, hm, prompts):
            assert a.tokens == b.tokens, (p, a.tokens, b.tokens)
        s = meshed.summary()
        assert s["mesh_devices"] == N_DEV
        assert s["slots_per_device"] == N_DEV // N_DEV
    finally:
        api.uninstall(cfg)


def test_mesh_engine_rejects_unshardable_modes(mesh8):
    from repro.models.lm import init_lm
    from repro.serve import ServeEngine

    cfg = configs.get_smoke("qwen2-0.5b")
    api.uninstall(cfg)
    params = init_lm(KEY, cfg, jnp.dtype(cfg.dtype))
    try:
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(params, cfg, max_slots=8, max_cache=32, paged=True,
                        mesh=mesh8)
        with pytest.raises(ValueError, match="divide evenly"):
            ServeEngine(params, cfg, max_slots=6, max_cache=32, mesh=mesh8)
        with pytest.raises(ValueError, match="speculative"):
            ServeEngine(params, cfg, max_slots=8, max_cache=32, spec_k=2,
                        draft="rank:0.5", mesh=mesh8)
    finally:
        api.uninstall(cfg)
