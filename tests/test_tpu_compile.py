"""The main path's Pallas kernels compile for a TPU v5e at qwen2-0.5b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` for one chip
of a described (not attached) ``v5e:2x2`` topology and compiled by the TPU
compiler, which refuses what interpret mode accepts — blocks not aligned to
the tiling, more scoped VMEM than a kernel may use. The kernel functions of
``kernels/*.py`` are called directly, because the ``ops`` wrappers read the
route chosen from this process's (CPU) backend. The one exception is the
data-parallel ``wsi`` train step, compiled whole for all four chips with
the ``ops`` route set to the kernels.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import repro.configs as configs
from repro import api
from repro.config import TrainConfig
from repro.kernels import ops
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.lowrank import lowrank_bwd_tiled, lowrank_fused_tiled
from repro.kernels.ops import _bwd_fits_vmem
from repro.kernels.qr import choleskyqr_tiled
from repro.kernels.quant import lowrank_q8_tiled
from repro.models.lm import init_lm, lm_loss
from repro.nn.attention import FLASH_HEAD_DIMS, FLASH_MIN_SEQ
from repro.train.step import (dp_batch_sharding, dp_state_shardings,
                              make_train_state, make_train_step)

ARCH = "qwen2-0.5b"
TRAIN_M = 4 * 512     # tokens of a 4 x 512 training batch
DECODE_M = 8          # live serve slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # the TPU library, loaded here, writes its logs to a fixed path under
    # /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture(scope="module")
def plan():
    return api.resolve(configs.get(ARCH), batch=4, seq=512)


def compile_on(sharding, fn, *shapes):
    """Compile ``fn`` for ``sharding``'s chip from (shape, dtype) pairs and
    return the program text, which must hold a Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("site", ["mlp/up", "mlp/down"])
@pytest.mark.parametrize("m,save_sketch", [(TRAIN_M, False),
                                           (TRAIN_M, True),
                                           (DECODE_M, False)],
                         ids=["prefill", "train", "decode"])
def test_lowrank_forward_compiles_at_mlp_width(one_chip, plan, site, m,
                                               save_sketch):
    s = plan.spec(site)
    i, k, o = s.in_dim, s.rank, s.out_dim
    bf = jnp.bfloat16
    compile_on(one_chip,
               lambda x, rt, lt: lowrank_fused_tiled(
                   x, rt, lt, save_sketch=save_sketch, interpret=False),
               ((m, i), bf), ((i, k), bf), ((k, o), bf))


def _compile_bwd(one_chip, m, o, i, k):
    bf = jnp.bfloat16
    compile_on(one_chip,
               lambda dy, x, h, l, r: lowrank_bwd_tiled(
                   dy, x, h, l, r, interpret=False),
               ((m, o), bf), ((m, i), bf), ((m, k), jnp.float32),
               ((o, k), bf), ((k, i), bf))


def test_lowrank_backward_compiles_at_attention_width(one_chip, plan):
    s = plan.spec("attn/wq")
    _compile_bwd(one_chip, TRAIN_M, s.out_dim, s.in_dim, s.rank)


def test_lowrank_backward_compiles_wherever_the_gate_admits_it(one_chip,
                                                              plan):
    # _bwd_fits_vmem decides, per site, whether training takes the fused
    # backward; every site it admits must compile for the chip
    admitted = {(s.out_dim, s.in_dim, s.rank) for s in plan.specs
                if s.rank and _bwd_fits_vmem(TRAIN_M, s.out_dim, s.in_dim,
                                             s.rank)}
    assert admitted, "the gate admits no qwen2-0.5b site"
    for o, i, k in sorted(admitted):
        _compile_bwd(one_chip, TRAIN_M, o, i, k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_choleskyqr_compiles_at_factor_width(one_chip, plan, dtype):
    s = plan.spec("attn/wq")
    compile_on(one_chip, lambda y: choleskyqr_tiled(y, interpret=False),
               ((s.out_dim, s.rank), dtype))


def test_int8_lowrank_compiles_at_decode(one_chip, plan):
    s = plan.spec("mlp/up")
    i, k, o = s.in_dim, s.rank, s.out_dim
    compile_on(one_chip,
               lambda x, rt, rs, lt, ls: lowrank_q8_tiled(
                   x, rt, rs, lt, ls, interpret=False),
               ((DECODE_M, i), jnp.bfloat16), ((i, k), jnp.int8),
               ((k,), jnp.float32), ((k, o), jnp.int8), ((o,), jnp.float32))


@pytest.mark.parametrize("dh", FLASH_HEAD_DIMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("pass_", ["fwd", "bwd"])
def test_flash_attention_compiles_at_the_train_cell_shape(one_chip, dh,
                                                          dtype, pass_):
    # qwen2-0.5b's heads at the train cell's 4 x 2048, causal, at the
    # tiles ops.flash_attention picks for 2048 tokens; the head dim is the
    # block's whole last dim, 64 unpadded
    b, h, kvh, s = 4, 14, 2, 2048
    s_pad, bq, bk = ops._flash_tiles(s)
    kw = dict(causal=True, window=0, s_valid=s, bq=bq, bk=bk,
              mxu_dtype=jnp.bfloat16, interpret=False)
    qs, kvs = ((b, h, s_pad, dh), dtype), ((b, kvh, s_pad, dh), dtype)
    row = ((b, h, 1, s_pad), jnp.float32)
    if pass_ == "fwd":
        compile_on(one_chip, lambda q, k, v: flash_attention_fwd(
            q, k, v, **kw), qs, kvs, kvs)
    else:
        compile_on(one_chip, lambda q, k, v, do, lse, di: flash_attention_bwd(
            q, k, v, do, lse, di, **kw), qs, kvs, kvs, qs, row, row)


def test_dp_wsi_train_step_compiles_on_four_chips(four_chips, monkeypatch):
    # the DP step is one shard_map program over the mesh; XLA cannot
    # partition a Mosaic kernel, so the fused kernels must sit inside the
    # per-device body. Layers share one scanned program: two keep the
    # published widths at a fraction of the compile time.
    cfg = configs.get(ARCH)
    cfg = cfg.replace(n_layers=2,
                      wasi=dataclasses.replace(cfg.wasi, method="wsi"))
    b, s = 4, 512
    tcfg = TrainConfig(steps=1, checkpoint_every=0)
    api.install(api.resolve(cfg, batch=b, seq=s).with_sharding())
    monkeypatch.setattr(ops, "INTERPRET", False)
    try:
        state = jax.eval_shape(
            lambda k: make_train_state(k, init_lm(k, cfg, jnp.dtype(cfg.dtype)),
                                       cfg, tcfg, dp_degree=4),
            jax.random.PRNGKey(0))
        state = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            state, dp_state_shardings(state, four_chips))
        rows = jax.ShapeDtypeStruct((b, s), jnp.int32,
                                    sharding=dp_batch_sharding(four_chips))
        step = make_train_step(lm_loss, cfg, tcfg, mesh=four_chips)
        text = jax.jit(step).lower(
            state, {"tokens": rows, "labels": rows}).compile().as_text()
    finally:
        api.uninstall(cfg)
    assert "tpu_custom_call" in text
    # at 512 tokens the per-device body takes the flash kernel, forward and
    # backward, each call under the attention scope
    assert s >= FLASH_MIN_SEQ
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv"):
        calls = [ln for ln in text.splitlines()
                 if "custom-call(" in ln and kernel in ln]
        assert calls, kernel
        assert all("attn.core/" in ln for ln in calls), kernel
