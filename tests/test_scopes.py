"""Named scopes in the train step (repro/utils/scopes.py): every scope of
the vocabulary lands in the compiled step's op_name metadata, and the ones
that belong to the forward or the backward pass land there. Built on the
WASI smoke LM with ASI states threaded and block remat, as the train cell
runs it; a refactor that drops or moves a scope fails here."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

import repro.configs as configs
from repro.config import TrainConfig
from repro.kernels import ops
from repro.models.lm import init_lm, init_lm_states, lm_loss
from repro.nn import attention
from repro.train.step import make_train_state, make_train_step
from repro.utils import scopes

B, S = 2, 64
OP_NAME = re.compile(r'op_name="([^"]*)"')
TRANSFORMED = re.compile(r"^[\w-]+\((.*)\)$")


def _parts(path: str) -> list[str]:
    """The names on an op_name path, transform wrappers taken off
    ("transpose(jvp(lm.head))" -> "lm.head")."""
    out = []
    for part in path.split("/"):
        while (m := TRANSFORMED.match(part)):
            part = m.group(1)
        out.append(part)
    return out


@pytest.fixture(scope="module")
def op_names():
    cfg = configs.get_smoke("qwen2-0.5b")
    cfg = cfg.replace(remat="block", wasi=dataclasses.replace(
        cfg.wasi, method="wasi", update_mode="factored"))
    assert cfg.wasi.compress_acts and cfg.wasi.refresh_every > 0
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, clip_norm=2.0, steps=8,
                       checkpoint_every=0)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(
        lambda k: make_train_state(k, init_lm(k, cfg), cfg, tcfg,
                                   asi_states=init_lm_states(k, cfg, B, S)),
        key)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    step = jax.jit(make_train_step(lm_loss, cfg, tcfg), donate_argnums=0)
    text = step.lower(state, {"tokens": tok, "labels": tok}).compile() \
        .as_text()
    return set(OP_NAME.findall(text))


CUSTOM_CALL = re.compile(r'tpu_custom_call.*kernel_name = "(\w+)".*'
                         r"loc\(#(loc\d+)\)")
LOC = re.compile(r'^#(loc\d+) = loc\("([^"]*)"', re.M)


@pytest.fixture(scope="module")
def cell_step():
    """The train cell's step (qwen2-0.5b, 4 x 2048, float32 weights, ASI
    states, block remat) traced with the kernel route on and lowered for a
    TPU: (route tally, [(kernel name, location name)] of its Mosaic
    calls). Nothing compiles; the TPU library is not loaded."""
    cfg = configs.get("qwen2-0.5b").replace(remat="block", dtype="float32")
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, clip_norm=2.0, steps=8,
                       checkpoint_every=0)
    b, s = 4, 2048
    state = jax.eval_shape(
        lambda k: make_train_state(
            k, init_lm(k, cfg, jnp.float32), cfg, tcfg,
            asi_states=init_lm_states(k, cfg, b, s, jnp.float32)),
        jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "INTERPRET", False)
        attention.reset_attention_routes()
        traced = jax.jit(make_train_step(lm_loss, cfg, tcfg)).trace(
            state, {"tokens": tok, "labels": tok})
        routes = attention.attention_routes()
        text = traced.lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    locs = dict(LOC.findall(text))
    calls = [(name, locs[loc]) for name, loc in CUSTOM_CALL.findall(text)]
    return routes, calls


def test_attention_routes_count_the_cell_step(cell_step):
    """All 24 layers of the scanned group take the flash kernel."""
    routes, _ = cell_step
    assert routes == {"flash": 24}


@pytest.mark.parametrize("kernel", ["flash_attention_fwd",
                                    "flash_attention_dq",
                                    "flash_attention_dkv"])
def test_flash_calls_carry_attention_scope(cell_step, kernel):
    """The kernel's forward (in the forward and its remat re-run) and its
    backward custom calls sit under attn.core, where
    attention_share.train reads them."""
    _, calls = cell_step
    names = [loc for name, loc in calls if name == kernel]
    assert len(names) == (2 if kernel == "flash_attention_fwd" else 1)
    assert all(scopes.ATTENTION in _parts(loc) for loc in names), names


def test_vocabulary_is_distinct():
    assert len(set(scopes.ALL)) == len(scopes.ALL) == 9
    assert all("/" not in s for s in scopes.ALL)


@pytest.mark.parametrize("scope", scopes.ALL)
def test_scope_in_compiled_step(op_names, scope):
    assert any(scope in _parts(p) for p in op_names), scope


@pytest.mark.parametrize("scope", [scopes.GRAD, scopes.XENT])
def test_scope_in_backward_ops(op_names, scope):
    assert any(scope in _parts(p) for p in op_names
               if "transpose(" in p), scope


@pytest.mark.parametrize("scope", [scopes.ASI_UPKEEP, scopes.ATTENTION])
def test_scope_in_forward_ops(op_names, scope):
    assert any(scope in _parts(p) for p in op_names
               if "jvp(" in p and "transpose(" not in p), scope


def test_recursive_scope_unwinds():
    """A scoped function that calls itself leaves the name stack as it
    found it (the chunked attention recurses over query blocks)."""
    @scopes.scoped(scopes.ATTENTION)
    def f(x, depth):
        return jnp.sin(x) if depth == 0 else f(x, depth - 1)

    def g(x):
        y = f(x, 2)
        return jnp.cos(y)

    text = jax.jit(g).lower(jnp.ones(3)).compile().as_text()
    names = OP_NAME.findall(text)
    cos = [p for p in names if p.endswith("/cos")]
    assert cos and all(scopes.ATTENTION not in _parts(p) for p in cos)
    assert any(_parts(p).count(scopes.ATTENTION) == 3 for p in names)
