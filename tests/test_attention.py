"""Attention module invariants: chunked==dense, rolling cache correctness,
decode==prefill consistency, flash-decode partials, the flash kernel's
route and its gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import MeshPolicy
from repro.kernels import ops
from repro.nn.attention import (
    KVCache,
    attention_route,
    cache_update,
    chunked_attention,
    decode_attention,
    dense_attention,
)

KEY = jax.random.PRNGKey(0)


def _qkv(b=2, s=64, h=4, kvh=2, dh=16, key=KEY):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (b, s, h, dh)),
            jax.random.normal(ks[1], (b, s, kvh, dh)),
            jax.random.normal(ks[2], (b, s, kvh, dh)))


def test_chunked_equals_dense():
    q, k, v = _qkv(s=100)
    for causal, window in [(True, 0), (True, 24), (False, 0)]:
        d = dense_attention(q, k, v, causal=causal, window=window)
        c = chunked_attention(q, k, v, causal=causal, window=window, chunk=32)
        np.testing.assert_allclose(np.asarray(c), np.asarray(d), rtol=1e-4,
                                   atol=1e-4)


def test_decode_matches_full_attention():
    """Token-by-token decode over a full cache == row i of dense attention."""
    b, s, h, kvh, dh = 1, 16, 4, 2, 8
    q, k, v = _qkv(b, s, h, kvh, dh)
    full = dense_attention(q, k, v, causal=True)
    cache = KVCache(k=jnp.zeros((b, s, kvh, dh)), v=jnp.zeros((b, s, kvh, dh)))
    for t in range(s):
        cache = cache_update(cache, k[:, t:t + 1], v[:, t:t + 1], t)
        o = decode_attention(q[:, t:t + 1], cache, t)
        np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)


def test_rolling_cache_equals_full_cache_with_window():
    """Rolling W-slot cache == full cache + window mask (the long_500k
    memory trick must not change results)."""
    b, s, h, kvh, dh, w = 1, 40, 2, 2, 8, 8
    q, k, v = _qkv(b, s, h, kvh, dh)
    full = KVCache(k=jnp.zeros((b, s, kvh, dh)), v=jnp.zeros((b, s, kvh, dh)))
    roll = KVCache(k=jnp.zeros((b, w, kvh, dh)), v=jnp.zeros((b, w, kvh, dh)))
    for t in range(s):
        full = cache_update(full, k[:, t:t + 1], v[:, t:t + 1], t)
        roll = cache_update(roll, k[:, t:t + 1], v[:, t:t + 1], t, window=w)
        o_full = decode_attention(q[:, t:t + 1], full, t, window=w)
        o_roll = decode_attention(q[:, t:t + 1], roll, t, window=w)
        np.testing.assert_allclose(np.asarray(o_roll), np.asarray(o_full),
                                   rtol=1e-4, atol=1e-4)


def test_window_masks_out_distant_tokens():
    """With window=1 each token attends only to itself."""
    q, k, v = _qkv(s=8)
    out = dense_attention(q, k, v, causal=True, window=1)
    # manual self-attention value: softmax over single element = v itself
    g = q.shape[2] // k.shape[2]
    vr = v[:, :, jnp.arange(q.shape[2]) // g, :]
    np.testing.assert_allclose(np.asarray(out), np.asarray(vr), rtol=1e-4,
                               atol=1e-4)


def test_flash_decode_partials_match_dense():
    """distributed/collectives._local_partials combined across two manual
    shards == full softmax attention (the psum algebra)."""
    from repro.distributed.collectives import _local_partials

    b, s, h, dh = 1, 32, 4, 8
    q = jax.random.normal(KEY, (b, h, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, h, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, h, dh))
    valid = jnp.arange(s) <= 20

    # full reference
    m, l, acc = _local_partials(q, k, v, valid)
    want = acc / l[..., None]

    # two shards combined with the flash-decode algebra
    m1, l1, a1 = _local_partials(q, k[:, :16], v[:, :16], valid[:16])
    m2, l2, a2 = _local_partials(q, k[:, 16:], v[:, 16:], valid[16:])
    mg = jnp.maximum(m1, m2)
    s1, s2 = jnp.exp(m1 - mg), jnp.exp(m2 - mg)
    lg = l1 * s1 + l2 * s2
    ag = a1 * s1[..., None] + a2 * s2[..., None]
    got = ag / lg[..., None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# bf16 keeps 8 significant bits: one rounding of an operand moves it by at
# most U relative
U = 2.0 ** -8


@pytest.mark.parametrize("mxu", ["float32", "bfloat16"], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "s,h,kvh,causal,window,dtype",
    [(256, 7, 1, True, 0, jnp.float32),      # G=7, two tiles each way
     (200, 2, 2, True, 0, jnp.float32),      # G=1, ragged: padded to 256
     (256, 2, 2, True, 96, jnp.float32),     # windowed
     (200, 14, 2, True, 48, jnp.bfloat16),   # G=7, windowed, ragged
     (256, 14, 2, True, 0, jnp.bfloat16),
     (200, 4, 2, False, 0, jnp.float32),
     (256, 4, 2, False, 0, jnp.float32)],
    ids=["g7", "g1-ragged", "window", "g7-window-ragged-bf16", "g7-bf16",
         "bidirectional-ragged", "bidirectional"])
def test_flash_matches_dense_and_chunked(monkeypatch, s, h, kvh, causal,
                                         window, dtype, mxu):
    """The flash route's output and its dq/dk/dv (jax.grad of a scalar
    loss) against dense attention and the chunked scan, head_dim 64, at
    128-token tiles (interpret mode). ``mxu`` "f32" is the kernel as it runs
    off TPU, held to f32 rounding; "bf16" casts every matmul operand to
    bf16, as on TPU. That, or bf16 inputs, is held to 4 roundings of U
    relative to the largest reference value: a score takes two rounded
    operands, the combine a rounded probability and a rounded value (with
    bf16 inputs: the cotangent, the saved output and the result)."""
    monkeypatch.setattr(ops, "FLASH_BLOCK", 128)
    mxu_dtype = jnp.dtype(mxu)
    monkeypatch.setattr(ops, "_attention_mxu_dtype", lambda: mxu_dtype)
    q, k, v = (x.astype(dtype) for x in _qkv(1, s, h, kvh, 64))
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (1, s, h, 64))

    def value_and_grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    kw = dict(causal=causal, window=window)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    got = (ops.flash_attention(q, k, v, **kw),
           *value_and_grads(lambda *a: ops.flash_attention(*a, **kw))[1])
    assert [x.dtype for x in got] == [dtype] * 4
    refs = [dense_attention] + ([chunked_attention] if s > 128 else [])
    for ref in refs:
        attn = (lambda q, k, v, ref=ref: ref(q.astype(jnp.float32),
                                             k.astype(jnp.float32),
                                             v.astype(jnp.float32), **kw))
        want = (attn(*f32), *value_and_grads(attn)[1])
        for name, a, b in zip(["o", "dq", "dk", "dv"], got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            tol = 4 * U if jnp.bfloat16 in (dtype, mxu_dtype) else 1e-5
            err = np.abs(a - b).max() / np.abs(b).max()
            assert err <= tol, (ref.__name__, name, err / U)


def _inside_shard_map(fn):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    out = []
    jax.jit(jax.shard_map(lambda x: (out.append(fn()), x)[1], mesh=mesh,
                          in_specs=P("data"), out_specs=P("data")))(
        jnp.ones(1))
    return out[0]


@pytest.mark.parametrize(
    "kernels,sq,sk,dh,policy,where,route",
    [(True, 2048, 2048, 64, None, "jit", "flash"),       # the train cell
     (True, 8192, 8192, 64, None, "jit", "flash"),       # past 2048 too
     (True, 2048, 2048, 128, None, "jit", "flash"),
     (True, 197, 197, 64, None, "jit", "dense"),         # ViT-Base
     (True, 448, 1500, 64, None, "jit", "dense"),        # cross-attention
     (True, 2048, 2048, 80, None, "jit", "dense"),       # head dim
     (True, 2048, 2048, 64, MeshPolicy(), "jit", "dense"),  # GSPMD
     (True, 2048, 2048, 64, MeshPolicy(), "shard_map", "flash"),
     (False, 2048, 2048, 64, None, "jit", "dense"),      # off TPU
     (False, 8192, 8192, 64, None, "jit", "chunked")],
    ids=["cell", "long", "dh128", "vit", "cross", "dh80", "gspmd",
         "shard-map", "cpu", "cpu-long"])
def test_attention_route(monkeypatch, kernels, sq, sk, dh, policy, where,
                         route):
    """The full-sequence route, from shapes, backend and sharding alone."""
    monkeypatch.setattr(ops, "INTERPRET", not kernels)

    def pick():
        return attention_route(sq, sk, dh, policy=policy)

    assert (_inside_shard_map(pick) if where == "shard_map"
            else pick()) == route
