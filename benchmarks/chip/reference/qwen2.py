"""Plain reference of the qwen2 family under WASI, in float32.

Written from the published architecture (Qwen2: pre-norm decoder, RMSNorm,
grouped-query attention with rotary positions and q/k/v biases, SwiGLU MLP,
tied embedding head) and from the method's equations (factored linears
y = (x R^T) L^T; for training, each linear's saved input is replaced by its
rank-(r_token, r_feature) Tucker projection, found by one warm-started
subspace iteration per mode, and the weight gradients are taken against
that projection). It imports nothing of the program: it reads the weight
and state trees the benchmark draws from the seed, and the sizes from the
configuration file.

Departures from the published model, kept because the program has them:
the vocabulary table is padded to a multiple of 256 rows and the softmax
runs over the padded table.

``prec`` selects the arithmetic of every matrix product: ``"f32"`` is
float32 at ``highest`` precision; the controls round both operands (and,
on the way back, the gradient) first: ``"bf16"`` to bfloat16, ``"fp8"``
to float8, e4m3 forward and e5m2 backward, each scaled by its largest
magnitude.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    """Per-tensor scaled float8 rounding, as fp8 training does it: e4m3 on
    the way forward, e5m2 for the gradient on the way back."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_round(g, jnp.float8_e5m2, E5M2_MAX),))


@jax.custom_vjp
def _bf16(x):
    """bfloat16 rounding, of the value and of its gradient."""
    return x.astype(jnp.bfloat16).astype(F32)


_bf16.defvjp(lambda x: (_bf16(x), None),
             lambda _, g: (g.astype(jnp.bfloat16).astype(F32),))

ROUND = {"fp8": _fp8, "bf16": _bf16}


def ein(prec: str, spec: str, a, b):
    a, b = a.astype(F32), b.astype(F32)
    if prec in ROUND:
        a, b = ROUND[prec](a), ROUND[prec](b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, pos, theta):
    """Half-rotation rotary embedding. x (B, N, H, D), pos (N,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def orth(m):
    return jnp.linalg.qr(m)[0]


def subspace(prec, x, ut, uf):
    """One warm-started subspace iteration per mode on x (B, N, I): the
    token basis spans A A^T U_t of the token unfolding, the feature basis
    that of the feature unfolding. Returns the projection of x onto both
    and the new bases."""
    ut2 = orth(ein(prec, "bni,bir->nr", x, ein(prec, "bni,nr->bir", x, ut)))
    uf2 = orth(ein(prec, "bni,bnr->ir", x, ein(prec, "bni,ir->bnr", x, uf)))
    xt = ein(prec, "nr,bri->bni", ut2, ein(prec, "nr,bni->bri", ut2, x))
    xt = ein(prec, "ir,bnr->bni", uf2, ein(prec, "ir,bni->bnr", uf2, xt))
    return xt, ut2, uf2


@functools.lru_cache(maxsize=None)
def compressed_linear(prec: str):
    """y = (x R^T) L^T whose weight gradients use the projection xt of x
    in place of x; the input gradient is exact."""
    @jax.custom_vjp
    def f(x, L, R, xt):
        return ein(prec, "bnk,ok->bno", ein(prec, "bni,ki->bnk", x, R), L)

    def fwd(x, L, R, xt):
        return f(x, L, R, xt), (L, R, xt)

    def bwd(res, dy):
        L, R, xt = res
        dh = ein(prec, "bno,ok->bnk", dy, L)
        dx = ein(prec, "bnk,ki->bni", dh, R)
        ht = ein(prec, "bni,ki->bnk", xt, R)
        dL = ein(prec, "bno,bnk->ok", dy, ht)
        dR = ein(prec, "bnk,bni->ki", dh, xt)
        return dx, dL, dR, jnp.zeros_like(xt)

    f.defvjp(fwd, bwd)
    return f


def linear(prec, p, x, st):
    """One factored linear; with a state ``st`` (token basis, feature
    basis) its weight gradients see the compressed input."""
    L, R = p["L"].astype(F32), p["R"].astype(F32)
    if st is None:
        y = ein(prec, "bnk,ok->bno", ein(prec, "bni,ki->bnk", x, R), L)
        new = None
    else:
        xt, ut, uf = subspace(prec, jax.lax.stop_gradient(x),
                              st[0].astype(F32), st[1].astype(F32))
        y = compressed_linear(prec)(x, L, R, xt)
        new = (ut, uf)
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y, new


def attention(prec, q, k, v, q_chunk: int):
    """Causal grouped-query attention, q (B, N, H, D), k/v (B, N, KVH, D),
    over blocks of ``q_chunk`` queries."""
    b, n, h, d = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for s in range(0, n, q_chunk):
        qs = q[:, s:s + q_chunk]
        e = min(n, s + q_chunk)
        sc = ein(prec, "bqhd,bkhd->bhqk", qs, k[:, :e]) * d ** -0.5
        ok = jnp.arange(e)[None, :] <= (s + jnp.arange(qs.shape[1]))[:, None]
        sc = jnp.where(ok, sc, -jnp.inf)
        outs.append(ein(prec, "bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                        v[:, :e]))
    return jnp.concatenate(outs, 1)


def block(prec, m, p, x, st, q_chunk):
    """One decoder layer. ``st`` maps each site to its two bases, or None."""
    b, n, _ = x.shape
    h, kvh, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(n)
    new = {}

    def lin(group, name, inp):
        s = None if st is None else st[group][name]
        y, ns = linear(prec, p[group][name], inp, s)
        new.setdefault(group, {})[name] = ns
        return y

    a = rmsnorm(x, p["ln1"]["scale"], eps)
    q = rope(lin("attn", "wq", a).reshape(b, n, h, d), pos, theta)
    k = rope(lin("attn", "wk", a).reshape(b, n, kvh, d), pos, theta)
    v = lin("attn", "wv", a).reshape(b, n, kvh, d)
    o = attention(prec, q, k, v, q_chunk).reshape(b, n, h * d)
    x = x + lin("attn", "wo", o)
    a = rmsnorm(x, p["ln2"]["scale"], eps)
    u = jax.nn.silu(lin("mlp", "gate", a)) * lin("mlp", "up", a)
    x = x + lin("mlp", "down", u)
    return x, new


def _layer_params(params):
    return params["groups"][0][0]


def _layer_states(states):
    """{group: {site: (token basis, feature basis)}} stacked over layers,
    from the state tree (each site's state holds its per-mode bases, the
    batch mode kept whole)."""
    s = states[0][0]
    return {g: {name: (st[0][1], st[0][2]) for name, st in sites.items()}
            for g, sites in s.items()}


def hidden(prec, cfg, params, tokens, states=None, q_chunk=512):
    """Final normed hidden states (B, N, d) and the new bases."""
    m = cfg["model"]
    x = params["embed"]["w"].astype(F32)[tokens]
    lp = _layer_params(params)
    ls = None if states is None else _layer_states(states)

    @jax.checkpoint
    def body(x, xs):
        p, s = xs
        x, new = block(prec, m, p, x, s, q_chunk)
        return x, (new if s is not None else 0)

    x, new = jax.lax.scan(body, x, (lp, ls) if ls is not None
                          else (lp, None))
    return rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"]), new


def loss(prec, cfg, params, tokens, labels, states, v_chunk=256):
    """Mean next-token cross-entropy over every position, the head applied
    in blocks of ``v_chunk`` positions."""
    x, new = hidden(prec, cfg, params, tokens, states)
    emb = params["embed"]["w"]
    b, n, dm = x.shape
    v_chunk = min(v_chunk, n)
    xs = x.reshape(b, n // v_chunk, v_chunk, dm).swapaxes(0, 1)
    ls = labels.reshape(b, n // v_chunk, v_chunk).swapaxes(0, 1)

    @jax.checkpoint
    def ce(tot, xl):
        xc, lc = xl
        lg = ein(prec, "bnd,vd->bnv", xc, emb)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lc[..., None], -1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(ce, jnp.zeros((), F32), (xs, ls))
    return tot / (b * n), new


def leaf_norms(tree, xp=jnp, stacked: str = "groups") -> dict:
    """Norm of every weight leaf, the leaves stacked over layers (under
    ``stacked``) one per layer: {path: vector of norms}. ``xp`` is ``jnp``
    (in float32, on the device) or ``numpy`` (in the leaves' own type, on
    the host)."""
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, x in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        if xp is jnp:
            x = x.astype(F32)
        if name.startswith(stacked):
            out[name] = xp.sqrt(xp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = xp.sqrt(xp.sum(x * x))[None]
    return out


def train_reference(prec, cfg, opt, params, states, batches):
    """The method's first steps from ``params`` and ``states`` on
    ``batches`` with AdamW on clipped gradients, as ``opt`` states them.
    Returns the loss of every step, the leaf norms of the first clipped
    gradient, and those of the parameters' change over all the steps."""
    p0 = jax.tree.map(lambda x: x.astype(F32), params)
    st = jax.tree.map(lambda x: x.astype(F32), states)
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["clip_norm"]

    def one(p, st, mu, nu, t, batch):
        (l, new), g = jax.value_and_grad(
            lambda q: loss(prec, cfg, q, batch["tokens"], batch["labels"], st),
            has_aux=True)(p)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / gn), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(lambda q, m, v: q - lr * (
            m / c1 / (jnp.sqrt(v / c2) + eps) + wd * q), p, mu, nu)
        st = _restack(st, new)
        return p, st, mu, nu, l, g

    step = jax.jit(one)
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p, mu, nu = p0, zeros, zeros
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        p, st, mu, nu, l, g = step(p, st, mu, nu, float(t), batch)
        losses.append(float(l))
        if g1 is None:
            g1 = jax.device_get(leaf_norms(g))
        del g
    change = jax.device_get(leaf_norms(jax.tree.map(jnp.subtract, p, p0)))
    return {"losses": losses, "grad": g1, "change": change}


def _restack(states, new):
    """Write the new bases back into the state tree's layout."""
    s = states[0][0]
    out = {}
    for g, sites in s.items():
        out[g] = {}
        for name, stt in sites.items():
            ut, uf = new[g][name]
            us = stt[0]
            out[g][name] = type(stt)((us[0], ut.astype(us[1].dtype),
                                      uf.astype(us[2].dtype)))
    return [[out]]
