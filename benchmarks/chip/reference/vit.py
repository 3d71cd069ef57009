"""Plain reference of ViT-B/16 fine-tuning under WASI's project mode, in
float32.

Written from the published architecture (ViT: patch embedding, a class
token and learned positions, pre-norm encoder layers of LayerNorm,
bidirectional multi-head attention with q/k/v biases and a GELU MLP, a
final LayerNorm and a linear class head on the class token) and from the
method's equations in its project mode (paper Eqs. 9-11 and Alg. 1):

* each MLP linear keeps its dense weight W as the parameter and runs its
  forward through rank-K factors y = (x R^T) L^T, taken at the start by
  a truncated SVD of W (L = U_K S_K, R = V_K^T);
* its input gradient is exact for that forward, dx = (dy L) R, and its
  weight gradient dW = dy^T x~ is taken against x~, the input's Tucker
  projection onto one basis per mode (batch, token, feature), each found
  by one warm-started subspace iteration U <- orth(X X^T U) on the mode's
  unfolding;
* after the optimizer has updated W, one subspace iteration refreshes the
  factors: L <- orth(W orth(W^T orth(L))), R <- L^T W.

The attention's linears, the patch embedding and the head are dense with
exact gradients. It imports nothing of the program: it reads the weight
and state trees the benchmark draws from the seed, the sizes and ranks
from the configuration file, and takes its own SVD of the drawn weights.

Departures from the published model, kept because the program has them:
no biases on the patch embedding, the attention output and the MLP
linears; LayerNorm with eps 1e-5 (published 1e-12); GELU in its tanh form.

``prec`` selects the arithmetic of every matrix product, as in
``reference/qwen2.py`` (``"f32"``: float32 at ``highest``; ``"bf16"``,
``"fp8"``: operands and gradients rounded first).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference import qwen2
from reference.qwen2 import F32, ein, orth

LN_EPS = 1e-5
SITES = ("up", "down")


def layernorm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def dense(prec, p, x):
    y = ein(prec, "bni,oi->bno", x, p["w"])
    return y + p["b"].astype(F32) if "b" in p else y


def tucker_projection(prec, x, us):
    """x (B, N, I) projected onto one basis per mode, each from one
    warm-started subspace iteration from ``us``; and the new bases."""
    new = []
    for m, u in enumerate(us):
        a = jnp.moveaxis(x, m, 0).reshape(x.shape[m], -1)
        new.append(orth(ein(prec, "dr,rk->dk", a,
                            ein(prec, "dr,dk->rk", a, u.astype(F32)))))
    xt = x
    for m, u in enumerate(new):
        t = ein(prec, "...d,dk->...k", jnp.moveaxis(xt, m, -1), u)
        xt = jnp.moveaxis(ein(prec, "...k,dk->...d", t, u), -1, m)
    return xt, tuple(new)


@functools.lru_cache(maxsize=None)
def project_linear(prec: str):
    """y = (x R^T) L^T whose gradient lands on the dense W, taken against
    the projection xt of x; the input gradient is exact."""
    @jax.custom_vjp
    def f(x, w, L, R, xt):
        return ein(prec, "bnk,ok->bno", ein(prec, "bni,ki->bnk", x, R), L)

    def fwd(x, w, L, R, xt):
        return f(x, w, L, R, xt), (L, R, xt)

    def bwd(res, dy):
        L, R, xt = res
        dx = ein(prec, "bnk,ki->bni", ein(prec, "bno,ok->bnk", dy, L), R)
        dw = ein(prec, "bno,bni->oi", dy, xt)
        return dx, dw, jnp.zeros_like(L), jnp.zeros_like(R), \
            jnp.zeros_like(xt)

    f.defvjp(fwd, bwd)
    return f


def attention(prec, m, p, x):
    """Bidirectional multi-head attention over every token."""
    b, n, _ = x.shape
    h = m["num_attention_heads"]
    d = m["hidden_size"] // h
    q, k, v = (dense(prec, p[w], x).reshape(b, n, h, d)
               for w in ("wq", "wk", "wv"))
    sc = ein(prec, "bqhd,bkhd->bhqk", q, k) * d ** -0.5
    o = ein(prec, "bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return dense(prec, p["wo"], o.reshape(b, n, h * d))


def block(prec, m, p, x, factors, st):
    """One encoder layer; ``factors`` and ``st`` map each MLP site to its
    (L, R) and its three activation bases."""
    x = x + attention(prec, m, p["attn"], layernorm(x, p["ln1"]))
    new = {}

    def mlp(name, inp):
        xt, new[name] = tucker_projection(prec, jax.lax.stop_gradient(inp),
                                          st[name])
        L, R = factors[name]
        return project_linear(prec)(inp, p["mlp"][name]["w"].astype(F32),
                                    L, R, xt)

    u = jax.nn.gelu(mlp("up", layernorm(x, p["ln2"])))
    return x + mlp("down", u), new


def loss(prec, cfg, params, factors, states, batch):
    """Mean cross-entropy of the class head, and the new bases."""
    m = cfg["model"]
    x = ein(prec, "bnp,dp->bnd", batch["patches"], params["patch"]["w"])
    cls = jnp.broadcast_to(params["cls"].astype(F32), (x.shape[0], 1,
                                                        x.shape[-1]))
    x = jnp.concatenate([cls, x], 1) + params["pos"].astype(F32)

    @jax.checkpoint
    def body(x, xs):
        p, f, s = xs
        return block(prec, m, p, x, f, s)

    x, new = jax.lax.scan(body, x, (params["blocks"], factors, states))
    x = layernorm(x, params["final_norm"])
    logits = ein(prec, "bd,cd->bc", x[:, 0], params["head"]["w"]) \
        + params["head"]["b"].astype(F32)
    gold = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold), new


def svd_factors(w, k: int):
    """Rank-k truncated SVD of stacked (…, O, I) weights: L = U S, R = V^T,
    taken on the host by LAPACK in float32 (on a TPU an SVD of these sizes
    compiles for minutes)."""
    import numpy as np

    u, s, vt = np.linalg.svd(np.asarray(jax.device_get(w), np.float32),
                             full_matrices=False)
    return jnp.asarray(u[..., :k] * s[..., None, :k]), jnp.asarray(vt[..., :k, :])


def subspace_step(prec, w, L):
    """One warm-started subspace iteration of the factors against W."""
    v = orth(ein(prec, "...oi,...ok->...ik", w, orth(L)))
    L = orth(ein(prec, "...oi,...ik->...ok", w, v))
    return L, ein(prec, "...ok,...oi->...ki", L, w)


# the encoder layers' leaves are stacked under ``blocks``
leaf_norms = functools.partial(qwen2.leaf_norms, stacked="blocks")


def train_reference(prec, cfg, opt, params, states, batches):
    """The method's first steps from ``params`` and the activation bases
    ``states`` on ``batches`` with AdamW on clipped gradients, as ``opt``
    states them. Returns the loss of every step, the leaf norms of the
    first clipped gradient, and those of the parameters' change over all
    the steps."""
    p0 = jax.tree.map(lambda x: x.astype(F32), params)
    st = {n: tuple(u.astype(F32) for u in states["mlp"][n].us)
          for n in SITES}
    sites = cfg["plan"]["sites"]
    factors = {n: svd_factors(p0["blocks"]["mlp"][n]["w"],
                              sites[f"mlp/{n}"]["rank"]) for n in SITES}
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["clip_norm"]

    def one(p, factors, st, mu, nu, t, batch):
        (l, new), g = jax.value_and_grad(
            lambda q: loss(prec, cfg, q, factors, st, batch), has_aux=True)(p)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / gn), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(lambda q, m, v: q - lr * (
            m / c1 / (jnp.sqrt(v / c2) + eps) + wd * q), p, mu, nu)
        factors = {n: subspace_step(prec, p["blocks"]["mlp"][n]["w"],
                                    factors[n][0]) for n in SITES}
        return p, factors, new, mu, nu, l, g

    step = jax.jit(one)
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p, mu, nu = p0, zeros, zeros
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        p, factors, st, mu, nu, l, g = step(p, factors, st, mu, nu,
                                            float(t), batch)
        losses.append(float(l))
        if g1 is None:
            g1 = jax.device_get(leaf_norms(g))
        del g
    change = jax.device_get(leaf_norms(jax.tree.map(jnp.subtract, p, p0)))
    return {"losses": losses, "grad": g1, "change": change}
