"""From a configuration file to the program's model: its ``ModelConfig``,
its resolved plan (checked against the ranks the file pins), and weights
and activation-subspace states drawn from the seed.

The weights are the benchmark's, not the program's: one jitted call fills
the program's parameter layout (read with ``jax.eval_shape``, which makes
nothing) leaf by leaf from the seed, on the device, in the type they are
served in. The plain references draw the same tree from the same seed.
"""
from __future__ import annotations

import zlib

import harness


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import AsiConfig, LayerGroup, ModelConfig, WasiConfig

    m, prog, w = cfg["model"], cfg["program"], cfg["wasi"]
    wasi = WasiConfig(**{k: v for k, v in w.items() if k != "asi"},
                      asi=AsiConfig(**w["asi"]))
    return ModelConfig(
        name=cfg["name"], family=prog["family"],
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        head_dim=m["head_dim"], qkv_bias=m["attention_bias"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        mlp_act=prog["mlp_act"], norm=prog["norm"],
        groups=(LayerGroup(pattern=(prog["block"],),
                           repeat=m["num_hidden_layers"]),),
        wasi=wasi, dtype=prog["dtype"], remat=prog["remat"])


def install_plan(cfg: dict, mcfg, *, batch: int | None = None,
                 seq: int | None = None):
    """Resolve and install the plan; fail where a site's rank (or, with a
    training shape, its activation-subspace ranks) differs from the file."""
    from repro import api

    plan = api.resolve(mcfg, batch=batch, seq=seq)
    pinned = cfg["plan"]["sites"]
    got = {s.name: s for s in plan.specs}
    for name, want in pinned.items():
        s = got[name]
        have = {"in": s.in_dim, "out": s.out_dim, "rank": s.rank,
                "bias": s.bias}
        if have != want:
            raise RuntimeError(f"plan site {name}: program resolves {have}, "
                               f"the configuration pins {want}")
    key = f"asi_ranks_seq{seq}"
    if seq and key in cfg["plan"]:
        for name, want in cfg["plan"][key].items():
            have = list(got[name].asi_ranks[1:])
            if have != want:
                raise RuntimeError(f"plan site {name}: activation ranks "
                                   f"{have}, the configuration pins {want}")
    if cfg["plan"]["padded_vocab"] != mcfg.padded_vocab:
        raise RuntimeError("padded vocabulary differs from the configuration")
    api.uninstall(mcfg)
    return api.install(plan)


def _path(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx",
                                                    getattr(p, "name", p)))))
    return "/".join(parts)


def _fill(key, path: str, shape, dtype):
    """One leaf of the weight tree, by its name: norm scales 1, biases and
    the embedding N(0, 0.02), factors L ~ N(0, 1/K) and R, W ~ N(0, 1/I)
    (each factored linear keeps its input's scale)."""
    import jax
    import jax.numpy as jnp

    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return jnp.ones(shape, dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "b" or path.startswith("embed") or path.startswith("lm_head"):
        std = 0.02
    else:        # L (O, K), R (K, I), dense w (O, I): 1/sqrt(fan-in)
        std = shape[-1] ** -0.5
    return (z * std).astype(dtype)


def _orthonormal(key, shape, dtype):
    """Stacked (…, D, r) random matrices with orthonormal columns."""
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(key, shape, jnp.float32)
    q, _ = jnp.linalg.qr(z)
    return q.astype(dtype)


def _draw(template, key, fill):
    import jax

    flat, tree = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        p = _path(path)
        k = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
        leaves.append(fill(k, p, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, leaves)


def params_template(mcfg):
    import jax
    import jax.numpy as jnp

    from repro.models.lm import init_lm

    return jax.eval_shape(lambda k: init_lm(k, mcfg, jnp.dtype(mcfg.dtype)),
                          jax.random.PRNGKey(0))


def states_template(mcfg, batch: int, seq: int):
    import jax
    import jax.numpy as jnp

    from repro.models.lm import init_lm_states

    return jax.eval_shape(
        lambda k: init_lm_states(k, mcfg, batch, seq, jnp.dtype(mcfg.dtype)),
        jax.random.PRNGKey(0))


def draw_params(seed: int, template):
    """The weight tree for ``seed`` in one jitted call on the device."""
    import jax

    return jax.jit(lambda k: _draw(template, k, _fill))(
        harness.jax_key(seed, 1))


def draw_states(seed: int, template):
    """Activation-subspace warm starts for ``seed``: random orthonormal
    factors, as the method starts them."""
    import jax

    return jax.jit(lambda k: _draw(
        template, k, lambda kk, p, shape, dt: _orthonormal(kk, shape, dt)))(
        harness.jax_key(seed, 2))
