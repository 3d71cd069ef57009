"""From a configuration file to the program's model: its ``ModelConfig``,
its resolved plan (checked against the ranks the file pins), and weights
and activation-subspace states drawn from the seed.

The configuration file says everything particular to its model (the keys
are listed in ``harness.py``): the program's family (whose module under
``families/`` holds its templates, loss, batches and operation count),
the layer pattern, sub-configurations and other ``ModelConfig`` fields,
and the rule that draws each leaf. Absent keys give the uniform decoder
stack of ``program.block`` with the default rules below.

The weights are the benchmark's, not the program's: one jitted call fills
the program's parameter layout (read with ``jax.eval_shape``, which makes
nothing) leaf by leaf from the seed, on the device, in the type they are
served in. The plain references draw the same tree from the same seed.
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import harness


def family_name(cfg: dict) -> str:
    return cfg["program"].get("family", "lm")


def family(cfg: dict):
    """The module ``families/<program.family>.py``: the program's templates
    and loss for the family, what a batch holds, a step's operations."""
    path = harness.HERE / "families" / f"{family_name(cfg)}.py"
    if not path.exists():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise ValueError(f"{cfg['name']}: unknown program.family "
                         f"{family_name(cfg)!r}; known: {known}")
    return harness.load_module(path)


def layer_groups(cfg: dict) -> list[dict]:
    """The layer groups, ``[{"pattern": [kind, ...], "repeat": n}, ...]``:
    ``program.groups`` or one uniform group of ``program.block``; their
    layers must sum to the model's depth."""
    prog, n = cfg["program"], cfg["model"]["num_hidden_layers"]
    spec = prog.get("groups") or [{"pattern": [prog["block"]], "repeat": n}]
    layers = sum(len(g["pattern"]) * g["repeat"] for g in spec)
    if layers != n:
        raise ValueError(f"{cfg['name']}: program.groups hold {layers} layers, "
                         f"the model has num_hidden_layers {n}")
    return spec


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import (AsiConfig, LayerGroup, ModelConfig, MoeConfig,
                              SsmConfig, WasiConfig)

    m, prog, w = cfg["model"], cfg["program"], cfg["wasi"]
    wasi = WasiConfig(**{k: v for k, v in w.items() if k != "asi"},
                      asi=AsiConfig(**w["asi"]))
    heads = m["num_attention_heads"]
    kw = dict(
        name=cfg["name"], family=family_name(cfg),
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=heads, n_kv_heads=m.get("num_key_value_heads", heads),
        d_ff=m["intermediate_size"], vocab_size=m.get("vocab_size", 0),
        head_dim=m.get("head_dim", m["hidden_size"] // heads),
        qkv_bias=m.get("attention_bias", m.get("qkv_bias", False)),
        tie_embeddings=m.get("tie_word_embeddings", False),
        mlp_act=prog["mlp_act"], norm=prog["norm"], groups=tuple(
            LayerGroup(pattern=tuple(g["pattern"]), repeat=g["repeat"])
            for g in layer_groups(cfg)),
        wasi=wasi, dtype=prog["dtype"], remat=prog["remat"])
    if "rope_theta" in m:
        kw["rope_theta"] = m["rope_theta"]
    if "ssm" in prog:
        kw["ssm"] = SsmConfig(**prog["ssm"])
    if "moe" in prog:
        kw["moe"] = MoeConfig(**prog["moe"])
    fields = prog.get("fields", {})
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(fields) - known)
    if unknown:
        raise ValueError(f"{cfg['name']}: program.fields {unknown} are not "
                         "ModelConfig fields")
    kw.update(fields)
    return ModelConfig(**kw)


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
    if cfg["plan"].get("padded_vocab", mcfg.padded_vocab) != mcfg.padded_vocab:
        raise RuntimeError("padded vocabulary differs from the configuration")
    api.uninstall(mcfg)
    return api.install(plan)


def params_template(cfg: dict, mcfg):
    import jax
    import jax.numpy as jnp

    fam = family(cfg)
    return jax.eval_shape(
        lambda k: fam.init_params(cfg, mcfg, k, jnp.dtype(mcfg.dtype)),
        jax.random.PRNGKey(0))


def states_template(cfg: dict, mcfg, batch: int, seq: int):
    """Activation-subspace states for ``batch`` rows of ``seq`` tokens."""
    import jax
    import jax.numpy as jnp

    fam = family(cfg)
    return jax.eval_shape(
        lambda k: fam.init_states(cfg, mcfg, k, batch, seq,
                                  jnp.dtype(mcfg.dtype)),
        jax.random.PRNGKey(0))


def _path(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx",
                                                    getattr(p, "name", p)))))
    return "/".join(parts)


def _default(key, path: str, shape, dtype):
    """A leaf no ``init`` rule names: norm scales 1, biases and the
    embedding N(0, 0.02), factors L ~ N(0, 1/K) and R, W ~ N(0, 1/I)
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


def _by_rule(key, rule: dict, shape, dtype):
    """One leaf drawn by an ``init`` rule of the configuration file:

    * ``{"rule": "ones"}``, ``{"rule": "zeros"}``;
    * ``{"rule": "normal", "std": s}``: N(0, s^2);
    * ``{"rule": "log_uniform", "range": [lo, hi]}``: log(U(lo, hi)), as
      Mamba's ``A_log``;
    * ``{"rule": "inv_softplus_log_uniform", "range": [lo, hi]}``: the
      inverse softplus of dt = exp(U(log lo, log hi)), as Mamba's
      ``dt_bias`` (softplus of the leaf gives dt back).
    """
    import jax
    import jax.numpy as jnp

    kind = rule["rule"]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "normal":
        z = jax.random.normal(key, shape, jnp.float32) * rule["std"]
    elif kind == "log_uniform":
        lo, hi = rule["range"]
        z = jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    elif kind == "inv_softplus_log_uniform":
        lo, hi = rule["range"]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        z = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init rule {kind!r}")
    return z.astype(dtype)


def rule_for(init: dict, path: str) -> dict | None:
    """The ``init`` rule for a leaf: the longest key that is the leaf's
    path or a tail of it at a ``/`` ("bias" names every ``.../bias``,
    "head/w" the head's weight alone)."""
    best = None
    for name in init:
        if (path == name or path.endswith("/" + name)) and \
                (best is None or len(name) > len(best)):
            best = name
    return None if best is None else init[best]


def filler(init: dict):
    """``fill(key, path, shape, dtype)`` drawing by ``init``'s rules and,
    for leaves it does not name, by the default ones."""
    def fill(key, path, shape, dtype):
        rule = rule_for(init, path)
        if rule is None:
            return _default(key, path, shape, dtype)
        return _by_rule(key, rule, shape, dtype)
    return fill


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


def draw_params(seed: int, cfg: dict, mcfg):
    """The configuration's weight tree for ``seed`` in one jitted call on
    the device."""
    import jax

    template, fill = params_template(cfg, mcfg), filler(cfg.get("init", {}))
    return jax.jit(lambda k: _draw(template, k, fill))(
        harness.jax_key(seed, 1))


def draw_states(seed: int, template):
    """Activation-subspace warm starts for ``seed``: random orthonormal
    factors, as the method starts them."""
    import jax

    return jax.jit(lambda k: _draw(
        template, k, lambda kk, p, shape, dt: _orthonormal(kk, shape, dt)))(
        harness.jax_key(seed, 2))
