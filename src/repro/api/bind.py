"""Plan-driven linear init/apply + the ONLY place allowed to look at raw
param-dict keys.

``init_params`` / ``apply`` replace the cfg-threaded ``nn.linear``
entry points: dispatch is on the typed :class:`~repro.api.plan.LinearSpec`
(mode, rank, kernel route), not on ``"L" in p`` sniffing. Param layouts are
unchanged plain pytrees:

    dense:    {"w": (O, I) [, "b"]}
    factored: {"L": (O, K), "R": (K, I) [, "b"]}
    project:  {"w": (O, I) [, "L", "R"]}   (factors injected per-step by
              core/project.py, or carried by a converted checkpoint)

What each path saves for backward is unchanged (the sketch-saving contract,
docs/training.md): Tucker x~ + rank-K sketch for WASI, x + dense sketch via
the fused kernel for factored-no-ASI, dense x for vanilla.

Everything else in the tree that must walk param structure by key
(factored-refresh mapping, project-factor injection/extraction, legacy
param inspection) lives here too, so no other module dispatches on keys.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.api.plan import (
    LinearSpec,
    SubspacePlan,
    _act_mode_ranks,
    resolve_linear_spec,
    role_treated,
)
from repro.config import WasiConfig
from repro.core.asi import ASIState, asi_init, asi_project, asi_step
from repro.core.lowrank_linear import (
    asi_matmul,
    wasi_matmul,
    wasi_matmul_project,
    wsi_matmul_project_exact,
)
from repro.core.wsi import WSIState
from repro.utils.scopes import MATMUL


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, spec: LinearSpec, *, dtype=jnp.float32,
                scale: float | None = None, bias: bool | None = None) -> dict:
    """Random init for one linear site, in the layout its spec dictates.
    RNG consumption matches the historical ``nn.linear.init_linear`` so
    seeded runs reproduce across the API change."""
    std = scale if scale is not None else spec.in_dim ** -0.5
    with_bias = spec.bias if bias is None else bias
    kw, kb = jax.random.split(key)
    p: dict = {}
    if spec.mode == "factored":
        k = spec.rank
        kl, kr = jax.random.split(kw)
        split = (std / k ** 0.5) ** 0.5
        p["L"] = (jax.random.normal(kl, (spec.out_dim, k), jnp.float32)
                  * split).astype(dtype)
        p["R"] = (jax.random.normal(kr, (k, spec.in_dim), jnp.float32)
                  * split).astype(dtype)
    else:
        # project mode inits DENSE; its (L, R) live in WSI states (train) or
        # arrive via convert.factorize (checkpoints)
        p["w"] = (jax.random.normal(kw, (spec.out_dim, spec.in_dim),
                                    jnp.float32) * std).astype(dtype)
    if with_bias:
        p["b"] = jnp.zeros((spec.out_dim,), dtype)
    return p


def asi_state(key, act_shape: Sequence[int], wasi: WasiConfig,
              dtype=jnp.float32) -> ASIState | None:
    """Warm-start ASI state for a linear whose input activation has
    ``act_shape`` (B, N, I) or (B, H, W, I). None if compression is off."""
    if not wasi.compress_acts:
        return None
    ranks = _act_mode_ranks(tuple(act_shape), wasi)
    return asi_init(key, act_shape, ranks, dtype)


def init_state(key, spec: LinearSpec, act_shape: Sequence[int],
               wasi: WasiConfig, dtype=jnp.float32) -> ASIState | None:
    """Per-spec ASI warm-start state; None when this site's activations
    stay dense under the plan."""
    if not (wasi.compress_acts and role_treated(wasi, spec.role)):
        return None
    return asi_state(key, act_shape, wasi, dtype)


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def apply(spec: LinearSpec, p: dict, x: jax.Array, wasi: WasiConfig,
          state: ASIState | None = None):
    """Apply one linear site per its spec. Returns (y, new_state) —
    new_state is None when no ASI state is involved."""
    new_state = None

    def compress(x_):
        if wasi.asi.frozen:
            return asi_project(jax.lax.stop_gradient(x_), state), state
        return asi_step(jax.lax.stop_gradient(x_), state)

    if is_quantized(p):
        # int8 deployment path (plan.quantized + convert.quantize): weights
        # are {L,sL,R,sR} / {w,sW}; scales fold into the matmul, and the
        # fused int8 kernel keeps factors VMEM-resident on TPU
        if spec.quant is None and spec.draft != "int8":
            # A draft-stamped spec legitimately sees BOTH layouts: f32
            # master params on the verify pass, int8-packed draft params
            # on the draft pass (serve/engine.py builds the latter).
            raise ValueError(
                f"site {spec.name}: params are quantized but the spec is "
                "not — serve under plan.quantized(...) (docs/deployment.md)")
        if state is not None:
            raise ValueError(
                f"site {spec.name}: quantized params are serve-only; ASI "
                "states cannot thread through an int8 site")
        from repro.kernels.ops import dense_matmul_q8, lowrank_matmul_q8
        if "L" in p:
            y = lowrank_matmul_q8(x, p["R"], p["sR"], p["L"], p["sL"])
        else:
            y = dense_matmul_q8(x, p["w"], p["sW"])
    elif spec.quant is not None:
        raise ValueError(
            f"site {spec.name}: plan stamps quant={spec.quant!r} but the "
            "params are not packed — run convert.quantize(params, plan)")
    elif spec.mode == "project" and "L" in p:
        # factored forward, dense-W gradient (paper Eq. 9-11); factors come
        # from the per-step WSI injection or a converted checkpoint
        if state is not None:
            xt, new_state = compress(x)
            y = wasi_matmul_project(x, p["w"], p["L"], p["R"], xt)
        else:
            y = wsi_matmul_project_exact(x, p["w"], p["L"], p["R"])
    elif spec.mode == "factored":
        if state is not None:
            xt, new_state = compress(x)
            y = wasi_matmul(x, p["L"], p["R"], xt)
        else:
            # no-ASI factored path (serving, `wsi` factored training)
            with jax.named_scope(MATMUL):
                if spec.kernel == "fused_lowrank":
                    # fused Pallas kernel on TPU, XLA einsum pair elsewhere
                    from repro.kernels.ops import lowrank_matmul
                    y = lowrank_matmul(x, p["R"], p["L"])
                else:
                    h = jnp.einsum("...i,ki->...k", x, p["R"])
                    y = jnp.einsum("...k,ok->...o", h, p["L"])
    else:
        # dense weights (vanilla, ASI baseline, or un-injected project)
        if state is not None:
            xt, new_state = compress(x)
            y = asi_matmul(x, p["w"], xt)
        else:
            y = jnp.einsum("...i,oi->...o", x, p["w"])
    if "La" in p:
        y = y + adapter_delta(x, p["La"], p["Ra"])
    if "b" in p:
        y = y + p["b"]
    return y, new_state


def adapter_delta(x, La, Ra):
    """The per-tenant additive delta ``x R_u^T L_u^T`` (repro/tenancy/).

    Two layouts, told apart by rank alone: the single-tenant pair
    La (O, K_a) / Ra (K_a, I) routes through the same fused lowrank kernel
    the factored sites use; a per-slot GATHERED bank row — La (B, O, K_a) /
    Ra (B, K_a, I), one tenant's factors per batch row, selected inside the
    serve engine's jitted step — contracts per row so one executable serves
    any mix of tenants. A zero pair contributes exactly zero, which is how
    the engine's identity row serves adapter-less slots."""
    if La.ndim == x.ndim:
        h = jnp.einsum("b...i,bki->b...k", x, Ra)
        return jnp.einsum("b...k,bok->b...o", h, La)
    from repro.kernels.ops import lowrank_matmul
    return lowrank_matmul(x, Ra, La)


def linear_out_dim(p: dict) -> int:
    return p["L"].shape[0] if "L" in p else p["w"].shape[0]


def linear_layout(p: dict) -> str:
    """The subspace layout a param dict is in: "dense" | "factored" |
    "project". The canonical key-inspection entry for api.convert."""
    if "L" in p and "w" in p:
        return "project"
    if "L" in p:
        return "factored"
    return "dense"


def is_linear_params(v) -> bool:
    """Does ``v`` look like one linear's param dict (any layout)?"""
    return isinstance(v, dict) and ("w" in v or "L" in v)


def is_quantized(p: dict) -> bool:
    """Is this linear dict in an int8-packed layout (quant/quantize.py:
    scales ride next to the int8 payload as sL/sR/sW)?"""
    return "sL" in p or "sW" in p


def is_adapter_params(v) -> bool:
    """Does ``v`` carry a per-tenant adapter pair (repro/tenancy/)? True
    for both a pure adapter dict ({"La","Ra"}) and a merged linear dict
    that carries the delta next to its base weights."""
    return isinstance(v, dict) and "La" in v


def draft_slice(p: dict, k: int) -> dict:
    """The rank-k draft view of a factored linear dict: the leading k
    columns of L and rows of R (plus the matching sR rows when the site is
    int8-packed — sL scales one-per-output-channel and is untouched).
    These are slices of the ALREADY-RESIDENT factors: the draft model
    costs zero extra weights (docs/serving.md)."""
    out = dict(p)
    out["L"] = p["L"][..., :k]
    out["R"] = p["R"][..., :k, :]
    if "sR" in p:
        out["sR"] = p["sR"][..., :k]
    return out


def dense_weight(v):
    """The dense (…, O, I) weight of a dense-layout linear dict, else
    None (used by plan calibration, which only reads dense trees)."""
    if isinstance(v, dict) and "w" in v and getattr(v["w"], "ndim", 0) >= 2:
        return v["w"]
    return None


def linear_dims(p: dict) -> tuple[int, int]:
    """(out_dim, in_dim) of a linear param dict in any layout."""
    if linear_layout(p) == "factored":
        return int(p["L"].shape[-2]), int(p["R"].shape[-1])
    return int(p["w"].shape[-2]), int(p["w"].shape[-1])


def infer_spec(p: dict, wasi: WasiConfig, *, role: str = "mlp",
               name: str = "adhoc") -> LinearSpec:
    """Bridge for the legacy dict-first API: recover a spec from a param
    dict's layout. Mode comes from the keys (the one sanctioned place),
    dims/rank from the shapes, kernel route from the plan policy."""
    if "L" in p and "w" in p:
        mode, rank = "project", p["L"].shape[-1]
        out_dim, in_dim = p["w"].shape[-2:]
    elif "L" in p:
        mode, rank = "factored", p["L"].shape[-1]
        out_dim, in_dim = p["L"].shape[-2], p["R"].shape[-1]
    else:
        mode, rank = "dense", 0
        out_dim, in_dim = p["w"].shape[-2:]
    return LinearSpec(name=name, role=role, in_dim=int(in_dim),
                      out_dim=int(out_dim), mode=mode, rank=int(rank),
                      bias="b" in p,
                      kernel="fused_lowrank" if mode == "factored"
                      else "einsum",
                      quant="int8" if is_quantized(p) else None)


# ---------------------------------------------------------------------------
# Structure-walking helpers (the key-dispatch monopoly)
# ---------------------------------------------------------------------------

def iter_linear_dicts(tree, prefix: str = ""):
    """Yield (path, linear_dict) for every linear param dict in a tree —
    the sanctioned walk for consumers that only need per-site accounting
    (utils/memprof.py), never dispatch."""
    if isinstance(tree, dict):
        if is_linear_params(tree):
            yield prefix, tree
            return
        for k, v in tree.items():
            yield from iter_linear_dicts(v, f"{prefix}/{k}" if prefix else k)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from iter_linear_dicts(v, f"{prefix}/{i}" if prefix else str(i))


def iter_adapter_dicts(tree, prefix: str = ""):
    """Yield (path, dict) for every adapter-pair-bearing dict in a tree.
    Walks pure adapter trees ({"La","Ra"} at the sites, repro/tenancy/)
    and merged param trees (delta riding next to base weights) alike —
    the sanctioned walk for per-tenant byte accounting."""
    if isinstance(tree, dict):
        if "La" in tree:
            yield prefix, tree
            return
        if is_linear_params(tree):
            return
        for k, v in tree.items():
            yield from iter_adapter_dicts(v, f"{prefix}/{k}" if prefix else k)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from iter_adapter_dicts(
                v, f"{prefix}/{i}" if prefix else str(i))


def linear_param_bytes(p: dict) -> dict:
    """Storage of one linear dict, split by payload kind:
    {"weights": .., "scales": .., "bias": ..} bytes. Quantized layouts show
    their packing win in the weights/scales split."""
    import numpy as np

    def nbytes(a) -> int:
        return int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize

    out = {"weights": 0, "scales": 0, "bias": 0,
           "adapter_weights": 0, "adapter_scales": 0}
    for k, v in p.items():
        if k in ("w", "L", "R"):
            out["weights"] += nbytes(v)
        elif k in ("sW", "sL", "sR"):
            out["scales"] += nbytes(v)
        elif k in ("La", "Ra"):
            out["adapter_weights"] += nbytes(v)
        elif k in ("sLa", "sRa"):
            out["adapter_scales"] += nbytes(v)
        elif k == "b":
            out["bias"] += nbytes(v)
    return out


def map_factored(params, fn):
    """Apply fn(WSIState) -> WSIState to every {L, R} factor pair in a
    param tree (factored-mode WSI refresh)."""
    def walk(node):
        if isinstance(node, dict):
            if "L" in node and "R" in node and "w" not in node \
                    and "sL" not in node:  # int8 factors are serve-frozen
                st = fn(WSIState(L=node["L"], R=node["R"]))
                out = dict(node)
                out["L"], out["R"] = st.L, st.R
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple) and not hasattr(node, "_fields"):
            return tuple(walk(v) for v in node)
        return node

    return walk(params)


def inject_factors(params, states: dict):
    """Insert (L, R) from ``states`` (path-keyed WSIState dict, paths ending
    "/w") next to each dense W so ``apply`` takes the project path."""
    def patch(node, prefix=""):
        if isinstance(node, dict):
            if "w" in node and prefix + "/w" in states:
                st = states[prefix + "/w"]
                node = dict(node)
                node["L"] = jax.lax.stop_gradient(st.L)
                node["R"] = jax.lax.stop_gradient(st.R)
                return node
            return {k: patch(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [patch(v, f"{prefix}/{i}" if prefix else str(i))
                 for i, v in enumerate(node)]
            return type(node)(t) if not isinstance(node, tuple) else tuple(t)
        return node

    return patch(params)


def extract_project_factors(params):
    """Split converted project-mode params {"w","L","R"} into a dense param
    tree plus a path-keyed {".../w": WSIState} dict (same keying as
    core/project.init_project_states) for warm-starting the WSI states.
    Trees without carried factors return (params, {})."""
    factors: dict[str, WSIState] = {}

    def strip(node, prefix=""):
        if isinstance(node, dict):
            if "w" in node and "L" in node and "R" in node:
                factors[prefix + "/w"] = WSIState(L=node["L"], R=node["R"])
                return {k: v for k, v in node.items() if k not in ("L", "R")}
            return {k: strip(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [strip(v, f"{prefix}/{i}" if prefix else str(i))
                 for i, v in enumerate(node)]
            return t if isinstance(node, list) else tuple(t)
        return node

    stripped = strip(params)
    return (stripped, factors) if factors else (params, {})


def plan_param_specs(params, plan: SubspacePlan, policy=None, rules=None):
    """Pytree of PartitionSpecs for ``params``, PLAN-DRIVEN: sites whose
    spec carries a ``sharding`` stamp (SubspacePlan.with_sharding) use it
    verbatim — the plan owns placement the same way it owns mode/rank —
    and everything else (embeddings, norms, unstamped plans) falls back to
    the distributed/sharding.py path-rule table. Stacked scan layers pad
    leading replicated axes, exactly like spec_for_path."""
    from jax.sharding import PartitionSpec as P

    from repro.api.plan import LEAF_TO_SPEC
    from repro.distributed.sharding import (
        LM_RULES,
        MeshPolicy,
        _path_str,
        spec_for_path,
    )

    policy = policy if policy is not None else MeshPolicy()
    rules = rules if rules is not None else LM_RULES
    stamped = {s.name: dict(s.sharding) for s in plan.specs
               if s.sharding is not None}

    def one(path, leaf):
        ps = _path_str(path)
        parts = ps.split("/")
        if len(parts) >= 2:
            site = LEAF_TO_SPEC.get(parts[-2], (None, None))[0]
            entries = stamped.get(site, {}).get(parts[-1])
            if entries is not None:
                e = tuple(entries)
                nd = getattr(leaf, "ndim", len(e))
                if nd > len(e):
                    e = (None,) * (nd - len(e)) + e
                elif nd < len(e):
                    e = e[-nd:] if nd else ()
                return P(*e)
        return spec_for_path(ps, leaf, policy, rules)

    return jax.tree_util.tree_map_with_path(one, params)
