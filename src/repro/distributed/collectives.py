"""Hand-scheduled collectives for the sharded train/serve hot paths.

``shard_map`` — one compat alias every mesh consumer (train/step.py, the
parity tests, this module) imports, so the jax.shard_map ->
jax.experimental.shard_map rename (and check_rep -> check_vma) across jax
versions lives in exactly one place.

``collective_bytes`` / ``measured_collective_bytes`` — the MEASURED side of
the communication story: parse the post-SPMD HLO of a compiled executable
and sum the result sizes of every collective op. ``launch/dryrun.py`` uses
it for the planning matrix; ``distributed/grad_compress.py`` and
``benchmarks/fig_comm.py`` use it to report the factor-only DP all-reduce
bytes as an observation, not a formula.

``flash_decode`` — sequence-sharded single-token attention: the KV cache for
a 500k-token context is sharded along the SEQUENCE dim across the ``data``
mesh axis. Each shard computes a LOCAL partial softmax (max, sum, weighted
value) over its KV slice; partials are combined with three tiny psums
(per-head scalars + one Dh vector) instead of all-gathering the cache —
collective bytes drop from O(S * d_kv) to O(H * Dh).

This is the shard_map fast path; the pjit path (XLA-scheduled) is the
baseline it is hillclimbed against in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

try:  # newer jax: top level, its replication check is named check_vma
    shard_map = jax.shard_map
except AttributeError:  # 0.4.x: the same check is named check_rep
    from jax.experimental.shard_map import shard_map as _shard_map

    def shard_map(f, *, check_vma: bool = True, **kw):
        return _shard_map(f, check_rep=check_vma, **kw)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Measured collective bytes (post-SPMD HLO)
# ---------------------------------------------------------------------------

DTYPE_RE = re.compile(
    r"(f64|f32|bf16|f16|s32|u32|s64|u64|s16|u16|s8|u8|pred|c64|c128)"
    r"\[([0-9,]*)\]")
BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
         "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
         "pred": 1, "c64": 8, "c128": 16}
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * BYTES[dt]


def _tuple_elements(t: str) -> list[str]:
    """Top-level elements of an HLO tuple type "(a, (b, c), d)"."""
    parts, depth, start = [], 0, 1
    for i, ch in enumerate(t):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 1:
            parts.append(t[start:i])
            start = i + 1
    parts.append(t[start:t.rindex(")")])
    return parts


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum RESULT sizes of collective ops in post-SPMD HLO (per device).

    A combined op's result is a tuple, "(f32[a], f32[b]) all-reduce(...)",
    and every element counts. The async start of any collective but
    all-reduce returns (operand, result, context...): only its result
    element counts, itself a tuple when the op is combined."""
    out = {c: 0 for c in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        for c in COLLECTIVES:
            # match op lines: "%x = TYPE all-reduce(...)" (incl. -start)
            op = re.search(rf"\b{c}(-start)?\(", ls)
            if op:
                result = ls[ls.find("=") + 1:op.start()].strip()
                if op.group(1) and c != "all-reduce" and result[:1] == "(":
                    result = _tuple_elements(result)[1]
                sizes = [_shape_bytes(m) for m in DTYPE_RE.finditer(result)]
                if sizes:
                    out[c] += sum(sizes)
                    out["count"] += 1
                break
    out["total"] = sum(out[c] for c in COLLECTIVES)
    return out


def measured_collective_bytes(fn, *args) -> dict[str, int]:
    """Compile ``fn(*args)`` and read its per-device collective bytes out of
    the post-SPMD HLO. ``fn`` must already carry its mesh (a shard_map-
    wrapped step, or a jit with explicit shardings); args are concrete
    arrays or ShapeDtypeStructs."""
    compiled = jax.jit(fn).lower(*args).compile()
    return collective_bytes(compiled.as_text())


def _local_partials(q, k, v, valid):
    """q (B,H,Dh); k/v (B,Sl,KVH,Dh); valid (Sl,) bool.
    Returns (m (B,H), l (B,H), acc (B,H,Dh)) local partial softmax."""
    b, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh) * (dh ** -0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k).astype(jnp.float32)
    s = s + jnp.where(valid, 0.0, NEG_INF)[None, None, None, :]
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p.astype(q.dtype), v).astype(jnp.float32)
    return m.reshape(b, h), l.reshape(b, h), acc.reshape(b, h, dh)


def flash_decode(q, k_shard, v_shard, valid_shard, axis_name: str):
    """Inside shard_map: combine per-shard partial softmaxes via psum.

    q (B,H,Dh) replicated across the sequence shards; k/v (B,S_local,KVH,Dh);
    valid_shard (S_local,). Returns (B,H,Dh) fully-reduced attention output.
    """
    m, l, acc = _local_partials(q, k_shard, v_shard, valid_shard)
    m_glob = jax.lax.pmax(m, axis_name)                       # (B,H)
    scale = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * scale, axis_name)
    acc_glob = jax.lax.psum(acc * scale[..., None], axis_name)
    return (acc_glob / jnp.maximum(l_glob[..., None], 1e-30)).astype(q.dtype)


def make_flash_decode(mesh: Mesh, seq_axis: str = "data"):
    """shard_map-wrapped flash decode over a sequence-sharded KV cache.

    Returns fn(q (B,H,Dh), k (B,S,KVH,Dh), v, pos) -> (B,H,Dh), where k/v are
    sharded P(None, seq_axis, None, None) and q is replicated.
    """
    def fn(q, k, v, pos):
        s = k.shape[1]

        def local(qi, ki, vi, posi):
            idx = jax.lax.axis_index(seq_axis)
            sl = ki.shape[1]
            kpos = idx * sl + jnp.arange(sl)
            return flash_decode(qi, ki, vi, kpos <= posi, seq_axis)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(None, seq_axis, None, None),
                      P(None, seq_axis, None, None), P()),
            out_specs=P(),
        )(q, k, v, pos)

    return fn
