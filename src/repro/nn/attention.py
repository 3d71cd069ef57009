"""Grouped-query attention with causal / sliding-window / cross variants,
chunked (flash-style) computation, and full + rolling KV caches.

Shapes: hidden (B, S, d); heads laid out (B, S, H, Dh). GQA repeats each of
the KVH key/value heads across G = H // KVH query heads via a reshape —
no materialized repetition.

Full-sequence attention (training, cache-less prefill) picks its route in
:func:`attention_route`: on TPU the Pallas flash attention with its own
backward (``kernels.ops.flash_attention``, no (S, S) tensor in HBM);
elsewhere :func:`dense_attention`, or above ``chunked_threshold``
:func:`chunked_attention` — an online-softmax scan over KV chunks that never
materializes the (S, S) score matrix (XLA maps it to a fori loop with
O(S * chunk) live memory), and the kernel's oracle off TPU.

Sliding-window layers keep a ROLLING cache of ``window`` slots: absolute
position p lives in slot p % W; slot validity and relative distance are
reconstructed arithmetically (see ``_rolling_slot_positions``) so decode is
O(W) compute and memory regardless of sequence length — this is what makes
`long_500k` decode cheap for gemma3/mixtral local layers.
"""
from __future__ import annotations

import collections
import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import MeshPolicy, shard
from repro.api import bind, plan_of, role_treated
from repro.kernels import ops
from repro.nn.rotary import apply_rope
from repro.utils.scopes import ATTENTION, scoped

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_cache, KVH, Dh)
    v: jax.Array  # (B, S_cache, KVH, Dh)


class PagedKVCache(NamedTuple):
    """Pooled KV storage: pages instead of per-slot rows.

    The batch dimension is gone — storage is a pool of ``n_pages`` pages of
    ``page_size`` token slots each, shared by every serve slot. A per-slot
    ``page_table`` (B, pages_per_slot) int32 maps logical page j of slot b
    to a physical page; reads gather the table into a (B, S_logical) view,
    writes scatter through it. Page 0 is the engine's trash page (dead and
    still-prefilling rows point their table there), so the pool never needs
    per-row validity flags: a position is readable iff the causal mask says
    so, exactly as with dense caches.
    """

    k: jax.Array  # (n_pages, page_size, KVH, Dh)
    v: jax.Array  # (n_pages, page_size, KVH, Dh)

    @property
    def page_size(self) -> int:
        return self.k.shape[1]


# ---------------------------------------------------------------------------
# Parameter init / projection plumbing
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    plan = plan_of(cfg)
    qb = cfg.qkv_bias
    return {
        "wq": bind.init_params(kq, plan.linear("attn/wq", d, h * dh), dtype=dtype, bias=qb),
        "wk": bind.init_params(kk, plan.linear("attn/wk", d, kvh * dh), dtype=dtype, bias=qb),
        "wv": bind.init_params(kv, plan.linear("attn/wv", d, kvh * dh), dtype=dtype, bias=qb),
        "wo": bind.init_params(ko, plan.linear("attn/wo", h * dh, d), dtype=dtype,
                               scale=(h * dh) ** -0.5 / max(cfg.total_pattern_layers, 1) ** 0.5),
    }


def init_attention_state(key, cfg: ModelConfig, batch: int, seq: int,
                         dtype=jnp.float32) -> dict:
    """ASI warm-start states for the four projections (train path)."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    w = cfg.wasi
    if not (w.compress_acts and role_treated(w, "attn")):
        return {}
    return {
        "wq": bind.asi_state(ks[0], (batch, seq, d), w, dtype),
        "wk": bind.asi_state(ks[1], (batch, seq, d), w, dtype),
        "wv": bind.asi_state(ks[2], (batch, seq, d), w, dtype),
        "wo": bind.asi_state(ks[3], (batch, seq, h * dh), w, dtype),
    }


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Sq,KVH,G,Dh) x k (B,Sk,KVH,Dh) -> (B,KVH,G,Sq,Sk)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k)


def _gqa_combine(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (B,KVH,G,Sq,Sk) x v (B,Sk,KVH,Dh) -> (B,Sq,KVH,G,Dh)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)


def _mask_bias(sq: int, sk: int, q_offset, *, causal: bool,
               window: int) -> jax.Array:
    """Additive mask (Sq, Sk). q position = q_offset + row index."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


@scoped(ATTENTION)
def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset=0) -> jax.Array:
    """Reference attention materializing scores. q (B,Sq,H,Dh)."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, k).astype(jnp.float32)
    s = s + _mask_bias(sq, k.shape[1], q_offset, causal=causal, window=window)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _gqa_combine(p, v)
    return o.reshape(b, sq, h, dh)


@scoped(ATTENTION)
def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_offset=0, chunk: int = 1024,
                      q_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention, tiled over BOTH query blocks and KV chunks
    (flash semantics, pure JAX).

    Live score memory: O(q_chunk * chunk) per (B, KVH, G). Both the KV-scan
    body and the q-block body are jax.checkpoint'ed so the BACKWARD pass
    recomputes scores per tile instead of stacking them across the scan —
    without this, autodiff through the scan saves every chunk's f32 scores
    (measured: 7 GiB/device at train_4k before the fix; EXPERIMENTS.md §Perf).
    """
    b, sq, h, dh = q.shape
    if sq > q_chunk:
        nq = -(-sq // q_chunk)
        pad = nq * q_chunk - sq
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
        qb = qp.reshape(b, nq, q_chunk, h, dh)

        @jax.checkpoint
        def qblock(qi, idx):
            return chunked_attention(qi, k, v, causal=causal, window=window,
                                     q_offset=q_offset + idx * q_chunk,
                                     chunk=chunk, q_chunk=q_chunk)

        out = jax.lax.map(lambda t: qblock(t[0], t[1]),
                          (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, h, dh)
        return out[:, :sq]
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    sk_orig = sk
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sk = sk + pad
    n_chunks = sk // chunk
    qg = (q.reshape(b, sq, kvh, g, dh) * (dh ** -0.5)).astype(q.dtype)
    kc = k.reshape(b, n_chunks, chunk, kvh, dh)
    vc = v.reshape(b, n_chunks, chunk, kvh, dh)

    qpos = q_offset + jnp.arange(sq)

    @jax.checkpoint
    def step(carry, xs):
        m, l, acc = carry
        kb, vb, c_idx = xs
        s = _gqa_scores(qg, kb).astype(jnp.float32)      # (B,KVH,G,Sq,chunk)
        kpos = c_idx * chunk + jnp.arange(chunk)
        ok = jnp.ones((sq, chunk), bool)
        if causal:
            ok &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            ok &= kpos[None, :] > qpos[:, None] - window
        if sk != sk_orig:
            ok &= (kpos < sk_orig)[None, :]
        s = s + jnp.where(ok, 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale_old = jnp.exp(m - m_new)
        l_new = l * scale_old + p.sum(axis=-1)
        acc_new = acc * scale_old[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, sq, dh), jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kc_t, vc_t, jnp.arange(n_chunks)))
    o = acc / jnp.maximum(l[..., None], 1e-30)
    o = jnp.moveaxis(o.reshape(b, kvh * g, sq, dh), 1, 2)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Route of the full-sequence branch
# ---------------------------------------------------------------------------

# The flash kernel's route starts at this many tokens: below it dense
# attention's forward and backward are faster on a TPU v5e (PERF.md, timed
# at qwen2-0.5b widths).
FLASH_MIN_SEQ = 512
# head dims the kernel is compiled for in tests/test_tpu_compile.py
FLASH_HEAD_DIMS = (64, 128)

# trace-time tally of the route each full-sequence call took; a call in a
# scanned layer group counts once per layer of the group
_ROUTES: collections.Counter = collections.Counter()
_LAYERS_PER_CALL = [1]


def attention_routes() -> dict[str, int]:
    """{route: calls} traced since the last :func:`reset_attention_routes`;
    route is "flash", "dense" or "chunked"."""
    return dict(_ROUTES)


def reset_attention_routes() -> None:
    _ROUTES.clear()


@contextlib.contextmanager
def layers_per_call(n: int):
    """Count each route traced inside as ``n`` calls: a scan body traced
    once stands for ``n`` layers."""
    _LAYERS_PER_CALL.append(_LAYERS_PER_CALL[-1] * n)
    try:
        yield
    finally:
        _LAYERS_PER_CALL.pop()


def _partitioned_by_xla(policy: MeshPolicy | None) -> bool:
    """A sharding policy outside shard_map leaves the partitioning to
    GSPMD, which cannot partition a Mosaic call; inside shard_map (every
    mesh axis manual) each device runs its own shard."""
    if policy is None:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return not (mesh.axis_names
                and set(mesh.manual_axes) == set(mesh.axis_names))


def attention_route(sq: int, sk: int, head_dim: int, *,
                    chunked_threshold: int = 2048,
                    policy: MeshPolicy | None = None) -> str:
    """Which attention the full-sequence branch takes, from shapes, the
    backend (``ops.INTERPRET``) and the sharding."""
    if (not ops.INTERPRET and sq == sk and sq >= FLASH_MIN_SEQ
            and head_dim in FLASH_HEAD_DIMS
            and not _partitioned_by_xla(policy)):
        return "flash"
    return "chunked" if sq > chunked_threshold else "dense"


# ---------------------------------------------------------------------------
# Rolling-window cache arithmetic
# ---------------------------------------------------------------------------

def _rolling_slot_positions(pos: jax.Array, w: int) -> jax.Array:
    """Absolute position stored in each of the W slots when the writer is at
    absolute position ``pos`` (already written). Slots never written hold a
    negative value (=> masked)."""
    slots = jnp.arange(w)
    return pos - (pos - slots) % w  # in (pos-W, pos]; negative if unwritten


def is_vector_pos(pos) -> bool:
    """Per-slot (B,) vector vs a single shared scalar — the convention for
    decode positions and prefill valid lengths across nn/ modules."""
    return hasattr(pos, "ndim") and pos.ndim == 1


@scoped(ATTENTION)
def decode_attention(q, cache: KVCache, pos, *, window: int = 0) -> jax.Array:
    """Single-token decode. q (B,1,H,Dh); cache holds positions <= pos.

    ``pos`` is either a scalar (all rows at the same absolute position —
    lockstep batch) or a (B,) vector of per-row positions (continuous
    batching: every serve slot decodes at its own offset).

    For full caches, slot index == absolute position; for rolling caches
    (cache length == window) slot positions are reconstructed.
    """
    b, _, h, dh = q.shape
    s_cache = cache.k.shape[1]
    kvh = cache.k.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, kvh, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, cache.k).astype(jnp.float32)   # (B,KVH,G,1,S)
    posb = pos[:, None] if is_vector_pos(pos) else jnp.full((1, 1), pos)
    if window > 0 and s_cache == window:
        slot_pos = _rolling_slot_positions(posb, window)   # (B|1, W)
        ok = slot_pos >= 0
    else:
        kpos = jnp.arange(s_cache)[None, :]
        ok = kpos <= posb
        if window > 0:
            ok &= kpos > posb - window
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _gqa_combine(p, cache.v)
    return o.reshape(b, 1, h, dh)


def cache_update(cache: KVCache, k_new, v_new, pos, *, window: int = 0) -> KVCache:
    """Write one token's K/V at ``pos`` (rolling if cache len == window).
    ``pos`` scalar or (B,) per-row positions."""
    s_cache = cache.k.shape[1]
    rolling = window > 0 and s_cache == window
    if is_vector_pos(pos):
        slot = pos % window if rolling else pos
        rows = jnp.arange(cache.k.shape[0])
        k = cache.k.at[rows, slot].set(k_new[:, 0])
        v = cache.v.at[rows, slot].set(v_new[:, 0])
        return KVCache(k=k, v=v)
    slot = pos % window if rolling else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, slot, axis=1)
    return KVCache(k=k, v=v)


def cache_update_prefill(cache: KVCache, k_new, v_new, offset, *,
                         window: int = 0,
                         valid_len: jax.Array | None = None) -> KVCache:
    """Write a whole prompt's K/V (S tokens starting at absolute position
    ``offset``) into the cache in one pass — the batched-prefill analogue of
    scanning :func:`cache_update` token by token.

    ``valid_len`` (B,) marks per-row true prompt lengths for right-padded
    (length-bucketed) prefill: positions >= valid_len are NOT written, so
    the cache is indistinguishable from an exact-length prefill and the
    decode masks (slot-position arithmetic included) stay correct.
    """
    s_cache = cache.k.shape[1]
    b, s = k_new.shape[:2]
    rolling = window > 0 and s_cache == window
    if rolling:
        # Slot j of a rolling cache must hold the LAST valid position p with
        # p % W == j. Gather that position's K/V per (row, slot); slots whose
        # owner predates this prefill chunk keep their current contents.
        last = (jnp.full((b,), offset + s)
                if valid_len is None else jnp.minimum(offset + s, valid_len)) - 1
        slots = jnp.arange(window)[None, :]
        owner = last[:, None] - (last[:, None] - slots) % window   # (B, W)
        take = jnp.clip(owner - offset, 0, s - 1)
        kg = jnp.take_along_axis(k_new, take[..., None, None], axis=1)
        vg = jnp.take_along_axis(v_new, take[..., None, None], axis=1)
        write = (owner >= offset)[..., None, None]
        return KVCache(k=jnp.where(write, kg, cache.k),
                       v=jnp.where(write, vg, cache.v))
    if valid_len is not None:
        cur_k = jax.lax.dynamic_slice_in_dim(cache.k, offset, s, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(cache.v, offset, s, axis=1)
        pos_abs = offset + jnp.arange(s)
        valid = (pos_abs[None, :] < valid_len[:, None])[..., None, None]
        k_new = jnp.where(valid, k_new, cur_k)
        v_new = jnp.where(valid, v_new, cur_v)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, offset, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, offset, axis=1)
    return KVCache(k=k, v=v)


def cache_update_verify(cache: KVCache, k_new, v_new, offset,
                        valid_len: jax.Array | None = None) -> KVCache:
    """Write a (B, C) token block at PER-ROW absolute offsets — the dense
    analogue of :func:`paged_update_prefill`, built for the spec-decode
    verify pass where every serve slot sits at its own position.

    Positions past ``valid_len[b]`` are routed OUT OF BOUNDS (index ==
    cache length), which JAX scatter drops — so rows drafting fewer than k
    tokens (and dead rows, valid_len 0) leave the cache untouched, the
    same trick the paged path plays with its trash page."""
    b, c = k_new.shape[:2]
    pos = offset[:, None] + jnp.arange(c)[None, :]            # (B, C) abs
    if valid_len is not None:
        pos = jnp.where(jnp.arange(c)[None, :] < valid_len[:, None],
                        pos, cache.k.shape[1])
    rows = jnp.arange(b)[:, None]
    return KVCache(k=cache.k.at[rows, pos].set(k_new, mode="drop"),
                   v=cache.v.at[rows, pos].set(v_new, mode="drop"))


@scoped(ATTENTION)
def dense_verify_attention(q, cache: KVCache, qpos):
    """Token-parallel attention over a row's ENTIRE dense cache: q
    (B, C, H, Dh) at absolute positions ``qpos`` (B, C), masked causally
    by absolute position. Same math as :func:`paged_prefill_attention`
    minus the page gather — the dense prefill path cannot serve here
    because it attends only within the chunk, and a verify block's
    positions condition on all the history before them."""
    b, c, h, dh = q.shape
    kvh = cache.k.shape[2]
    g = h // kvh
    qg = q.reshape(b, c, kvh, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, cache.k).astype(jnp.float32)  # (B,KVH,G,C,S_cache)
    kpos = jnp.arange(cache.k.shape[1])
    ok = kpos[None, None, :] <= qpos[:, :, None]      # (B, C, S_cache)
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _gqa_combine(p, cache.v)
    return o.reshape(b, c, h, dh)


def init_cache(cfg: ModelConfig, batch: int, seq: int, *, window: int = 0,
               dtype=jnp.bfloat16) -> KVCache:
    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    s = min(seq, window) if window > 0 else seq
    shape = (batch, s, kvh, dh)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    """Pooled KV storage for one layer: ``n_pages`` pages shared by every
    serve slot (serve/kvpool.py owns the page accounting)."""
    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_pages, page_size, kvh, dh)
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# Paged gather / scatter / attention
# ---------------------------------------------------------------------------

def paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """(n_pages, PG, KVH, Dh) pool + (B, P) table -> (B, P*PG, KVH, Dh)
    per-slot logical view. Positions no page was written at hold whatever
    the physical page last held — every read below masks by position, so
    such slots contribute an exact softmax weight of zero."""
    g = pool[page_table]                        # (B, P, PG, KVH, Dh)
    b, p, pg = g.shape[:3]
    return g.reshape(b, p * pg, *g.shape[3:])


def paged_update_decode(cache: PagedKVCache, k_new, v_new, pos,
                        page_table: jax.Array) -> PagedKVCache:
    """Write one token's K/V per row at absolute position ``pos`` (B,)
    through the page table. The engine guarantees the page under any LIVE
    row's write position is exclusively owned (shared prefix pages are
    read-only by construction); dead rows carry an all-trash table, so
    their lockstep writes collide harmlessly on page 0."""
    pg = cache.page_size
    pid = jnp.take_along_axis(page_table, (pos // pg)[:, None], axis=1)[:, 0]
    off = pos % pg
    return PagedKVCache(k=cache.k.at[pid, off].set(k_new[:, 0]),
                        v=cache.v.at[pid, off].set(v_new[:, 0]))


def paged_update_prefill(cache: PagedKVCache, k_new, v_new, offset,
                         page_table: jax.Array,
                         valid_len: jax.Array | None = None) -> PagedKVCache:
    """Write one prefill chunk (B, C) of K/V at absolute positions
    ``offset[b] + i`` through the page table. ``valid_len`` (B,) counts the
    chunk's valid rows; padded positions are routed to the trash page."""
    b, c = k_new.shape[:2]
    pos = offset[:, None] + jnp.arange(c)[None, :]            # (B, C) abs
    pid = jnp.take_along_axis(page_table, pos // cache.page_size, axis=1)
    if valid_len is not None:
        pid = jnp.where(jnp.arange(c)[None, :] < valid_len[:, None], pid, 0)
    off = pos % cache.page_size
    return PagedKVCache(k=cache.k.at[pid, off].set(k_new),
                        v=cache.v.at[pid, off].set(v_new))


@scoped(ATTENTION)
def paged_prefill_attention(q, cache: PagedKVCache, page_table, qpos):
    """Chunked-prefill attention: q (B, C, H, Dh) at absolute positions
    ``qpos`` (B, C) attends over the slot's ENTIRE logical cache (history
    from earlier chunks and shared prefix pages included), masked causally
    by absolute position. This is what lets a prompt prefill in chunks —
    unlike the dense prefill path, which attends only within the chunk."""
    k = paged_gather(cache.k, page_table)
    v = paged_gather(cache.v, page_table)
    b, c, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, c, kvh, g, dh) * (dh ** -0.5)
    s = _gqa_scores(qg, k).astype(jnp.float32)      # (B,KVH,G,C,S_log)
    kpos = jnp.arange(k.shape[1])
    ok = kpos[None, None, :] <= qpos[:, :, None]    # (B, C, S_log)
    s = s + jnp.where(ok, 0.0, NEG_INF)[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _gqa_combine(p, v)
    return o.reshape(b, c, h, dh)


# ---------------------------------------------------------------------------
# Full block-level attention apply
# ---------------------------------------------------------------------------

def apply_attention(p: dict, x: jax.Array, cfg: ModelConfig, *,
                    causal: bool = True, window: int = 0,
                    cache: KVCache | None = None, pos=None,
                    states: dict | None = None,
                    policy: MeshPolicy | None = None,
                    kv_memory: jax.Array | None = None,
                    valid_len: jax.Array | None = None,
                    page_table: jax.Array | None = None,
                    chunked_threshold: int = 2048):
    """Attention sublayer (projections + core + output projection).

    Modes:
      - train:   cache None            -> full attention over x, by
                 :func:`attention_route` (flash kernel, dense or chunked)
      - prefill: cache given, S > 1    -> token-parallel forward over the
                 whole prompt; K/V for ALL positions written to the cache in
                 one pass (``pos`` = offset of x[_, 0], normally 0;
                 ``valid_len`` (B,) masks right-padding of bucketed prompts)
      - decode:  cache given, S == 1   -> one-token step, cache updated
                 (``pos`` scalar, or (B,) per-slot for continuous batching)
      - cross:   kv_memory given       -> keys/values from encoder memory

    A :class:`PagedKVCache` (``page_table`` required, (B, pages_per_slot))
    switches the prefill/decode modes to the paged pool: reads gather the
    slot's logical view through the table, writes scatter through it, and
    prefill becomes CHUNKED — ``pos`` is a (B,) vector of absolute chunk
    offsets and q attends over the whole logical cache (earlier chunks and
    shared prefix pages), not just the chunk. Paged mode is causal
    full-attention only (no sliding window, no cross-attention).
    Returns (out, new_cache, new_states).
    """
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, sq, _ = x.shape
    st = states or {}
    new_st = dict(st)
    paged = isinstance(cache, PagedKVCache)
    if paged:
        if page_table is None:
            raise ValueError("PagedKVCache needs a page_table")
        if window > 0 or kv_memory is not None or not causal:
            raise ValueError("paged KV supports causal full attention only")

    def maybe_rope(t, positions):
        # rope_theta <= 0 disables RoPE (whisper: absolute sinusoidal embeds)
        if cfg.rope_theta <= 0:
            return t
        return apply_rope(t, positions, cfg.rope_theta)

    plan = plan_of(cfg)

    def proj(name, inp):
        spec = plan.linear(f"attn/{name}", inp.shape[-1],
                           bind.linear_out_dim(p[name]))
        y, ns = bind.apply(spec, p[name], inp, cfg.wasi, st.get(name))
        if ns is not None:
            new_st[name] = ns
        return y

    q = proj("wq", x).reshape(b, sq, h, dh)
    if kv_memory is not None:  # cross-attention: KV from encoder memory
        src = kv_memory
        k = proj("wk", src).reshape(b, src.shape[1], kvh, dh)
        v = proj("wv", src).reshape(b, src.shape[1], kvh, dh)
        o = dense_attention(q, k, v, causal=False)
        new_cache = cache
    elif cache is None:  # train / prefill over the full sequence
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        positions = jnp.arange(sq)
        q = maybe_rope(q, positions)
        k = maybe_rope(k, positions)
        # NOTE: no explicit q/k head-dim constraints — H / KVH are often not
        # divisible by the model axis (GQA); GSPMD propagates from the
        # projection outputs without forcing an involuntary reshard.
        route = attention_route(sq, sq, dh,
                                chunked_threshold=chunked_threshold,
                                policy=policy)
        _ROUTES[route] += _LAYERS_PER_CALL[-1]
        if route == "flash":
            o = ops.flash_attention(q, k, v, causal=causal, window=window)
        elif route == "chunked":
            o = chunked_attention(q, k, v, causal=causal, window=window)
        else:
            o = dense_attention(q, k, v, causal=causal, window=window)
        new_cache = None
    elif sq > 1 and paged:  # chunked prefill through the page table
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        offset = jnp.zeros((b,), jnp.int32) if pos is None else pos
        qpos = offset[:, None] + jnp.arange(sq)[None, :]      # (B, C) abs
        q = maybe_rope(q, qpos)
        k = maybe_rope(k, qpos)
        new_cache = paged_update_prefill(cache, k, v, offset, page_table,
                                         valid_len=valid_len)
        o = paged_prefill_attention(q, new_cache, page_table, qpos)
    elif sq > 1 and is_vector_pos(pos):  # spec-decode verify, dense cache
        # each row carries its own absolute offset; q attends over the
        # row's WHOLE cache (history included), unlike the prefill branch
        # below which only sees the chunk itself
        if window > 0:
            raise ValueError("per-row dense verify needs full attention "
                             "(spec decode is gated on supports_paging)")
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        qpos = pos[:, None] + jnp.arange(sq)[None, :]         # (B, C) abs
        q = maybe_rope(q, qpos)
        k = maybe_rope(k, qpos)
        new_cache = cache_update_verify(cache, k, v, pos, valid_len=valid_len)
        o = dense_verify_attention(q, new_cache, qpos)
    elif sq > 1:  # token-parallel prefill: attend + build caches in one pass
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        offset = 0 if pos is None else pos
        positions = offset + jnp.arange(sq)
        q = maybe_rope(q, positions)
        k = maybe_rope(k, positions)
        new_cache = cache_update_prefill(cache, k, v, offset, window=window,
                                         valid_len=valid_len)
        if sq > chunked_threshold:
            o = chunked_attention(q, k, v, causal=causal, window=window,
                                  q_offset=offset)
        else:
            o = dense_attention(q, k, v, causal=causal, window=window,
                                q_offset=offset)
    elif paged:  # decode one token per row through the page table
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        if not is_vector_pos(pos):
            raise ValueError("paged decode needs per-row (B,) positions")
        q = maybe_rope(q, pos[:, None])
        k = maybe_rope(k, pos[:, None])
        new_cache = paged_update_decode(cache, k, v, pos, page_table)
        # gather the per-slot logical view and run the SAME masked decode
        # attention the dense path runs — with pages_per_slot * page_size
        # equal to the dense cache length this is the identical executable
        # shape, which is what makes paged decode bitwise-comparable to the
        # dense oracle in tests/test_serve_fuzz.py
        gathered = KVCache(k=paged_gather(new_cache.k, page_table),
                           v=paged_gather(new_cache.v, page_table))
        o = decode_attention(q, gathered, pos, window=0)
    else:  # decode one token at absolute position ``pos`` (scalar or (B,))
        k = proj("wk", x).reshape(b, sq, kvh, dh)
        v = proj("wv", x).reshape(b, sq, kvh, dh)
        rope_pos = pos[:, None] if is_vector_pos(pos) else jnp.full((sq,), pos)
        q = maybe_rope(q, rope_pos)
        k = maybe_rope(k, rope_pos)
        new_cache = cache_update(cache, k, v, pos, window=window)
        o = decode_attention(q, new_cache, pos, window=window)
    o = o.reshape(b, sq, h * dh)
    o = shard(o, policy, "batch", "seq", "model")
    out = proj("wo", o)
    out = shard(out, policy, "batch", "seq", None)
    return out, new_cache, new_st
