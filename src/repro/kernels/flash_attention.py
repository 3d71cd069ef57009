"""Flash attention with its own backward pass: causal / sliding-window,
grouped-query, online softmax over score tiles that never leave VMEM.

Layout: q, o (B, H, S, D); k, v (B, KVH, S, D); H = KVH * G. Query head h
reads KV head h // G through the BlockSpec index maps, so K and V are
never repeated in HBM. D is the block's whole last dim (64 is not padded
to 128). S is a multiple of the tiles; ``s_valid`` < S masks the keys of
a padded (ragged) sequence (inside the kernels, ``s_valid`` None: none).

Three kernels:
  forward  grid (B, H, nq, nk): o and the per-row logsumexp (f32)
  dq       grid (B, H, nq, nk): dq = sum_j dS_ij K_j
  dkv      grid (B, KVH, nk, G, nq): dk, dv summed over the G query heads
           of the KV head and over the query tiles
The backward recomputes each score tile from q, k and the saved
logsumexp; no (S, S) tensor reaches HBM.

Precision: every matmul takes its operands in ``mxu_dtype``, cast in
VMEM, and accumulates in f32. bf16 there is the one bf16 pass that XLA's
DEFAULT precision gives an f32 einsum on TPU; ``kernels.ops`` passes f32
off TPU, where DEFAULT is f32. Scores, the running max and sum, the
logsumexp and the softmax gradient are f32. q is scaled by 1/sqrt(D)
before its cast, as ``nn.attention.dense_attention`` scales it before its
einsum.

Tiles wholly above the causal diagonal or outside the window do no MXU
work, and their index maps repeat the nearest needed block, so Pallas
fetches nothing new for them. Masks are built only on tiles that straddle
the diagonal, the window's edge or the end of a ragged sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_LANES = 128


def _keep(qpos, kpos, *, causal, window, s_valid):
    ok = []
    if s_valid:
        ok.append(kpos < s_valid)
    if causal:
        ok.append(kpos <= qpos)
    if window > 0:
        ok.append(kpos > qpos - window)
    return functools.reduce(jnp.logical_and, ok)


def _when_visible(q_lo, k_lo, bq, bk, step, *, causal, window, s_valid):
    """Run ``step(mask)`` on the score tile of query rows [q_lo, q_lo+bq)
    and keys [k_lo, k_lo+bk) if any of its entries survives the mask,
    building the mask only where some entry needs it."""
    visible, masked = [k_lo >= 0], []
    if s_valid:
        masked.append(k_lo + bk > s_valid)
    if causal:
        visible.append(k_lo <= q_lo + bq - 1)
        masked.append(k_lo + bk - 1 > q_lo)
    if window > 0:
        visible.append(k_lo + bk - 1 > q_lo - window)
        masked.append(k_lo <= q_lo + bq - 1 - window)
    visible = functools.reduce(jnp.logical_and, visible)
    if not masked:
        pl.when(visible)(lambda: step(False))
        return
    masked = functools.reduce(jnp.logical_or, masked)
    pl.when(jnp.logical_and(visible, masked))(lambda: step(True))
    pl.when(jnp.logical_and(visible, jnp.logical_not(masked)))(
        lambda: step(False))


def _scaled_q(q_ref, scale, dt):
    return (q_ref[...].astype(jnp.float32) * scale).astype(dt)


def _cast(ref, dt):
    return ref[...].astype(dt)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, causal, window, s_valid, dt):
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(mask):
        s = jax.lax.dot_general(_scaled_q(q_ref, scale, dt), _cast(k_ref, dt),
                                _NT, preferred_element_type=jnp.float32)
        if mask:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_keep(qpos, kpos, causal=causal, window=window,
                                s_valid=s_valid), s, NEG_INF)
        m_prev = m_sc[...]                                   # (bq, 128)
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
        acc_sc[...] = alpha[:, :1] * acc_sc[...] + jnp.dot(
            p.astype(dt), _cast(v_ref, dt),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_next

    _when_visible(i * bq, j * bk, bq, bk, step, causal=causal, window=window,
                  s_valid=s_valid)

    @pl.when(j == pl.num_programs(3) - 1)
    def _store():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)
        # (bq, 128) lane-replicated -> the (1, bq) row of the lse block
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]


def _kv_block(i, j, *, bq, bk, nk, causal, window):
    """The KV block that query tile i reads at step j: j itself where the
    tile is visible, else the nearest visible one (no new fetch)."""
    if causal:
        j = jnp.minimum(j, (i * bq + bq - 1) // bk)
    if window > 0:
        j = jnp.maximum(j, jnp.maximum(i * bq - window + 1, 0) // bk)
    return jnp.minimum(j, nk - 1)


def _q_block(j, i, *, bq, bk, nq, causal, window):
    """The query tile that KV tile j reads at step i (as ``_kv_block``)."""
    if causal:
        i = jnp.maximum(i, (j * bk) // bq)
    if window > 0:
        i = jnp.minimum(i, (j * bk + bk + window - 2) // bq)
    return jnp.minimum(i, nq - 1)


def _check(q, k, bq, bk):
    b, h, s, d = q.shape
    kvh = k.shape[1]
    if h % kvh or k.shape != (b, kvh, s, d) or s % bq or s % bk:
        raise ValueError(f"flash attention: q {q.shape}, k {k.shape}, "
                         f"tiles ({bq}, {bk})")
    return b, h, kvh, s, d


def flash_attention_fwd(q, k, v, *, causal: bool, window: int, s_valid: int,
                        bq: int, bk: int, mxu_dtype, interpret: bool):
    """q (B, H, S, D), k/v (B, KVH, S, D) -> (o (B, H, S, D) in q.dtype,
    lse (B, H, 1, S) f32)."""
    b, h, kvh, s, d = _check(q, k, bq, bk)
    g, nq, nk = h // kvh, s // bq, s // bk
    s_valid = s_valid if s_valid < s else None
    kv_block = functools.partial(_kv_block, bq=bq, bk=bk, nk=nk,
                                 causal=causal, window=window)
    q_spec = pl.BlockSpec((None, None, bq, d), lambda b_, h_, i, j:
                          (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), lambda b_, h_, i, j:
                           (b_, h_ // g, kv_block(i, j), 0))
    row_spec = pl.BlockSpec((None, None, 1, bq), lambda b_, h_, i, j:
                            (b_, h_, 0, i))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, causal=causal,
                          window=window, s_valid=s_valid, dt=mxu_dtype),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        name="flash_attention_fwd",
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_sc,
               *, scale, causal, window, s_valid, dt):
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(mask):
        kb = _cast(k_ref, dt)
        s = jax.lax.dot_general(_scaled_q(q_ref, scale, dt), kb, _NT,
                                preferred_element_type=jnp.float32)
        if mask:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_keep(qpos, kpos, causal=causal, window=window,
                                s_valid=s_valid), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = jax.lax.dot_general(_cast(do_ref, dt), _cast(v_ref, dt), _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        acc_sc[...] += jnp.dot(ds.astype(dt), kb,
                               preferred_element_type=jnp.float32)

    _when_visible(i * bq, j * bk, bq, bk, step, causal=causal, window=window,
                  s_valid=s_valid)

    @pl.when(j == pl.num_programs(3) - 1)
    def _store():
        dq_ref[...] = (acc_sc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, causal, window, s_valid, dt):
    """Transposed tiles: rows are keys, columns queries, so the logsumexp
    and di rows broadcast down the sublanes."""
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(mask):
        qs = _scaled_q(q_ref, scale, dt)
        do = _cast(do_ref, dt)
        st = jax.lax.dot_general(_cast(k_ref, dt), qs, _NT,
                                 preferred_element_type=jnp.float32)
        if mask:
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(_keep(qpos, kpos, causal=causal, window=window,
                                 s_valid=s_valid), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[...])                     # (bk, bq)
        dv_sc[...] += jnp.dot(pt.astype(dt), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(_cast(v_ref, dt), do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_sc[...] += jnp.dot(dst.astype(dt), qs,
                              preferred_element_type=jnp.float32)

    _when_visible(i * bq, j * bk, bq, bk, step, causal=causal, window=window,
                  s_valid=s_valid)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1,
                             i == pl.num_programs(4) - 1))
    def _store():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, do, lse, di, *, causal: bool, window: int,
                        s_valid: int, bq: int, bk: int, mxu_dtype,
                        interpret: bool):
    """Gradients of :func:`flash_attention_fwd`. ``do`` in q's layout;
    ``lse`` its logsumexp; ``di`` (B, H, 1, S) f32 = rowsum(do * o).
    Returns dq, dk, dv in the dtypes of q, k, v."""
    b, h, kvh, s, d = _check(q, k, bq, bk)
    g, nq, nk = h // kvh, s // bq, s // bk
    s_valid = s_valid if s_valid < s else None
    kw = dict(scale=d ** -0.5, causal=causal, window=window, s_valid=s_valid,
              dt=mxu_dtype)

    kv_block = functools.partial(_kv_block, bq=bq, bk=bk, nk=nk,
                                 causal=causal, window=window)
    q_spec = pl.BlockSpec((None, None, bq, d), lambda b_, h_, i, j:
                          (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), lambda b_, h_, i, j:
                           (b_, h_ // g, kv_block(i, j), 0))
    row_spec = pl.BlockSpec((None, None, 1, bq), lambda b_, h_, i, j:
                            (b_, h_, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        name="flash_attention_dq",
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)

    q_block = functools.partial(_q_block, bq=bq, bk=bk, nq=nq,
                                causal=causal, window=window)
    qh_spec = pl.BlockSpec((None, None, bq, d), lambda b_, kh, j, g_, i:
                           (b_, kh * g + g_, q_block(j, i), 0))
    rowh_spec = pl.BlockSpec((None, None, 1, bq), lambda b_, kh, j, g_, i:
                             (b_, kh * g + g_, 0, q_block(j, i)))
    kvh_spec = pl.BlockSpec((None, None, bk, d), lambda b_, kh, j, g_, i:
                            (b_, kh, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(b, kvh, nk, g, nq),
        name="flash_attention_dkv",
        in_specs=[qh_spec, kvh_spec, kvh_spec, qh_spec, rowh_spec, rowh_spec],
        out_specs=[kvh_spec, kvh_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv
