"""Public jit'd wrappers around the Pallas kernels.

The route is chosen once, from the backend, when this module is imported:
``INTERPRET`` is False exactly when ``jax.default_backend() == "tpu"``.
On TPU the wrappers launch the compiled kernels; on any other backend they
take the einsum fallbacks, or run a kernel in Pallas interpret mode where
no fallback exists. No launcher sets it. A TPU that fails to initialise
leaves JAX on the CPU and this flag True, so ``chip_smoke.py`` asserts it
is False before it measures anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.gram import gram_tiled
from repro.kernels.lowrank import lowrank_bwd_tiled, lowrank_fused_tiled
from repro.kernels.matmul_tiled import matmul_tiled
from repro.kernels.qr import choleskyqr_tiled
from repro.kernels.quant import lowrank_q8_tiled
from repro.utils.scopes import ATTENTION, scoped

INTERPRET = jax.default_backend() != "tpu"

# VMEM headroom for the single-launch fused backward (kernels/lowrank.py):
# all five operand tiles plus the two (O,K)/(K,I) f32 accumulators must
# co-reside. Larger layers fall back to the XLA einsum backward.
_BWD_VMEM_BUDGET = 12 * 1024 * 1024

# Largest flash attention tile, query and key alike; chosen by timing the
# kernels on a TPU v5e (PERF.md).
FLASH_BLOCK = 1024


def _bwd_fits_vmem(m: int, o: int, i: int, k: int, bm: int = 128) -> bool:
    bm = min(bm, m)
    o_, i_, k_ = (-(-o // 128)) * 128, (-(-i // 128)) * 128, (-(-k // 128)) * 128
    tiles = bm * (o_ + 2 * i_ + 2 * k_) + 3 * (o_ * k_ + k_ * i_)
    return 4 * tiles <= _BWD_VMEM_BUDGET


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128):
    return matmul_tiled(a, b, bm=bm, bn=bn, bk=bk, interpret=INTERPRET)


@jax.custom_vjp
def _lowrank_fused(x2, r_factor, l_factor):
    """Fused (x R^T) L^T — the rank-K intermediate stays in VMEM."""
    return lowrank_fused_tiled(x2, r_factor.T, l_factor.T,
                               interpret=INTERPRET)


def _lowrank_fused_fwd(x2, r_factor, l_factor):
    # Sketch-saving forward: the kernel writes the rank-K sketch h = x R^T
    # out of its VMEM scratch once per row block, and h rides along as a
    # residual. The backward therefore never recomputes the projection
    # (2*M*I*K FLOPs saved) at a residual cost of M*K f32 — with the WASI
    # rank policy (K <= 0.5*I) that is at most half the x residual we
    # already keep for dR.
    y, h = lowrank_fused_tiled(x2, r_factor.T, l_factor.T, save_sketch=True,
                               interpret=INTERPRET)
    return y, (x2, h, r_factor, l_factor)


def _lowrank_fused_bwd(res, dy):
    x2, h, r_factor, l_factor = res
    m, i = x2.shape
    o, k = l_factor.shape
    if not INTERPRET and _bwd_fits_vmem(m, o, i, k):
        # single launch: dh = dy L stays VMEM-resident across dx, dL, dR
        dx, dl, dr = lowrank_bwd_tiled(dy, x2, h, l_factor, r_factor,
                                       interpret=INTERPRET)
        return dx, dr.astype(r_factor.dtype), dl.astype(l_factor.dtype)
    # XLA fallback (off-TPU, or layer too large for the VMEM budget);
    # consumes the saved sketch rather than recomputing it
    xf = x2.astype(jnp.float32)
    rf = r_factor.astype(jnp.float32)
    lf = l_factor.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dh = dyf @ lf                                   # (M, K)
    dx = (dh @ rf).astype(x2.dtype)
    dr = (dh.T @ xf).astype(r_factor.dtype)         # (K, I)
    dl = (dyf.T @ h).astype(l_factor.dtype)         # (O, K)
    return dx, dr, dl


_lowrank_fused.defvjp(_lowrank_fused_fwd, _lowrank_fused_bwd)


@jax.jit
def lowrank_bwd_fused(dy, x, h, l_factor, r_factor):
    """The fused backward kernel, unconditionally (tests/benchmarks).
    dy (M, O), x (M, I), h (M, K) = x @ R^T -> (dx, dL f32, dR f32)."""
    return lowrank_bwd_tiled(dy, x, h, l_factor, r_factor,
                             interpret=INTERPRET)


@jax.jit
def lowrank_matmul_fused(x, r_factor, l_factor):
    """The fused Pallas kernel, unconditionally (tests/benchmarks).
    x (..., I), R (K, I), L (O, K) -> (..., O). Leading dims flattened.
    One kernel launch; the (M, K) intermediate never round-trips HBM.
    Differentiable (custom VJP with exact rank-K backward)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _lowrank_fused(x2, r_factor, l_factor)
    return y.reshape(lead + (l_factor.shape[0],))


@jax.jit
def lowrank_matmul(x, r_factor, l_factor):
    """WASI factored linear (Eq. 8): y = (x @ R^T) @ L^T — the public entry
    every factored linear routes through.

    On TPU this is the FUSED kernel (rank-K intermediate stays in VMEM
    across both contractions). Off-TPU the kernel would run in interpret
    mode — measured ~2x slower than the XLA einsum pair — so the dispatch
    falls back there; callers get the fast path on every backend."""
    if INTERPRET:
        h = jnp.einsum("...i,ki->...k", x, r_factor)
        return jnp.einsum("...k,ok->...o", h, l_factor)
    return lowrank_matmul_fused(x, r_factor, l_factor)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def lowrank_matmul_unfused(x, r_factor, l_factor, *, bm: int = 128,
                           bn: int = 128, bk: int = 128):
    """Two-launch reference path (pre-fusion): kept for benchmarking the
    HBM round-trip the fused kernel removes (benchmarks/tab2_latency.py)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    h = matmul_tiled(x2, r_factor.T, bm=bm, bn=bn, bk=bk, interpret=INTERPRET)
    y = matmul_tiled(h, l_factor.T, bm=bm, bn=bn, bk=bk, interpret=INTERPRET)
    return y.reshape(lead + (l_factor.shape[0],))


@jax.jit
def lowrank_matmul_q8_fused(x, r_q, r_s, l_q, l_s):
    """The fused int8 Pallas kernel, unconditionally (tests/benchmarks).
    x (..., I); Rq int8 (K, I) + sR f32 (K,); Lq int8 (O, K) + sL f32 (O,)
    -> (..., O). One launch; int8 factors stay VMEM-resident, scales fold
    into the f32 accumulator, no dequantized weight is materialized."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = lowrank_q8_tiled(x2, r_q.T, r_s, l_q.T, l_s, interpret=INTERPRET)
    return y.reshape(lead + (l_q.shape[0],))


@jax.jit
def lowrank_matmul_q8(x, r_q, r_s, l_q, l_s):
    """Quantized factored linear: y = ((x Rq^T) * sR) Lq^T * sL — the
    public entry every int8-deployed factored linear routes through
    (api/bind.py dispatches here when the plan stamps ``quant="int8"``).

    On TPU this is the fused int8 kernel. Off-TPU the scale-folded einsum
    pair runs instead (same math, same f32 accumulation) — the per-channel
    scales multiply the rank-K intermediate and the output, so no
    dequantized O×I weight ever exists on either path."""
    if INTERPRET:
        xf = x.astype(jnp.float32)
        h = jnp.einsum("...i,ki->...k", xf, r_q.astype(jnp.float32)) * r_s
        y = jnp.einsum("...k,ok->...o", h, l_q.astype(jnp.float32)) * l_s
        return y.astype(x.dtype)
    return lowrank_matmul_q8_fused(x, r_q, r_s, l_q, l_s)


@jax.jit
def dense_matmul_q8(x, w_q, w_s):
    """Quantized DENSE linear: y = (x Wq^T) * sW. Kept as a scaled einsum
    on every backend — XLA fuses the int8->f32 convert into the matmul, so
    the dequantized weight lives only in registers/VMEM, never HBM; a
    dedicated kernel would buy nothing the lowrank one doesn't already
    demonstrate (dense sites are the untreated minority of a WASI plan)."""
    xf = x.astype(jnp.float32)
    y = jnp.einsum("...i,oi->...o", xf, w_q.astype(jnp.float32)) * w_s
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("bm",))
def gram(y, *, bm: int = 512):
    """G = Y^T Y (f32), the CholeskyQR reduction. y (M, K)."""
    return gram_tiled(y, bm=bm, interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("bm",))
def choleskyqr_fused(y, *, bm: int = 512):
    """The fused CholeskyQR kernel, unconditionally (tests/benchmarks).
    y (M, K) -> (Q (M, K), mix (K, K) f32 = Q^T Y) in one launch."""
    return choleskyqr_tiled(y, bm=bm, interpret=INTERPRET)


def cholesky_qr_mix(y):
    """(Q, M = Q^T Y) for the WSI factored refresh — the public entry
    core/wsi.py routes through.

    On TPU with a 2D operand this is the single-launch fused kernel
    (Gram -> in-kernel Cholesky/inverse -> apply; Y swept twice, nothing
    else touches HBM). Off-TPU, or with leading batch dims (stacked scan
    layers / expert banks), it falls back to the jnp CholeskyQR with the
    mix computed from the Gram factor — still sparing the second
    tall-skinny (M,K)^T (M,K) product either way."""
    if INTERPRET or y.ndim != 2:
        from repro.core.orthogonal import cholesky_qr_mix_ref
        return cholesky_qr_mix_ref(y)
    return choleskyqr_fused(y)


def _flash_tiles(s: int) -> tuple[int, int, int]:
    """(padded length, query tile, key tile) for a sequence of ``s``
    tokens: one tile up to ``FLASH_BLOCK``, else the largest power-of-two
    multiple of 128 up to it that divides ``s`` rounded up to 128."""
    if s <= FLASH_BLOCK:
        p = -(-s // 8) * 8
        return p, p, p
    p = -(-s // 128) * 128
    t = FLASH_BLOCK
    while p % t:
        t //= 2
    return p, t, t


def _heads_major(x, s_pad: int):
    """(B, S, heads, D) -> (B, heads, S_pad, D), zero rows appended."""
    x = x.transpose(0, 2, 1, 3)
    pad = s_pad - x.shape[2]
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


def _seq_major(x, s: int):
    return x[:, :, :s].transpose(0, 2, 1, 3)


def _attention_mxu_dtype():
    """Operand dtype of the flash kernel's matmuls: what XLA's DEFAULT
    precision gives dense attention's f32 einsums on this backend, one
    bf16 pass on TPU and f32 elsewhere."""
    return jnp.float32 if INTERPRET else jnp.bfloat16


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, mxu_dtype):
    return _flash_fwd(q, k, v, causal, window, mxu_dtype)[0]


@scoped(ATTENTION)
def _flash_fwd(q, k, v, causal, window, mxu_dtype):
    s = q.shape[1]
    s_pad, bq, bk = _flash_tiles(s)
    qt, kt, vt = (_heads_major(x, s_pad) for x in (q, k, v))
    o, lse = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                                 s_valid=s, bq=bq, bk=bk, mxu_dtype=mxu_dtype,
                                 interpret=INTERPRET)
    return _seq_major(o, s), (qt, kt, vt, o, lse)


@scoped(ATTENTION)
def _flash_bwd(causal, window, mxu_dtype, res, do):
    qt, kt, vt, o, lse = res
    s = do.shape[1]
    s_pad, bq, bk = _flash_tiles(s)
    dot = _heads_major(do, s_pad)
    # rowsum(dO * O) = rowsum(P * dP), the softmax gradient's shift; zero
    # on padded rows, whose dO is zero
    di = jnp.sum(dot.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    dq, dk, dv = flash_attention_bwd(qt, kt, vt, dot, lse, di, causal=causal,
                                     window=window, s_valid=s, bq=bq, bk=bk,
                                     mxu_dtype=mxu_dtype, interpret=INTERPRET)
    return _seq_major(dq, s), _seq_major(dk, s), _seq_major(dv, s)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """GQA flash attention with its own backward pass. q (B, S, H, dh);
    k/v (B, S, KVH, dh) with H a multiple of KVH -> (B, S, H, dh) in
    q.dtype. Differentiable: the gradients leave in q/k/v's dtypes.

    Heads move ahead of the sequence and S is padded to the tiles (padded
    keys are masked, padded query rows sliced off; their zero dO adds
    nothing to dk/dv). The forward saves only its output and the per-row
    logsumexp; the backward recomputes the score tiles in VMEM.
    ``nn.attention`` decides when a model takes this route.
    """
    return _flash(q, k, v, causal, window, _attention_mxu_dtype())
