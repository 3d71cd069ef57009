"""WASI linear layers: factored weights + compressed saved activations.

This is the paper's core contribution as a composable JAX primitive. Three
custom-VJP matmul variants cover the paper's experiment matrix:

  wasi_matmul    — factored W = L R  AND  ASI-compressed residuals  (WASI)
  asi_matmul     — dense W, ASI-compressed residuals                (ASI)
  wasi_matmul_project — forward through (L, R) but gradient delivered to the
                   FULL W via f_LR (paper Eq. 9-11 "project" update mode)

Math (3D activations; 4D analogous — paper App. A.1):
  forward   y = (x R^T) L^T                       (Eq. 8)
  dx        = (dy L) R                            (Eq. 10)
  dL[o,k]   = sum_bn dy[b,n,o] h~[b,n,k],  h~ = x~ R^T
  dR[k,i]   = sum_bn dh[b,n,k] x~[b,n,i],  dh = dy L
  dW[o,i]   = sum_bn dy[b,n,o] x~[b,n,i]          (project mode, Eqs. 15-18)

where x~ is the Tucker form of x — the contractions consume the factors
directly (core/asi.flr_weight_grad_*), the dense activation is NEVER rebuilt.
Key trick: h~ = x~ R^T is itself a Tucker tensor whose last-mode factor is
(R @ U_last); so dL reuses the same f_LR kernel as dW.

SKETCH-SAVING RESIDUALS: the custom-VJP boundary is what makes the paper's
memory claim real — JAX saves exactly what the fwd rule returns, nothing
else. ``wasi_matmul`` saves the Tucker factors of x~ plus the rank-K sketch
h~ = x~ R^T (itself in Tucker form: same core, last factor R @ U_last,
materialized at FORWARD time so backward does zero residual rebuilding) —
never the (B, N, I) activation. ``measured_residual_bytes`` in
utils/memprof.py verifies this against a jax.vjp probe; the no-ASI factored
path gets the analogous treatment in kernels/ops.py (dense rank-K sketch
saved by the fused Pallas forward, consumed by the single-launch backward).

The ASI warm-start state is threaded functionally: compress() is called on a
stop-gradient copy of x OUTSIDE the custom-VJP boundary and its output rides
in as residual-only input (zero cotangent).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.asi import (
    ASIState,
    TuckerFactors,
    asi_init,
    asi_step,
    flr_weight_grad_3d,
    flr_weight_grad_4d,
)
from repro.utils.scopes import GRAD, MATMUL, scoped


def _flr(xt: TuckerFactors, dy: jax.Array) -> jax.Array:
    """Dispatch f_LR on activation tensor order (3D/4D)."""
    if dy.ndim == 3:
        return flr_weight_grad_3d(xt, dy)
    if dy.ndim == 4:
        return flr_weight_grad_4d(xt, dy)
    raise ValueError(f"f_LR supports 3D/4D activations, got ndim={dy.ndim}")


@scoped(MATMUL)
def _project_last_mode(xt: TuckerFactors, r: jax.Array) -> TuckerFactors:
    """Tucker form of (x~ contracted with R^T on the feature mode):
    replace last factor U_I (I, r_m) by R @ U_I (K, r_m). If the feature
    mode is identity (None), R itself becomes the factor (K, I)."""
    last = xt.us[-1]
    new_last = r if last is None else r.astype(last.dtype) @ last
    return TuckerFactors(core=xt.core, us=xt.us[:-1] + (new_last,))


# ---------------------------------------------------------------------------
# WASI: factored weights, compressed residuals (the scale branch).
# ---------------------------------------------------------------------------

@jax.custom_vjp
@scoped(MATMUL)
def wasi_matmul(x: jax.Array, L: jax.Array, R: jax.Array, xt: TuckerFactors):
    """y = (x @ R^T) @ L^T with Tucker residuals. x: (..., I) -> (..., O)."""
    h = jnp.einsum("...i,ki->...k", x, R)
    return jnp.einsum("...k,ok->...o", h, L)


def _wasi_fwd(x, L, R, xt):
    y = wasi_matmul(x, L, R, xt)
    # Residuals are the SKETCH, not the activation: the Tucker factors of
    # x~ plus h~ = x~ R^T in Tucker form (shares x~'s core; only the K×r_m
    # last factor is new, built here at forward time). x itself is dropped
    # at this boundary — residual bytes per linear are
    # tucker_storage(shape, ranks) + K*r_m + |L| + |R| instead of B*N*I
    # (utils/memprof.py measures exactly this via a jax.vjp probe).
    ht = _project_last_mode(xt, R)
    return y, (xt, ht, L, R)


@scoped(GRAD)
def _wasi_bwd(res, dy):
    xt, ht, L, R = res
    dh = jnp.einsum("...o,ok->...k", dy, L)            # (B,N,K)
    dx = jnp.einsum("...k,ki->...i", dh, R)            # Eq. 10
    # _flr returns dW[o,i] for dy[...,o], act[...,i]; here the activation is
    # h~ whose feature dim is K, so this is directly dL (O, K).
    dL = _flr(ht, dy)
    dR = _flr(xt, dh)                                   # "o"=K, "i"=I -> (K,I)
    zeros_xt = jax.tree.map(jnp.zeros_like, xt)
    return dx, dL.astype(L.dtype), dR.astype(R.dtype), zeros_xt


wasi_matmul.defvjp(_wasi_fwd, _wasi_bwd)


# ---------------------------------------------------------------------------
# ASI-only: dense weight, compressed residuals (paper's ASI baseline).
# ---------------------------------------------------------------------------

@jax.custom_vjp
@scoped(MATMUL)
def asi_matmul(x: jax.Array, w: jax.Array, xt: TuckerFactors):
    """y = x @ W^T with Tucker residuals. w: (O, I)."""
    return jnp.einsum("...i,oi->...o", x, w)


def _asi_fwd(x, w, xt):
    return asi_matmul(x, w, xt), (xt, w)


@scoped(GRAD)
def _asi_bwd(res, dy):
    xt, w = res
    dx = jnp.einsum("...o,oi->...i", dy, w)
    dw = _flr(xt, dy)
    zeros_xt = jax.tree.map(jnp.zeros_like, xt)
    return dx, dw.astype(w.dtype), zeros_xt


asi_matmul.defvjp(_asi_fwd, _asi_bwd)


# ---------------------------------------------------------------------------
# Project mode: paper-faithful Eq. 9-11 (full W param, factored forward).
# ---------------------------------------------------------------------------

@jax.custom_vjp
@scoped(MATMUL)
def wasi_matmul_project(x, w, L, R, xt: TuckerFactors):
    """Forward uses the factors; gradient lands on the full W (Eq. 11).
    L, R are derived from W by WSI *outside* the step (non-trainable here)."""
    h = jnp.einsum("...i,ki->...k", x, R)
    return jnp.einsum("...k,ok->...o", h, L)


def _wasi_proj_fwd(x, w, L, R, xt):
    return wasi_matmul_project(x, w, L, R, xt), (xt, L, R)


@scoped(GRAD)
def _wasi_proj_bwd(res, dy):
    xt, L, R = res
    dx = jnp.einsum("...o,ok,ki->...i", dy, L, R)       # Eq. 10
    dw = _flr(xt, dy)                                   # Eqs. 15-18: dW~
    zeros_xt = jax.tree.map(jnp.zeros_like, xt)
    return dx, dw, jnp.zeros_like(L), jnp.zeros_like(R), zeros_xt


wasi_matmul_project.defvjp(_wasi_proj_fwd, _wasi_proj_bwd)


@jax.custom_vjp
@scoped(MATMUL)
def wsi_matmul_project_exact(x, w, L, R):
    """Project mode without activation compression (WSI ablation): factored
    forward, EXACT dense gradient dW = dy^T x (residual: uncompressed x)."""
    h = jnp.einsum("...i,ki->...k", x, R)
    return jnp.einsum("...k,ok->...o", h, L)


def _wsi_proj_exact_fwd(x, w, L, R):
    return wsi_matmul_project_exact(x, w, L, R), (x, L, R)


@scoped(GRAD)
def _wsi_proj_exact_bwd(res, dy):
    x, L, R = res
    dx = jnp.einsum("...o,ok,ki->...i", dy, L, R)
    dw = jnp.einsum("...o,...i->oi", dy, x)
    return dx, dw, jnp.zeros_like(L), jnp.zeros_like(R)


wsi_matmul_project_exact.defvjp(_wsi_proj_exact_fwd, _wsi_proj_exact_bwd)


# ---------------------------------------------------------------------------
# Module-level convenience: compress-then-matmul with threaded ASI state.
# ---------------------------------------------------------------------------

class WasiLinearParams(NamedTuple):
    L: jax.Array           # (O, K)
    R: jax.Array           # (K, I)
    bias: jax.Array | None = None


def init_wasi_linear(key, in_dim: int, out_dim: int, rank: int, *,
                     bias: bool = False, dtype=jnp.float32,
                     scale: float | None = None) -> WasiLinearParams:
    """Initialize factored linear. The product L R matches a LeCun-normal
    dense init in expectation: both factors get std (fan_in)^-1/4-ish split;
    we draw a dense W then factor exactly via its top-K subspace? That costs
    an SVD per layer at init — instead we use the variance-preserving split
    std_L = std_R = (std_W / sqrt(K))^0.5 heuristic (tested: output variance
    matches dense init within 10%)."""
    kl, kr, kb = jax.random.split(key, 3)
    std_w = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    split = jnp.sqrt(std_w / jnp.sqrt(rank))
    L = (jax.random.normal(kl, (out_dim, rank), jnp.float32) * split).astype(dtype)
    R = (jax.random.normal(kr, (rank, in_dim), jnp.float32) * split).astype(dtype)
    b = jnp.zeros((out_dim,), dtype) if bias else None
    return WasiLinearParams(L=L, R=R, bias=b)


def init_asi_state_for(key, act_shape: Sequence[int], ranks: Sequence[int],
                       dtype=jnp.float32) -> ASIState:
    return asi_init(key, act_shape, ranks, dtype)


def wasi_linear_apply(params: WasiLinearParams, x: jax.Array,
                      asi_state: ASIState | None):
    """Apply a WASI linear. Returns (y, new_asi_state).

    If ``asi_state`` is None the layer runs without activation compression
    (inference / serve path, or ASI disabled) — the fused kernel path then
    applies, with exact gradients from its sketch-saving custom VJP.
    """
    if asi_state is None:
        from repro.kernels.ops import lowrank_matmul  # kernel on TPU

        y = lowrank_matmul(x, params.R, params.L)
    else:
        xt, new_state = asi_step(jax.lax.stop_gradient(x), asi_state)
        y = wasi_matmul(x, params.L, params.R, xt)
    if params.bias is not None:
        y = y + params.bias
    return y, (new_state if asi_state is not None else None)
