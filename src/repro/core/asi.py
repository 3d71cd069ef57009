"""Activation Subspace Iteration (paper §3.2, Alg. 2, App. A.1).

Compresses a saved-for-backward activation tensor A (3D: B×N×I, or 4D:
B×H×W×I) into a Tucker form

    A ~= S ×_1 U1 ×_2 U2 ... ×_m Um

with fixed per-mode ranks r, maintained across training steps by ONE
warm-started power-iteration per mode (PowerSGD-style; Vogels et al. 2019):

    t = 0 : V ~ N(0,1);                 U_m = orth(A_(m) V)
    t > 0 : V = A_(m)^T U_m^{(t-1)};    U_m = orth(A_(m) V)

Storage drops from prod(D) to prod(r) + sum(D_m * r_m)  (paper Eq. 31/44).

TPU adaptation: unfoldings are expressed as reshapes+transposes feeding plain
matmuls (MXU), mode products via einsum; orthogonalization via CholeskyQR.
All functions are shape-polymorphic over leading batch dims and jit/scansafe.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.orthogonal import cholesky_qr
from repro.utils.scopes import ASI_UPKEEP, scoped


class TuckerFactors(NamedTuple):
    """Tucker core + per-mode factor matrices. ``core``: (r1,...,rm);
    ``us``: tuple of (D_m, r_m) matrices with orthonormal columns — or None
    for a mode kept at FULL rank (identity factor; the scale default for the
    batch mode, which keeps the compression DP-shard-local AND skips the
    dense (B,B) rotation that would otherwise dominate f_LR FLOPs)."""

    core: jax.Array
    us: tuple


class ASIState(NamedTuple):
    """Warm-start state carried across training steps: per-mode factors."""

    us: tuple  # tuple of (D_m, r_m)


def _unfold(a: jax.Array, mode: int) -> jax.Array:
    """Mode-m unfolding: (D_m, prod_{j!=m} D_j)."""
    order = (mode,) + tuple(i for i in range(a.ndim) if i != mode)
    return jnp.transpose(a, order).reshape(a.shape[mode], -1)


def _mode_product(t: jax.Array, m: jax.Array, mode: int) -> jax.Array:
    """t ×_mode m  where m: (Q, D_mode) — contracts D_mode (paper Eq. 27)."""
    t2 = jnp.moveaxis(t, mode, -1)
    out = jnp.einsum("...d,qd->...q", t2, m)
    return jnp.moveaxis(out, -1, mode)


def asi_init(key: jax.Array, shape: Sequence[int], ranks: Sequence[int],
             dtype=jnp.float32) -> ASIState:
    """t=0 warm-start: random orthonormal factors (Alg. 2 line 7).
    rank == dim => identity mode (factor None, no iteration ever)."""
    us = []
    for d, r in zip(shape, ranks):
        if r >= d:
            us.append(None)
            continue
        key, sub = jax.random.split(key)
        v = jax.random.normal(sub, (d, r), jnp.float32)
        us.append(cholesky_qr(v).astype(dtype))
    return ASIState(us=tuple(us))


def _gram_last(v: jax.Array) -> jax.Array:
    """(r, r) Gram over ALL leading dims of v (..., r) — pure contraction,
    no reshape (a sharded leading dim stays a contraction dim)."""
    axes = tuple(range(v.ndim - 1))
    return jnp.tensordot(v, v, axes=(axes, axes))


def _orth_last(v: jax.Array, shift: float = 1e-6) -> jax.Array:
    """Orthonormalize the last axis of v against all leading dims via
    shifted Cholesky (tensor CholeskyQR; same shift ladder as
    core/orthogonal.cholesky_qr)."""
    vf = v.astype(jnp.float32)
    g = _gram_last(vf)
    r = g.shape[-1]
    scale = jnp.maximum(jnp.trace(g) / r, 1e-30)
    eye = jnp.eye(r, dtype=g.dtype)
    c1 = jnp.linalg.cholesky(g + shift * scale * eye)
    c2 = jnp.linalg.cholesky(g + 1e4 * shift * scale * eye)
    c = jnp.where(jnp.isfinite(c1).all(), c1, c2)
    inv = jax.scipy.linalg.solve_triangular(c, eye, lower=True)  # C^{-1}
    return jnp.einsum("...r,jr->...j", vf, inv)


@scoped(ASI_UPKEEP)
def asi_project(a: jax.Array, state: ASIState) -> TuckerFactors:
    """Project ``a`` onto the EXISTING factors (no power iteration) — the
    cheap steady-state compression when refreshes are amortized."""
    core = a
    for mode, u in enumerate(state.us):
        if u is None:
            continue
        core = _mode_product(core, u.T.astype(a.dtype), mode)
    return TuckerFactors(core=core, us=state.us)


@scoped(ASI_UPKEEP)
def asi_step(a: jax.Array, state: ASIState) -> tuple[TuckerFactors, ASIState]:
    """One warm-started subspace-iteration Tucker compression (Alg. 2).

    Returns the factors approximating ``a`` and the refreshed warm-start
    state to feed the next training step.

    RESHAPE-FREE: the textbook mode-m unfolding (D_m, prod other dims) puts
    the sharded batch dim INSIDE the merged axis, which GSPMD cannot
    represent — it all-gathers the whole activation per mode per linear
    (measured 150+ GiB/device on zamba2; EXPERIMENTS.md §Perf iter. 6). All
    unfolding matmuls are therefore expressed as tensor contractions over
    the ORIGINAL dims: sharded dims remain contraction dims and only (D_m,r)
    / (r,r) partials cross shards.
    """
    new_us = []
    core = a
    rest_axes = None
    for mode, u_prev in enumerate(state.us):
        if u_prev is None:  # identity (full-rank) mode: nothing to iterate
            new_us.append(None)
            continue
        af = a.astype(jnp.float32)
        rest = tuple(i for i in range(a.ndim) if i != mode)
        # v = A^T U  without unfolding: contract D_m, keep rest dims + r
        v = _mode_product(af, u_prev.astype(jnp.float32).T, mode)
        v = jnp.moveaxis(v, mode, -1)              # (..., r) rest-ordered
        # stage-wise orthogonalization (cond^2 per stage, see orthogonal.py)
        v = _orth_last(v)
        v = jnp.moveaxis(v, -1, mode)              # r back at mode position
        # u = orth(A V): contract ALL rest dims of a with those of v
        u = jnp.tensordot(af, v, axes=(rest, rest))  # (D_m, r)
        u = cholesky_qr(u).astype(a.dtype)
        new_us.append(u)
        core = _mode_product(core, u.T.astype(a.dtype), mode)  # project
    return TuckerFactors(core=core, us=tuple(new_us)), ASIState(us=tuple(new_us))


def tucker_reconstruct(f: TuckerFactors) -> jax.Array:
    """A~ = S ×_1 U1 ... ×_m Um (oracle / tests; backward never calls this
    at scale — it consumes the factors directly, see core/lowrank_linear)."""
    out = f.core
    for mode, u in enumerate(f.us):
        if u is None:
            continue
        out = _mode_product(out, u, mode)
    return out


def tucker_storage(shape: Sequence[int], ranks: Sequence[int]) -> int:
    """Element count of the compressed form (paper Eq. 31/44)."""
    prod_r = 1
    for r in ranks:
        prod_r *= r
    return prod_r + sum(d * r for d, r in zip(shape, ranks))


def compression_ratio(shape: Sequence[int], ranks: Sequence[int]) -> float:
    dense = 1
    for d in shape:
        dense *= d
    return dense / tucker_storage(shape, ranks)


def tucker_rel_error(a: jax.Array, f: TuckerFactors) -> jax.Array:
    """||A - A~||_F / ||A||_F."""
    diff = a.astype(jnp.float32) - tucker_reconstruct(f).astype(jnp.float32)
    return jnp.linalg.norm(diff) / jnp.maximum(jnp.linalg.norm(a.astype(jnp.float32)), 1e-30)


# ---------------------------------------------------------------------------
# f_LR — weight gradient straight from Tucker factors (paper App. A.1).
# ---------------------------------------------------------------------------

def _flr_general(f: TuckerFactors, dy: jax.Array) -> jax.Array:
    """dW for ANY None pattern of Tucker factors: partially reconstruct all
    modes but the feature mode (so the biggest intermediate is dy-sized,
    never the dense activation), contract with dy over every position dim,
    then expand the feature factor. Fallback for factor patterns the
    specialized reorderings below don't cover (e.g. compressed batch with
    identity token mode)."""
    t = f.core
    for mode, u in enumerate(f.us[:-1]):
        if u is not None:
            t = _mode_product(t, u, mode)           # expand (D_m, r_m)
    lead = tuple(range(dy.ndim - 1))
    g = jnp.tensordot(dy, t, axes=(lead, lead))     # (O, r_last or I)
    u_last = f.us[-1]
    return g if u_last is None else jnp.einsum("ot,it->oi", g, u_last)


def flr_weight_grad_3d(f: TuckerFactors, dy: jax.Array) -> jax.Array:
    """dW (O,I) from Tucker-compressed A (B,N,I) and dy (B,N,O).

    General path implements Eqs. 15-18 via reordered contractions so the
    dense (B,N,I) activation is never rebuilt:
        Z1[n,o,r1]   = sum_b dy[b,n,o] U1[b,r1]
        Z2[r1,r3,n]  = sum_r2 S[r1,r2,r3] U2[n,r2]
        Z3[r1,i,n]   = sum_r3 Z2[r1,r3,n] U3[i,r3]
        dW[o,i]      = sum_{n,r1} Z1[n,o,r1] Z3[r1,i,n]

    Identity-batch path (u1 is None — the sharding-friendly scale mode):
    contract the small ranks FIRST so no (r1, I, N)-sized intermediate ever
    exists:
        T[b,q,o]  = sum_n dy[b,n,o] U2[n,q]          (or dy directly if u2 None)
        G[t,o]    = sum_{b,q} S[b,q,t] T[b,q,o]
        dW[o,i]   = sum_t G[t,o] U3[i,t]
    """
    s, (u1, u2, u3) = f.core, f.us
    if u1 is None:
        # batch mode at full rank: core is (B, r2, r3)
        t = dy if u2 is None else jnp.einsum("bno,nq->bqo", dy, u2)
        if u3 is None:
            return jnp.einsum("bqi,bqo->oi", s, t)
        g = jnp.einsum("bqt,bqo->to", s, t)
        return jnp.einsum("to,it->oi", g, u3)
    if u2 is None or u3 is None:
        return _flr_general(f, dy)
    z1 = jnp.einsum("bno,br->nor", dy, u1)          # Eq. 15
    z2 = jnp.einsum("rqt,nq->rtn", s, u2)           # Eq. 16 (r=r1,q=r2,t=r3)
    z3 = jnp.einsum("rtn,it->rin", z2, u3)          # Eq. 17
    return jnp.einsum("nor,rin->oi", z1, z3)        # Eq. 18


def flr_weight_grad_4d(f: TuckerFactors, dy: jax.Array) -> jax.Array:
    """dW (O,I) from Tucker-compressed A (B,H,W,I) and dy (B,H,W,O).

    Eqs. 22-26 analogue (same reordering idea, one extra mode).
    """
    s, (u1, u2, u3, u4) = f.core, f.us
    if u1 is None:
        # identity batch mode: core (B, r2, r3, r4)
        t = dy
        if u2 is not None:
            t = jnp.einsum("bhwo,hq->bqwo", t, u2)
        if u3 is not None:
            t = jnp.einsum("bqwo,wt->bqto", t, u3)
        if u4 is None:
            return jnp.einsum("bqti,bqto->oi", s, t)
        g = jnp.einsum("bqtf,bqto->fo", s, t)
        return jnp.einsum("fo,if->oi", g, u4)
    if u2 is None or u3 is None or u4 is None:
        return _flr_general(f, dy)
    z1 = jnp.einsum("bhwo,br->rhwo", dy, u1)        # Eq. 22
    z2 = jnp.einsum("rqtf,hq->rhtf", s, u2)         # Eq. 23
    z3 = jnp.einsum("rhwo,wt->rhto", z1, u3)        # Eq. 24
    z4 = jnp.einsum("rhtf,if->rhit", z2, u4)        # Eq. 25
    return jnp.einsum("rhto,rhit->oi", z3, z4)      # Eq. 26
