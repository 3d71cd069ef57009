"""Train-step assembly: model loss -> grads -> clip -> optimizer -> WASI
subspace maintenance -> (optional) PowerSGD-compressed DP all-reduce.

One jittable pure function over a single TrainState pytree, so the same
step lowers for the single-pod (16x16) and multi-pod (2x16x16) meshes in
launch/dryrun.py and runs eagerly in CPU tests.

``make_train_step(..., mesh=...)`` wraps that same function in shard_map
over the policy's DP axes with FACTOR-ONLY gradient communication:
WASI-factored sites all-reduce their rank-K dL/dR directly (the factors
ARE the compressor — K(O+I) bytes instead of O*I), and the remaining
dense 2D sites go through the distributed/grad_compress.py PowerSGD path
whose small P/Q factors are the only thing that crosses the mesh. Every
non-gradient collective is a scalar (loss/metric pmeans). State stays
replicated except the PER-REPLICA buffers — PowerSGD error feedback and
ASI activation-subspace warm-starts — which carry a leading device axis
sharded over DP (each worker tracks its own local statistics; no sync
collective, see core/powersgd.py).

WASI maintenance per update mode:
* factored — every ``refresh_every`` steps, re-orthogonalize each (L, R)
  pair (wsi_refresh_factored: one fused CholeskyQR per pair). The refresh
  sits under jax.lax.cond so the 1 - 1/refresh_every majority of steps pay
  nothing for it (the step is jitted at the top level, where cond executes
  only the taken branch — a where-select would run the QR every step).
* project  — insert (L, R) from WSIState for the forward; after the
  optimizer updates W, run one WSI subspace iteration (paper Alg. 1).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.bind import extract_project_factors, map_factored
from repro.config import ModelConfig, TrainConfig
from repro.core.project import (
    init_project_states,
    project_forward_params,
    update_project_states,
)
from repro.core.powersgd import PowerSGDState
from repro.core.wsi import wsi_refresh_factored
from repro.distributed.grad_compress import compress_gradients, init_compression
from repro.distributed.sharding import MeshPolicy
from repro.optim import (
    clip_by_global_norm,
    init_optimizer,
    make_schedule,
    optimizer_update,
)
from repro.utils.scopes import OPTIMIZER, WSI_REFRESH, scoped


class TrainState(NamedTuple):
    params: Any
    opt: Any
    asi: Any            # ASI warm-start states (or None)
    wsi: Any            # project-mode WSIState dict (or None)
    psgd: Any           # PowerSGD compression states (or None)
    step: jax.Array


def make_train_state(key, params, cfg: ModelConfig, tcfg: TrainConfig, *,
                     asi_states=None, use_epsilon_ranks: bool = False,
                     dp_degree: int = 0) -> TrainState:
    """``dp_degree=D`` sizes the PowerSGD error buffers for a D-way DP mesh
    (per-replica error feedback, leading device axis); 0 = single device."""
    wsi = None
    if cfg.wasi.project:
        # converted checkpoints (api.convert.factorize, project mode) carry
        # {"w","L","R"}: strip the factors into warm WSI states so the
        # param tree stays dense and training resumes the stored subspace
        params, warm = extract_project_factors(params)
        wsi = init_project_states(params, cfg, use_epsilon=use_epsilon_ranks,
                                  warm=warm)
    psgd = None
    if tcfg.powersgd_rank > 0:
        psgd = init_compression(key, params, tcfg.powersgd_rank,
                                local_copies=dp_degree)
    if dp_degree and asi_states is not None:
        # ASI warm-starts are per-worker statistics (each replica tracks its
        # own local activation subspace — no sync collective): give every
        # leaf a leading device axis the DP step shards, like psgd.error.
        asi_states = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (dp_degree,) + x.shape),
            asi_states)
    return TrainState(params=params, opt=init_optimizer(params, tcfg),
                      asi=asi_states, wsi=wsi, psgd=psgd,
                      step=jnp.zeros((), jnp.int32))


def make_train_step(loss_fn, cfg: ModelConfig, tcfg: TrainConfig, *,
                    policy: MeshPolicy | None = None, mean_fn=None,
                    mesh: Mesh | None = None):
    """loss_fn(params, batch, cfg, states=..., policy=...) -> (loss, (ns, metrics)).

    Returns step(state, batch) -> (state, metrics).

    With ``mesh`` the step is shard_map'd data-parallel over the policy's
    batch axes (default ("data",)): the batch arrives sharded on its leading
    dim, cross-replica averaging is lax.pmean — rank-K dL/dR for factored
    sites, PowerSGD P/Q factors for dense sites when tcfg.powersgd_rank>0.
    The state must then come from ``make_train_state(..., dp_degree=D)``
    placed with :func:`dp_state_shardings`; batches with
    :func:`dp_batch_sharding`. ``mean_fn`` must be None when mesh is given.
    """
    schedule = make_schedule(tcfg)

    def build(mean_fn):
        return _build_step(loss_fn, cfg, tcfg, policy, mean_fn, schedule)

    if mesh is None:
        return build(mean_fn)

    if mean_fn is not None:
        raise ValueError("pass either mesh or mean_fn, not both")
    dp = _dp_axes(policy)
    for ax in dp:
        if ax not in mesh.axis_names:
            raise ValueError(f"policy batch axis {ax!r} not in mesh "
                             f"{mesh.axis_names}")

    def pmean(x):
        return jax.lax.pmean(x, dp)

    inner = build(pmean)

    def local_step(state: TrainState, batch):
        # per-replica state (PowerSGD error, ASI warm-starts) arrives as a
        # (1, ...) local shard of the (D, ...) buffer; the math runs on the
        # squeezed view and the device axis is restored on the way out.
        if state.psgd is not None:
            state = state._replace(psgd={
                k: s._replace(error=s.error[0])
                for k, s in state.psgd.items()})
        if state.asi is not None:
            state = state._replace(asi=jax.tree.map(lambda x: x[0],
                                                    state.asi))
        new_state, metrics = inner(state, batch)
        if new_state.psgd is not None:
            new_state = new_state._replace(psgd={
                k: s._replace(error=s.error[None])
                for k, s in new_state.psgd.items()})
        if new_state.asi is not None:
            new_state = new_state._replace(asi=jax.tree.map(
                lambda x: x[None], new_state.asi))
        # only the loss/metric scalars cross the mesh beyond the gradient
        # factors — pmean so every replica reports the global numbers
        metrics = jax.tree.map(pmean, metrics)
        return new_state, metrics

    from repro.distributed.collectives import shard_map

    def dp_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        sspecs = dp_state_specs(state, policy)
        bspecs = jax.tree.map(lambda _: P(dp), batch)
        return shard_map(local_step, mesh=mesh,
                         in_specs=(sspecs, bspecs),
                         out_specs=(sspecs, P()),
                         check_vma=False)(state, batch)

    return dp_step


def _build_step(loss_fn, cfg: ModelConfig, tcfg: TrainConfig,
                policy, mean_fn, schedule):
    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        params = state.params
        fwd_params = params
        if state.wsi is not None:
            fwd_params = project_forward_params(params, state.wsi)

        if tcfg.microbatch > 1:
            # gradient accumulation: scan over microbatches slices the batch
            # leading dim; activations (the HBM peak) shrink by the factor,
            # grads are averaged, ASI warm-start states thread through.
            nm = tcfg.microbatch

            def slice_mb(x):
                b = x.shape[0]
                return x.reshape((nm, b // nm) + x.shape[1:])

            mbatches = jax.tree.map(slice_mb, batch)

            def mb_step(carry, mb):
                acc, asi = carry
                (l, (asi2, mets)), g = jax.value_and_grad(
                    lambda p: loss_fn(p, mb, cfg, states=asi, policy=policy),
                    has_aux=True)(fwd_params)
                acc = jax.tree.map(lambda a, b: a + b / nm, acc, g)
                return (acc, asi2), (l, mets)

            zero = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), fwd_params)
            (grads, new_asi), (losses, metset) = jax.lax.scan(
                mb_step, (zero, state.asi), mbatches)
            loss = losses.mean()
            metrics = jax.tree.map(lambda m: m.mean(), metset)
        else:
            def lf(p):
                return loss_fn(p, batch, cfg, states=state.asi, policy=policy)

            (loss, (new_asi, metrics)), grads = jax.value_and_grad(
                lf, has_aux=True)(fwd_params)
        if state.wsi is not None:
            # strip gradient entries for the injected L/R (zeros by custom vjp)
            grads = jax.tree.map(lambda g: g, grads)
            grads = _strip_lr(grads, params)

        if state.psgd is not None:
            grads, new_psgd = compress_gradients(grads, state.psgd, mean_fn)
        else:
            new_psgd = None
            if mean_fn is not None:
                grads = jax.tree.map(mean_fn, grads)

        with jax.named_scope(OPTIMIZER):
            grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
            lr = schedule(state.step)
            new_params, new_opt = optimizer_update(params, grads, state.opt,
                                                   tcfg, lr)

        new_wsi = state.wsi
        if state.wsi is not None:
            # paper Alg. 1: one subspace iteration against the updated W
            new_wsi = update_project_states(new_params, state.wsi)
        elif cfg.wasi.factored and cfg.wasi.refresh_every > 0:
            do = (state.step + 1) % cfg.wasi.refresh_every == 0
            new_params = jax.lax.cond(
                do,
                scoped(WSI_REFRESH)(
                    lambda p: map_factored(p, wsi_refresh_factored)),
                lambda p: p,
                new_params)

        metrics = dict(metrics)
        metrics.update({"loss": loss, "grad_norm": gnorm, "lr": lr})
        return TrainState(params=new_params, opt=new_opt, asi=new_asi,
                          wsi=new_wsi, psgd=new_psgd,
                          step=state.step + 1), metrics

    return step


def _dp_axes(policy: MeshPolicy | None) -> tuple[str, ...]:
    return tuple(policy.batch) if policy is not None else ("data",)


def dp_state_specs(state: TrainState, policy: MeshPolicy | None = None):
    """PartitionSpecs for a DP TrainState: everything replicated except the
    per-replica buffers — PowerSGD error feedback and ASI warm-starts —
    whose leading device axis shards over the DP mesh axes."""
    dp = _dp_axes(policy)
    rep = jax.tree.map(lambda _: P(), state)
    if state.psgd is not None:
        rep = rep._replace(psgd={
            k: PowerSGDState(q=P(), error=P(dp)) for k in state.psgd})
    if state.asi is not None:
        rep = rep._replace(asi=jax.tree.map(lambda _: P(dp), state.asi))
    return rep


def dp_state_shardings(state: TrainState, mesh: Mesh,
                       policy: MeshPolicy | None = None):
    """NamedShardings for jax.device_put of a DP TrainState."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        dp_state_specs(state, policy),
                        is_leaf=lambda t: isinstance(t, P))


def dp_batch_sharding(mesh: Mesh, policy: MeshPolicy | None = None):
    """NamedSharding placing a batch's leading dim across the DP axes."""
    return NamedSharding(mesh, P(_dp_axes(policy)))


def _strip_lr(grads, params_template):
    """Zero-out/removal of grads for injected L/R keys absent in the real
    param tree (project mode: params hold w, fwd tree held w+L+R)."""
    def walk(g, p):
        if isinstance(p, dict):
            return {k: walk(g[k], p[k]) for k in p}
        if isinstance(p, list):
            return [walk(a, b) for a, b in zip(g, p)]
        if isinstance(p, tuple) and not hasattr(p, "_fields"):
            return tuple(walk(a, b) for a, b in zip(g, p))
        return g

    return walk(grads, params_template)
