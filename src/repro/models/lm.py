"""Unified decoder language model over layer-group scans.

Depth is organized as ``cfg.groups``: a list of (pattern, repeat) where
``pattern`` is a tuple of BlockKinds. Each group scans over ``repeat`` with
its pattern unrolled inside the scan body — HLO size stays independent of
total depth while supporting heterogeneous stacks (gemma3's 5 local : 1
global, zamba2's shared-attention interleave, deepseek's dense layer 0).

Parameters for a group are the per-pattern-position block params stacked on
a leading ``repeat`` axis (initialized via vmap over split keys). ASI
warm-start states and decode caches mirror the same structure, riding
through the scan as xs/ys.

Entry points:
    init_lm / init_lm_states / init_lm_cache
    lm_forward(...)            train/prefill logits (+ caches optionally)
    lm_loss(...)               cross-entropy train objective
    lm_decode_step(...)        one-token serve step
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import MeshPolicy, shard
from repro.models.blocks import (
    apply_block,
    init_block,
    init_block_cache,
    init_block_state,
)
from repro.nn.attention import init_attention, layers_per_call
from repro.nn.norms import apply_norm, init_norm
from repro.utils.scopes import EMBED, HEAD, scoped


def _needs_shared(cfg: ModelConfig) -> bool:
    return any("mamba2_attn" in g.pattern for g in cfg.groups)


def init_lm(key, cfg: ModelConfig, dtype=jnp.float32, *, plan=None) -> dict:
    """Init params in the layouts the SubspacePlan dictates. ``plan`` (an
    explicitly resolved SubspacePlan, e.g. with calibrated eps-ranks) is
    installed so every linear below reads it; default is the memoized
    static resolution for ``cfg`` (api.plan_of)."""
    if plan is not None:
        from repro.api import install
        install(plan)
    keys = jax.random.split(key, len(cfg.groups) + 4)
    d, v = cfg.d_model, cfg.padded_vocab
    params: dict[str, Any] = {
        "embed": {"w": (jax.random.normal(keys[0], (v, d), jnp.float32) * 0.02).astype(dtype)},
        "final_norm": init_norm(cfg.norm, d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": (jax.random.normal(keys[1], (v, d), jnp.float32)
                                   * d ** -0.5).astype(dtype)}
    if _needs_shared(cfg):
        from repro.nn.mlp import init_mlp

        k_sh1, k_sh2 = jax.random.split(keys[2])
        params["shared_attn"] = {"ln": init_norm(cfg.norm, d, dtype),
                                 "attn": init_attention(k_sh1, cfg, dtype),
                                 "ln2": init_norm(cfg.norm, d, dtype),
                                 "mlp": init_mlp(k_sh2, cfg, dtype=dtype)}
    groups = []
    for gi, g in enumerate(cfg.groups):
        gkey = jax.random.fold_in(keys[3], gi)
        stacked = []
        for pi, kind in enumerate(g.pattern):
            pkeys = jax.random.split(jax.random.fold_in(gkey, pi), g.repeat)
            stacked.append(jax.vmap(
                lambda k, kind=kind: init_block(k, kind, cfg, dtype))(pkeys))
        groups.append(stacked)
    params["groups"] = groups
    return params


def init_lm_states(key, cfg: ModelConfig, batch: int, seq: int,
                   dtype=jnp.float32) -> list:
    """ASI warm-start states, mirroring params['groups'] structure."""
    out = []
    for gi, g in enumerate(cfg.groups):
        gkey = jax.random.fold_in(key, gi)
        stacked = []
        for pi, kind in enumerate(g.pattern):
            pkeys = jax.random.split(jax.random.fold_in(gkey, pi), g.repeat)
            stacked.append(jax.vmap(
                lambda k, kind=kind: init_block_state(k, kind, cfg, batch, seq, dtype)
            )(pkeys))
        out.append(stacked)
    return out


def init_lm_cache(cfg: ModelConfig, batch: int, seq: int,
                  dtype=jnp.bfloat16, *,
                  pages: int | None = None,
                  page_size: int | None = None) -> list:
    """Decode caches, mirroring params['groups'] structure (stacked).

    With ``pages``/``page_size`` set, full-attention KV caches become
    per-layer PAGED pools of shape (repeat, pages, page_size, KVH, Dh)
    shared by all serve slots — ``batch``/``seq`` then only bound the
    LOGICAL per-slot view the engine gathers through its page table
    (serve/kvpool.py), decoupling live slot count from ``max_cache``.
    Only causal full-attention layers can be paged; sliding-window and
    recurrent (Mamba) caches raise — the engine gates paged mode to
    configs where every layer qualifies (``supports_paging``)."""
    if (pages is None) != (page_size is None):
        raise ValueError("pages and page_size must be given together")
    out = []
    for g in cfg.groups:
        stacked = []
        for kind in g.pattern:
            if pages is None:
                one = init_block_cache(kind, cfg, batch, seq, dtype)
            else:
                from repro.models.blocks import block_window
                from repro.nn.attention import init_paged_cache

                if kind not in ("dense", "moe") or block_window(kind, cfg):
                    raise ValueError(
                        f"block kind {kind!r} cannot use a paged KV cache "
                        "(causal full attention only)")
                one = {"kv": init_paged_cache(cfg, pages, page_size, dtype)}
            stacked.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (g.repeat,) + x.shape), one))
        out.append(stacked)
    return out


def supports_paging(cfg: ModelConfig) -> bool:
    """True when every layer's decode state can live in a paged pool:
    causal full attention only (no sliding window, no recurrent SSM/conv
    state, no shared-attention interleave)."""
    from repro.models.blocks import block_window

    return all(kind in ("dense", "moe") and not block_window(kind, cfg)
               for g in cfg.groups for kind in g.pattern)


def _empty_like_states(cfg: ModelConfig) -> list:
    """Leafless states structure for paths with ASI off (serve)."""
    return [[{} for _ in g.pattern] for g in cfg.groups]


def _group_scan(cfg: ModelConfig, gi: int, x, gparams, gstates, gcaches,
                shared, pos, policy, with_states: bool, valid_len=None,
                page_table=None):
    """Scan one layer group. gparams/gstates/gcaches: list per pattern pos."""
    g = cfg.groups[gi]

    n_pat = len(g.pattern)
    with_caches = gcaches is not None

    def body(h, xs):
        pslices, sslices, cslices = xs
        new_s, new_c = [], []
        aux_sum = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(g.pattern):
            h, nc, ns, aux = apply_block(
                kind, pslices[j], h, cfg, shared=shared,
                cache=cslices[j] if with_caches else None,
                pos=pos, states=sslices[j] if with_states else None,
                policy=policy, valid_len=valid_len, page_table=page_table)
            # SP residual storage: the tensor saved at the remat boundary
            # is seq-sharded on the model axis (EXPERIMENTS.md §Perf)
            h = shard(h, policy, "batch", "seq_resid", None)
            new_s.append(ns if with_states else {})
            new_c.append(nc if with_caches else {})
            aux_sum = aux_sum + aux
        return h, (new_s, new_c, aux_sum)

    if cfg.remat == "block":
        body = jax.checkpoint(body)

    # scan requires every xs leaf to carry a leading ``repeat`` dim; disabled
    # states/caches ride as empty dicts (no leaves) -- structure-safe.
    xs = (gparams,
          gstates if with_states else [{}] * n_pat,
          gcaches if with_caches else [{}] * n_pat)
    with layers_per_call(g.repeat):
        x, (ns, nc, aux) = jax.lax.scan(body, x, xs)
    return x, ns, (nc if with_caches else None), aux


def lm_backbone(params, x, cfg: ModelConfig, *, states=None, caches=None,
                pos=None, policy: MeshPolicy | None = None, valid_len=None,
                page_table=None):
    """Run embedded hidden states through all layer groups.
    Returns (x, new_states, new_caches, aux)."""
    shared = params.get("shared_attn")
    with_states = states is not None
    new_states, new_caches = [], []
    aux_total = jnp.zeros((), jnp.float32)
    for gi in range(len(cfg.groups)):
        x, ns, nc, aux = _group_scan(
            cfg, gi, x, params["groups"][gi],
            states[gi] if with_states else None,
            caches[gi] if caches is not None else None,
            shared, pos, policy, with_states, valid_len, page_table)
        new_states.append(ns)
        new_caches.append(nc)
        aux_total = aux_total + aux.sum()
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return x, (new_states if with_states else None), \
        (new_caches if caches is not None else None), aux_total


@scoped(EMBED)
def _embed(params, tokens, cfg: ModelConfig):
    """Rows of the embedding for ``tokens``, in the model's dtype."""
    return params["embed"]["w"].astype(jnp.float32)[tokens].astype(
        jnp.dtype(cfg.dtype))


@scoped(HEAD)
def _logits(params, x, cfg: ModelConfig, policy):
    head = params["embed"]["w"] if cfg.tie_embeddings else params["lm_head"]["w"]
    logits = jnp.einsum("bsd,vd->bsv", x, head)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return shard(logits, policy, "batch", "seq", "model")


def lm_forward(params, tokens, cfg: ModelConfig, *, states=None, caches=None,
               pos=None, policy: MeshPolicy | None = None):
    """tokens (B, S) -> logits (B, S, V). Returns (logits, states, caches, aux).

    Float ``tokens`` are treated as precomputed embeddings (B, S, d) — the
    modality-frontend stub path for VLM backbones (internvl2)."""
    if jnp.issubdtype(tokens.dtype, jnp.floating):
        x = tokens.astype(jnp.dtype(cfg.dtype))
    else:
        x = _embed(params, tokens, cfg)
    x = shard(x, policy, "batch", "seq", None)
    x, ns, nc, aux = lm_backbone(params, x, cfg, states=states, caches=caches,
                                 pos=pos, policy=policy)
    return _logits(params, x, cfg, policy), ns, nc, aux


def lm_loss(params, batch: dict, cfg: ModelConfig, *, states=None,
            policy: MeshPolicy | None = None):
    """Cross-entropy (fp32) + MoE aux. batch: {tokens (B,S), labels (B,S)}.
    Returns (loss, (new_states, metrics))."""
    logits, ns, _, aux = lm_forward(params, batch["tokens"], cfg,
                                    states=states, policy=policy)
    from repro.nn.losses import masked_xent

    mask = (batch["labels"] >= 0).astype(jnp.float32)
    ce = masked_xent(logits, jnp.maximum(batch["labels"], 0), mask)
    loss = ce + 0.01 * aux
    metrics = {"ce": ce, "aux": aux,
               "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}
    return loss, (ns, metrics)


def lm_decode_step(params, token, caches, pos, cfg: ModelConfig, *,
                   policy: MeshPolicy | None = None, page_table=None):
    """One serve step. token (B, 1) int32; pos: absolute position of this
    token — a scalar (lockstep batch) or a (B,) vector of per-slot positions
    (continuous batching: each serve slot is at its own depth).
    ``page_table`` (B, pages_per_slot) routes reads/writes through the
    paged KV pool when ``caches`` came from ``init_lm_cache(..., pages=)``.
    Returns (logits (B, V), new_caches)."""
    x = _embed(params, token, cfg)
    x, _, nc, _ = lm_backbone(params, x, cfg, states=None, caches=caches,
                              pos=pos, policy=policy, page_table=page_table)
    return _logits(params, x, cfg, policy)[:, 0], nc


def lm_prefill(params, tokens, cfg: ModelConfig, *, caches,
               valid_len=None, last_only: bool = False,
               policy: MeshPolicy | None = None,
               pos=None, page_table=None):
    """Token-parallel prefill: ONE forward over the whole prompt that also
    writes every layer's decode cache (KV slots — full and rolling — plus
    Mamba conv buffers and recurrent states) in the same pass. No per-token
    Python loop; decode continues from position ``tokens.shape[1]`` exactly
    as if the prompt had been scanned through ``lm_decode_step``.

    tokens (B, P) int32 prompts starting at absolute position 0; ``caches``
    from :func:`init_lm_cache`. ``valid_len`` (B,) gives per-row true prompt
    lengths when rows are right-padded to a common bucket length (serve
    admission): padded positions are masked out of cache writes and freeze
    recurrent states, so each row's caches match an exact-length prefill.

    Returns (logits, new_caches): logits (B, P, V), with the next-token
    logits for row b at ``logits[b, valid_len[b] - 1]`` (or ``[:, -1]``
    unpadded). ``last_only=True`` gathers each row's last VALID hidden state
    before the output projection and returns (B, 1, V) — serving only needs
    one next-token distribution per prompt, so this skips P-1 rows of vocab
    projection (with bucket-padded admission the saving is bucket-sized).

    Paged chunked prefill: with ``page_table`` and a paged cache, ``pos``
    is a (B,) vector of absolute chunk offsets and ``tokens`` is ONE chunk
    of a longer prompt; attention runs against the slot's whole logical
    cache (earlier chunks, shared prefix pages), so a prompt may prefill
    across several calls. ``valid_len`` then counts valid rows WITHIN the
    chunk, and the ``last_only`` gather picks the chunk's last valid row —
    only the final chunk's logits mean anything (the engine ignores the
    rest).

    Speculative verify: the serve engine's spec-decode path reuses this
    same entry point mid-decode — ``tokens`` is [last_committed, d_1..d_k]
    drafted ahead of position ``pos``, ``valid_len`` masks each row's true
    draft length, and the cache writes double as the rollback mechanism
    (accepted positions land exact full-precision KV; rejected tail
    positions are overwritten before they are ever attended to).
    """
    x = _embed(params, tokens, cfg)
    x = shard(x, policy, "batch", "seq", None)
    x, _, nc, _ = lm_backbone(params, x, cfg, states=None, caches=caches,
                              pos=0 if pos is None else pos, policy=policy,
                              valid_len=valid_len, page_table=page_table)
    if last_only:
        last = (jnp.full((x.shape[0],), x.shape[1] - 1, jnp.int32)
                if valid_len is None else valid_len - 1)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)   # (B, 1, d)
    return _logits(params, x, cfg, policy), nc


def count_params(params) -> int:
    import numpy as np

    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))
