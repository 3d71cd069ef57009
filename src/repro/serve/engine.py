"""Continuous-batching serve engine: streaming request lifecycle over a
fixed slot pool, with device-side sampling, pluggable scheduling, and an
optional paged KV pool with prefix sharing and chunked prefill.

Design (the TrainDeeploy lesson: kernel and serving loop co-designed):

* The engine owns ONE set of batched decode caches. A *slot* is a batch
  row; admitting a request means prefilling its prompt into that row,
  finishing (or cancelling, or evicting) means freeing the row for the
  next queued request. Model code never sees the queue.

* DENSE mode (`paged=False`, the oracle path): `init_lm_cache` with
  batch = max_slots — every slot reserves `max_cache` KV in every layer.
  Prefill is token-parallel (`lm_prefill`): admitted prompts are
  right-padded to a small set of bucket lengths and same-bucket
  admissions prefill together as one batch.

* PAGED mode (`paged=True` / `"auto"`): KV storage is a pool of
  fixed-size pages (`serve/kvpool.py` owns refcounts + free list;
  `nn/attention.py::PagedKVCache` is the device side). Each slot maps
  logical pages to physical ones through a per-slot page-table row, so
  live slot count decouples from `max_cache` — a 12-token prompt holds
  pages for 12+max_new tokens, not max_cache. A radix tree over prompt
  prefixes lets a shared system prompt prefill ONCE: later requests
  attach the shared pages by refcount and prefill only their suffix.
  Prefill is CHUNKED — at most `prefill_chunks_per_tick` fixed-size
  chunks advance per engine tick, interleaved with the decode tick, so
  one 8k prompt cannot spike every other request's TPOT. Paged mode
  needs causal full attention in every layer (`supports_paging`);
  sliding-window / Mamba configs serve dense.

* Decode runs ALL slots in lockstep shapes but at per-slot positions
  (`pos` is a (B,) vector): every active request decodes one token per
  engine tick regardless of when it was admitted — that is the continuous
  batching. Free (and still-prefilling) slots ride along as dead rows:
  dense dead rows write at stale positions the causal masks provably
  never read back; paged dead rows carry an all-zero page-table row, so
  their writes land on the reserved trash page.

* MESH mode (`mesh=...`): the dense engine sharded over a device mesh —
  weights (f32 or int8 factors) replicated on every device, the KV slot
  pool sharded across devices on the cache BATCH axis, so `max_slots`
  scales with the mesh while every executable stays
  one-per-bucket. The steps are per-device programs under shard_map
  (XLA cannot partition a Pallas kernel): decode runs each device's own
  slots, and prefill runs an admitted group on every device, each keeping
  the rows it holds. Each slot's decode math is row-independent, so on
  CPU mesh generations are bitwise-identical to the single-device dense
  engine (tests/test_mesh_parity.py pins this, f32 and int8). On TPU the
  per-device programs see fewer rows than the single-device one and may
  round differently, so greedy tokens can part at a near-tie. Paged pools,
  speculative decoding and tenant adapters keep their single-device
  engines for now — mesh serves the dense oracle path.

* Sampling is DEVICE-SIDE (`serve/sampling.py`): per-slot temperature /
  top-k / top-p / RNG key arrays ride into the jitted prefill and decode
  steps, which return sampled int32 tokens — the host never round-trips
  logits, and temperature-0 rows lower to the exact argmax the greedy
  engine ran (token-for-token identical, f32 and int8).

* The request lifecycle is event-driven (`serve/session.py`): `submit()`
  returns a `GenerationHandle` streaming TOKEN / FINISHED / CANCELLED /
  EVICTED events with TTFT/TPOT on the handle; admission order and
  deadline eviction are a pluggable `Scheduler` (`serve/scheduler.py`).
  In paged mode a popped request that cannot get enough pages is pushed
  back to the scheduler and admission stops for the tick — pages free as
  running requests retire (or the prefix cache evicts LRU entries).

The jit cache ends up with one decode executable, plus (dense) one
prefill executable per (bucket, group-size) pair actually seen or
(paged) exactly ONE chunk-prefill executable regardless of prompt mix.
"""
from __future__ import annotations

import collections
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.plan import SubspacePlan, install, installed, plan_of
from repro.config import ModelConfig
from repro.models.lm import (
    init_lm_cache,
    lm_decode_step,
    lm_prefill,
    supports_paging,
)
from repro.serve.kvpool import PagePool, RadixCache, pages_needed
from repro.serve.sampling import SamplingParams, sample_tokens
from repro.serve.scheduler import Scheduler, make_scheduler
from repro.serve.session import Event, EventKind, GenerationHandle, Request

DEFAULT_BUCKETS = (8, 16, 32, 64, 128, 256)
DEFAULT_PAGE_SIZE = 16


def bucket_for(length: int, buckets: Sequence[int],
               max_cache: int | None = None) -> int:
    """Smallest bucket >= length. Prompts beyond the largest bucket round
    UP to the next multiple of it — a handful of shared executables instead
    of one exact-length compile per adversarial prompt length — and every
    result is capped at ``max_cache`` (admission validated the prompt
    itself fits)."""
    cap = max_cache if max_cache is not None else float("inf")
    for b in buckets:
        if b >= length:
            return int(min(b, cap))
    big = buckets[-1]
    return int(min(-(-length // big) * big, cap))


class ServeEngine:
    """Streaming continuous-batching engine over a fixed slot pool."""

    def __init__(self, params, cfg: ModelConfig | None = None, *,
                 plan: SubspacePlan | None = None, max_slots: int = 4,
                 max_cache: int = 512,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 scheduler: Scheduler | str = "fcfs",
                 paged: bool | str = False,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 total_pages: int | None = None,
                 prefix_cache: bool = True,
                 prefill_chunk: int | None = None,
                 prefill_chunks_per_tick: int = 1,
                 prefill_every: int = 1,
                 spec_k: int = 0,
                 draft: str = "int8",
                 adapters=None,
                 adapter_slots: int = 4,
                 mesh: Mesh | None = None):
        if cfg is None:
            if plan is None:
                raise ValueError("ServeEngine needs a ModelConfig or a "
                                 "SubspacePlan (which carries one)")
            cfg = plan.model
        # the engine serves under ONE resolved plan: every linear in the
        # jitted prefill/decode must read the same subspace decision the
        # params were built (or converted) with. Install it only if the
        # slot is free — silently overriding another live plan for an
        # equal config would retrace someone else's model at wrong ranks.
        if plan is None:
            self.plan = plan_of(cfg)
        else:
            current = installed(cfg)
            if current is None:
                self.plan = install(plan)
            elif current == plan:
                self.plan = current
            else:
                raise ValueError(
                    "a different SubspacePlan is already installed for this "
                    "ModelConfig; api.uninstall(cfg) it first, or build the "
                    "engine with that plan")
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_cache = max_cache
        self.sched: Scheduler = (make_scheduler(scheduler)
                                 if isinstance(scheduler, str) else scheduler)
        # weight-storage accounting: an int8 deployment (plan.quantized +
        # convert.quantize) serves through the same engine; summary() then
        # reports the packed linear-weight bytes next to throughput
        self.quantized = self.plan.is_quantized
        from repro.utils.memprof import model_weight_bytes
        self.weight_report = model_weight_bytes(params)
        self.buckets = tuple(sorted(buckets))

        # -- self-speculative decoding setup ------------------------------
        # spec_k > 0: each decode tick drafts spec_k tokens ahead through a
        # cheap subspace view of the SAME weights (int8 factors, or a
        # rank-K' slice of the resident L/R), then verifies all of them in
        # one batched f32 forward with the standard rejection rule.
        self.spec_k = int(spec_k)
        self.draft_source = draft
        if self.spec_k:
            if self.spec_k > max_cache - 2:
                raise ValueError(f"spec_k ({spec_k}) leaves no room in "
                                 f"max_cache ({max_cache})")
            if not supports_paging(cfg):
                # rolling-window and recurrent (Mamba) caches update
                # destructively — a rejected draft could not be rolled back
                raise ValueError(
                    f"config {cfg.name!r} has sliding-window or recurrent "
                    "layers whose caches cannot roll back rejected drafts; "
                    "speculative decoding needs causal full attention")
            if draft == "int8" and self.quantized:
                raise ValueError(
                    "engine already serves int8 — an int8 draft would equal "
                    "the target; use draft='rank:<frac>' to slice the "
                    "resident int8 factors instead")
            # stamp the plan so bind.apply accepts the draft layouts (an
            # int8-packed draft under an f32 spec, or narrower factor
            # slices). Stamps never change f32 semantics, so overriding an
            # installed unstamped plan is safe for other consumers.
            stamped = self.plan.with_draft(draft)
            if stamped.draft_source is None:
                raise ValueError(
                    f"draft {draft!r} stamps no site of this plan (rank "
                    "drafts need factored sites — a dense-only plan has "
                    "nothing to slice)")
            if stamped != self.plan:
                install(stamped)
                self.plan = stamped
            from repro.api.convert import draft_view
            self.draft_params = draft_view(params, self.plan)
        else:
            self.draft_params = None

        # -- multi-tenant adapter banks -----------------------------------
        # `adapters` is a ResidentAdapters (or a store dir path): per-slot
        # int32 indices gather each request's tenant row out of the
        # device-resident banks INSIDE the jitted steps, so any tenant mix
        # — including adapter-less slots via identity row 0 — runs through
        # one executable, and tenant churn swaps bank contents only.
        if adapters is not None:
            if self.spec_k:
                raise ValueError(
                    "speculative decoding and tenant adapters are mutually "
                    "exclusive: the draft view does not carry per-tenant "
                    "deltas, so drafts would systematically diverge")
            from repro.tenancy.resident import ResidentAdapters
            if isinstance(adapters, str):
                adapters = ResidentAdapters(adapters, capacity=adapter_slots)
            self.adapters = adapters
            stamped = SubspacePlan.from_json(adapters.plan_json)
            if stamped.model != cfg:
                raise ValueError(
                    f"adapter store was trained for model "
                    f"{stamped.model.name!r} but the engine serves "
                    f"{cfg.name!r}")
            self.adapter_plan = stamped
            adapters.on_evict = self._adapter_evicted
        else:
            self.adapters = None
            self.adapter_plan = None
        self.adapter_events: list[Event] = []

        if paged == "auto":
            paged = supports_paging(cfg)
        elif paged and not supports_paging(cfg):
            raise ValueError(
                f"config {cfg.name!r} has layers a paged KV pool cannot hold "
                "(sliding-window or recurrent state); serve it dense or use "
                "paged='auto'")
        self.paged = bool(paged)

        # -- mesh mode: dense slots sharded across devices -----------------
        self.mesh = mesh
        if mesh is not None:
            n = mesh.devices.size
            if self.paged:
                raise ValueError(
                    "mesh serving shards the DENSE slot pool on the cache "
                    "batch axis; the paged pool's page tables are "
                    "single-device — serve paged without a mesh")
            if self.spec_k:
                raise ValueError("speculative decoding is single-device; "
                                 "drop spec_k or the mesh")
            if self.adapters is not None:
                raise ValueError("tenant adapter banks are single-device; "
                                 "drop adapters or the mesh")
            if max_slots % n:
                raise ValueError(
                    f"max_slots ({max_slots}) must divide evenly across the "
                    f"{n}-device mesh — every device holds max_slots/{n} "
                    "cache rows")
            # weights replicate; KV shards on the batch (slot) axis — cache
            # leaves are (repeat, B, ...), batch at axis 1 for every layout
            self._repl = NamedSharding(mesh, P())
            self._cache_shard = NamedSharding(
                mesh, P(None, tuple(mesh.axis_names)))
            self.params = params = jax.device_put(params, self._repl)
        dtype = jnp.dtype(cfg.dtype)
        if self.paged:
            self.page_size = int(page_size)
            self.pages_per_slot = pages_needed(max_cache, page_size)
            if total_pages is None:
                # dense-equivalent capacity by default; pass fewer pages to
                # oversubscribe slots, or more to grow the prefix cache
                total_pages = max_slots * self.pages_per_slot + 1
            self.pool = PagePool(total_pages, page_size)
            self.radix = RadixCache(self.pool) if prefix_cache else None
            self.prefill_chunk = int(prefill_chunk or self.buckets[-1])
            self.prefill_chunks_per_tick = int(prefill_chunks_per_tick)
            # stride: with decodes active, advance prefill only every Nth
            # tick — each chunk attends over the full gathered history, so
            # on long prompts a chunk can cost several decode ticks; the
            # stride bounds its TPOT tax at the price of long-request TTFT
            # (benchmarks/tab2_latency.py measures the trade)
            self.prefill_every = max(1, int(prefill_every))
            self._tick = 0
            self.caches = init_lm_cache(cfg, max_slots, max_cache,
                                        dtype=dtype, pages=total_pages,
                                        page_size=page_size)
            # host-side slot state: page-table rows, per-slot page lists,
            # and the prefill cursor (abs position of the next unprefilled
            # prompt token; None = not prefilling)
            self.tables = np.zeros((max_slots, self.pages_per_slot), np.int32)
            self.slot_pages: list[list[int]] = [[] for _ in range(max_slots)]
            self._cursor: list[int | None] = [None] * max_slots
            self._pf_rr = 0
            # pages reserved at admission (pages_needed(prompt + max_new));
            # spec decode may transiently allocate pages BEYOND this to hold
            # draft KV past the budget end, and releases them every tick
            self._prealloc = [0] * max_slots
        else:
            self.pool = self.radix = None
            self.caches = init_lm_cache(cfg, max_slots, max_cache,
                                        dtype=dtype)
            if mesh is not None:
                self.caches = jax.device_put(self.caches, self._cache_shard)
        self.slots: list[Request | None] = [None] * max_slots
        # per-slot decode state, row-aligned with the cache batch axis:
        # position / next input token, plus the device-side sampling
        # arrays (temperature, top-k, top-p, RNG seed, sampled-token count)
        self.pos = np.zeros(max_slots, np.int32)
        self.next_tok = np.zeros(max_slots, np.int32)
        # per-slot adapter bank row (0 = identity / no tenant)
        self.adapter_ix = np.zeros(max_slots, np.int32)
        self.temp = np.zeros(max_slots, np.float32)
        self.top_k = np.zeros(max_slots, np.int32)
        self.top_p = np.ones(max_slots, np.float32)
        self.seed = np.zeros(max_slots, np.uint32)
        self.count = np.zeros(max_slots, np.int32)
        self._rid = 0
        self.stats = {"prefill_tokens": 0, "prefill_chunks": 0,
                      "prefix_hit_tokens": 0, "decode_steps": 0,
                      "decode_tokens": 0, "completed": 0, "cancelled": 0,
                      "evicted": 0, "deferred": 0, "wall_s": 0.0,
                      "prefill_s": 0.0, "decode_s": 0.0,
                      "spec_steps": 0, "spec_draft_tokens": 0,
                      "spec_accepted_tokens": 0, "spec_page_shrinks": 0,
                      "adapter_evictions": 0}

        def _gather_rows(caches, rows):
            return jax.tree.map(lambda a: a[:, rows], caches)

        def _scatter_rows(caches, rows, sub):
            return jax.tree.map(lambda g, l: g.at[:, rows].set(l), caches,
                                sub)

        if mesh is not None:
            # under shard_map (below) each device holds `per` slots and an
            # admitted group's rows may lie on any device: gathering sums
            # the rows each device holds (exact — every other term is
            # zero), and scattering keeps only this device's rows
            axes = tuple(mesh.axis_names)
            per = max_slots // mesh.devices.size

            def _local(rows):
                local = rows - jax.lax.axis_index(axes) * per
                return local, (local >= 0) & (local < per)

            def _gather_rows(caches, rows):
                local, mine = _local(rows)
                idx = jnp.where(mine, local, 0)

                def gather(a):
                    m = mine.reshape((1, -1) + (1,) * (a.ndim - 2))
                    return jax.lax.psum(
                        jnp.where(m, a[:, idx], jnp.zeros((), a.dtype)), axes)
                return jax.tree.map(gather, caches)

            def _scatter_rows(caches, rows, sub):
                local, mine = _local(rows)
                idx = jnp.where(mine, local, per)
                return jax.tree.map(
                    lambda g, l: g.at[:, idx].set(l, mode="drop"), caches,
                    sub)

        def _merged(params_, banks, aix):
            # trace-time branch: a no-adapter engine passes banks=None and
            # compiles the EXACT pre-tenancy computation; an adapter
            # engine gathers each batch row's tenant factors from the
            # banks and merges them next to the base weights, so
            # bind.apply adds the delta. Tenant churn changes bank
            # CONTENTS only — one executable either way.
            if banks is None:
                return params_
            from repro.tenancy.adapter import gather_rows, merge_adapters
            return merge_adapters(params_, gather_rows(banks, aix))

        def _decode(params_, banks, aix, toks, caches, pos, table,
                    temp, tk, tp, seeds, counts):
            logits, caches = lm_decode_step(_merged(params_, banks, aix),
                                            toks, caches, pos, cfg,
                                            page_table=table)
            nxt = sample_tokens(logits, temp, tk, tp, seeds, counts)
            return nxt, caches

        def _prefill(params_, banks, aix, toks, caches, valid_len, rows,
                     temp, tk, tp, seeds):
            # dense grouped prefill: gather the admitted rows, prefill them
            # as one batch, scatter back — cache leaves are (repeat, B, ...),
            # batch on axis 1. `aix` is already row-gathered on the host.
            sub = _gather_rows(caches, rows)
            logits, sub = lm_prefill(_merged(params_, banks, aix), toks,
                                     cfg, caches=sub,
                                     valid_len=valid_len, last_only=True)
            first = sample_tokens(logits[:, 0], temp, tk, tp, seeds,
                                  jnp.zeros_like(seeds, jnp.int32))
            return first, _scatter_rows(caches, rows, sub)

        def _prefill_chunk(params_, banks, aix, toks, caches, offset,
                           valid_len, table, temp, tk, tp, seeds):
            # paged chunk prefill: one (1, chunk) executable for EVERY
            # prompt; the pool rides whole (pages are disjoint by
            # construction) and the chunk writes through this slot's table
            logits, caches = lm_prefill(_merged(params_, banks, aix), toks,
                                        cfg, caches=caches,
                                        pos=offset, valid_len=valid_len,
                                        last_only=True, page_table=table)
            first = sample_tokens(logits[:, 0], temp, tk, tp, seeds,
                                  jnp.zeros_like(seeds, jnp.int32))
            return first, caches

        def _draft_step(dparams, toks, caches, pos, table,
                        temp, tk, tp, seeds, counts):
            # one draft-model decode step: same shape as _decode but under
            # the cheap subspace view, sampling from the SALT_DRAFT stream
            # and returning the warped proposal distribution q for the
            # rejection test. Draft KV lands at the drafted positions and
            # is OVERWRITTEN by the verify pass's f32 KV (rows past their
            # capacity write at a sentinel position that scatter drops /
            # the padded trash table column routes to page 0).
            from repro.serve.sampling import sample_draft_tokens
            logits, caches = lm_decode_step(dparams, toks, caches, pos, cfg,
                                            page_table=table)
            nxt, q = sample_draft_tokens(logits, temp, tk, tp, seeds, counts)
            return nxt, q, caches

        def _verify(params_, toks, caches, offset, table, draft_toks,
                    draft_q, draft_len, temp, tk, tp, seeds, counts):
            # ONE token-parallel f32 forward over [cur, d_0..d_{k-1}] at
            # per-row offsets — the same machinery as chunked prefill
            # (paged) or the dense per-row verify branch — followed by the
            # device-side rejection rule. Only int32 tokens leave the jit.
            from repro.serve.sampling import spec_accept
            logits, caches = lm_prefill(params_, toks, cfg, caches=caches,
                                        pos=offset, valid_len=draft_len + 1,
                                        last_only=False, page_table=table)
            n_acc, out = spec_accept(logits.astype(jnp.float32), draft_toks,
                                     draft_q, draft_len, temp, tk, tp,
                                     seeds, counts)
            return n_acc, out, caches

        # donate the cache pytree: the engine rebinds self.caches on every
        # call and never touches the old buffers, so XLA can update KV/SSM
        # state in place instead of copying the whole cache per token.
        # (CPU ignores donation with a warning — skip it there.) The
        # adapter-aware steps carry caches at arg 4 (after banks + aix —
        # banks are NOT donated, they persist across calls); the spec
        # steps keep the original signature, caches at arg 2.
        if mesh is not None:
            # XLA cannot partition a Pallas kernel, so the mesh steps are
            # per-device programs: decode runs each device's own slots,
            # prefill runs the whole admitted group on every device
            from repro.distributed.collectives import shard_map
            rep, row, kv = P(), P(axes), P(None, axes)
            _decode = shard_map(
                _decode, mesh=mesh,
                in_specs=(rep, rep, rep, row, kv, row, rep,
                          row, row, row, row, row),
                out_specs=(row, kv), check_vma=False)
            _prefill = shard_map(
                _prefill, mesh=mesh,
                in_specs=(rep, rep, rep, rep, kv, rep, rep,
                          rep, rep, rep, rep),
                out_specs=(rep, kv), check_vma=False)
        cpu = jax.default_backend() == "cpu"
        donate = () if cpu else (4,)
        donate_spec = () if cpu else (2,)
        self._decode = jax.jit(_decode, donate_argnums=donate)
        self._prefill = jax.jit(_prefill, donate_argnums=donate)
        self._prefill_chunk = jax.jit(_prefill_chunk, donate_argnums=donate)
        self._draft_step = jax.jit(_draft_step, donate_argnums=donate_spec)
        self._verify = jax.jit(_verify, donate_argnums=donate_spec)

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, step: int | None = None,
                        **engine_kw) -> "ServeEngine":
        """Build an engine from a plan-bearing checkpoint — no config in
        hand. The manifest's SubspacePlan carries the ModelConfig and the
        per-site subspace layout the stored params use (api/convert.py) —
        including quant stamps, so an int8 checkpoint saved via
        ``convert.quantize`` serves quantized with zero extra flags."""
        from repro.api.convert import load_checkpoint

        params, plan, _ = load_checkpoint(ckpt_dir, step)
        if plan is None:
            raise ValueError(
                f"checkpoint at {ckpt_dir} carries no SubspacePlan; build "
                "the engine with ServeEngine(params, cfg) instead")
        return cls(params, plan=plan, **engine_kw)

    # -- submission / cancellation ------------------------------------------

    def submit(self, prompt: Sequence[int], max_new: int | None = None,
               eos_id: int | None = None, *,
               sampling: SamplingParams | None = None,
               tenant: str | None = None) -> GenerationHandle:
        """Queue a generation; returns its :class:`GenerationHandle`.

        ``sampling`` carries the full per-request contract (temperature /
        top-k / top-p / seed / max_new / eos / deadline / priority); the
        positional ``max_new`` / ``eos_id`` override it for the legacy
        call shape. Default is greedy decoding, token-for-token identical
        to the pre-redesign engine. ``tenant`` routes the request through
        that tenant's adapter delta (engine built with ``adapters=``);
        ``None`` serves the bare base via the identity bank row."""
        sp = (sampling or SamplingParams()).resolved(
            self._rid, max_new=max_new, eos_id=eos_id)
        if tenant is not None:
            if self.adapters is None:
                raise ValueError(
                    "engine has no adapter banks; build it with "
                    "adapters=<ResidentAdapters or store dir>")
            if not self.adapters.store.has(tenant):
                raise ValueError(f"unknown tenant {tenant!r}: no adapter "
                                 f"in store {self.adapters.store.root!r}")
        if len(prompt) + sp.max_new > self.max_cache:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({sp.max_new}) exceeds "
                f"max_cache ({self.max_cache})")
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if self.paged:
            need = pages_needed(len(prompt) + sp.max_new, self.page_size)
            if need > self.pool.usable_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool has "
                    f"{self.pool.usable_pages} usable (total_pages too "
                    "small for this prompt + max_new)")
        req = Request(rid=self._rid, prompt=list(map(int, prompt)),
                      sampling=sp, tenant=tenant,
                      submitted_at=time.perf_counter())
        self._rid += 1
        self.sched.add(req)
        return GenerationHandle(self, req)

    def cancel(self, rid: int) -> bool:
        """Cancel a queued or running request. Running requests free their
        slot IMMEDIATELY (the next tick can admit into it; the dead row's
        stale cache writes are provably never read back). Returns False if
        the rid is unknown or already terminal."""
        queued = self.sched.remove(rid)
        if queued is not None:
            self._retire(queued, EventKind.CANCELLED, "user cancel")
            return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                self._free_slot(slot)
                self._retire(req, EventKind.CANCELLED, "user cancel")
                return True
        return False

    @property
    def queue(self) -> collections.deque:
        """Queued requests in admission order (introspection only — the
        scheduler owns the real wait set)."""
        return collections.deque(self.sched.pending())

    @property
    def busy(self) -> bool:
        """True while any request is queued or occupying a slot — the
        ``step()``-until-done predicate ``run()`` (and any external
        driver) loops on."""
        return bool(len(self.sched)) or any(r is not None for r in self.slots)

    # -- paged-pool maintenance ---------------------------------------------

    def release_prefix_cache(self) -> int:
        """Drop every radix-held page (frees them back to the pool).
        Returns the number of pages released. After a full drain plus this
        call, every page refcount is zero — the invariant the fuzz harness
        pins."""
        return self.radix.clear() if self.radix is not None else 0

    def check_invariants(self) -> None:
        """Audit the paged bookkeeping (no-op in plain dense mode): pool
        structure is sound and every page's refcount equals its holder
        count (slots holding it in their table + radix nodes). Mesh
        engines additionally audit the cache placement: every leaf still
        sharded over the full mesh on the slot axis, one equal-size shard
        per device (a silent fallback to replicated layout would be a
        correctness-preserving but capacity-destroying regression)."""
        if self.mesh is not None:
            n = self.mesh.devices.size
            for leaf in jax.tree.leaves(self.caches):
                shards = leaf.addressable_shards
                if len(shards) != n:
                    raise AssertionError(
                        f"cache leaf lost mesh sharding: {len(shards)} "
                        f"shards for a {n}-device mesh")
                if shards[0].data.shape[1] * n != leaf.shape[1]:
                    raise AssertionError(
                        f"cache leaf not sharded on the slot axis: local "
                        f"{shards[0].data.shape} vs global {leaf.shape}")
        if not self.paged:
            return
        self.pool.check()
        expected = np.zeros(self.pool.total_pages, np.int64)
        for slot, pages in enumerate(self.slot_pages):
            if self.slots[slot] is not None:
                for p in pages:
                    expected[p] += 1
        if self.radix is not None:
            for p in self.radix.held_pages():
                expected[p] += 1
        actual = self.pool.refs.astype(np.int64)
        if not (expected == actual).all():
            bad = np.nonzero(expected != actual)[0]
            raise AssertionError(
                f"page refcount leak: pages {bad.tolist()} expected "
                f"{expected[bad].tolist()} got {actual[bad].tolist()}")

    # -- internals ----------------------------------------------------------

    def _adapter_evicted(self, tenant: str) -> None:
        """Resident-bank LRU displacement -> the existing EVICTED event
        machinery (rid -1: no single request owns a bank row)."""
        self.adapter_events.append(Event(
            EventKind.EVICTED, rid=-1,
            reason=f"adapter lru tenant={tenant}", t=time.perf_counter()))
        self.stats["adapter_evictions"] += 1

    def _acquire_adapter(self, req: Request,
                         admitted: list) -> int | None:
        """Bank row for this request's tenant (0 = identity). Rows held by
        slots still generating — and by requests admitted earlier this
        same round, whose slots aren't populated yet (dense prefills in
        one batch after the pop loop) — are pinned against eviction.
        ``None`` = every row pinned; caller defers the request."""
        if self.adapters is None or req.tenant is None:
            return 0
        pinned = {int(self.adapter_ix[s]) for s, r in enumerate(self.slots)
                  if r is not None}
        pinned.update(int(self.adapter_ix[s]) for s, _ in admitted)
        pinned.discard(0)
        return self.adapters.acquire(req.tenant, pinned)

    def _adapter_args(self, rows=None):
        """(banks, aix) for a jitted step — (None, None) on a no-adapter
        engine so it traces the exact pre-tenancy computation."""
        if self.adapters is None:
            return None, None
        ix = self.adapter_ix if rows is None else self.adapter_ix[rows]
        return self.adapters.banks, jnp.asarray(ix)

    def _free_slot(self, slot: int) -> None:
        """Recycle a slot AND reset its sampling row to greedy defaults —
        a stale temperature on a dead row would keep ``jnp.any(temp > 0)``
        true and defeat the all-greedy ``lax.cond`` fast path. In paged
        mode also release the slot's page references and point its table
        row at the trash page, so the dead row's lockstep writes can never
        land in a page the pool hands to someone else."""
        self.slots[slot] = None
        self.adapter_ix[slot] = 0     # unpin the tenant's bank row
        self.temp[slot] = 0.0
        self.top_k[slot] = 0
        self.top_p[slot] = 1.0
        if self.paged:
            for p in self.slot_pages[slot]:
                self.pool.unref(p)
            self.slot_pages[slot] = []
            self.tables[slot, :] = 0
            self._cursor[slot] = None
            self._prealloc[slot] = 0

    def _emit_token(self, req: Request, token: int, t: float) -> None:
        req.generated.append(token)
        if not req.first_token_at:
            req.first_token_at = t
        req.last_token_at = t
        req.events.append(Event(EventKind.TOKEN, req.rid, token=token, t=t))

    def _retire(self, req: Request, kind: EventKind, reason: str) -> None:
        t = time.perf_counter()
        req.events.append(Event(kind, req.rid, reason=reason, t=t))
        req.status = kind
        req.finished_at = t
        key = {EventKind.FINISHED: "completed",
               EventKind.CANCELLED: "cancelled",
               EventKind.EVICTED: "evicted"}[kind]
        self.stats[key] += 1

    def _finish_if_done(self, slot: int) -> None:
        req = self.slots[slot]
        if req is not None and req.hit_stop:
            self._free_slot(slot)         # recycle: next _admit reuses it
            s = req.sampling
            reason = ("eos" if s.eos_id is not None and req.generated
                      and req.generated[-1] == s.eos_id else "max_new")
            self._retire(req, EventKind.FINISHED, reason)

    def _evict(self, now: float) -> None:
        running = [r for r in self.slots if r is not None]
        for req in self.sched.victims(running, now):
            if req.terminal:      # defensive vs misbehaving schedulers:
                continue          # a request gets exactly ONE terminal event
            for slot, r in enumerate(self.slots):
                if r is req:
                    self._free_slot(slot)
                    break
            self._retire(req, EventKind.EVICTED, "deadline")

    def _set_sampling_row(self, slot: int, req: Request) -> None:
        sp = req.sampling
        self.temp[slot] = sp.temperature
        self.top_k[slot] = sp.top_k
        self.top_p[slot] = sp.top_p
        self.seed[slot] = np.uint32(sp.seed & 0xFFFFFFFF)

    def _admit(self) -> None:
        if self.paged:
            self._admit_paged()
        else:
            self._admit_dense()

    def _admit_dense(self) -> None:
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free or not len(self.sched):
            return
        t0 = time.perf_counter()
        admitted: list[tuple[int, Request]] = []
        while free:
            req = self.sched.pop(t0)
            if req is None:
                break
            if req.terminal:      # e.g. evicted-from-queue by a scheduler
                continue          # that didn't also dequeue it
            row = self._acquire_adapter(req, admitted)
            if row is None:
                self.sched.add(req)       # every bank row pinned: wait
                self.stats["deferred"] += 1
                break
            slot = free.pop(0)
            self.adapter_ix[slot] = row
            admitted.append((slot, req))
        # group by bucket so same-shape prompts prefill as one batch
        groups: dict[int, list[tuple[int, Request]]] = collections.defaultdict(list)
        for slot, req in admitted:
            groups[bucket_for(len(req.prompt), self.buckets,
                              self.max_cache)].append((slot, req))
        for bucket, group in groups.items():
            rows = np.array([s for s, _ in group], np.int32)
            vlen = np.array([len(r.prompt) for _, r in group], np.int32)
            toks = np.zeros((len(group), bucket), np.int32)
            for i, (slot, req) in enumerate(group):
                toks[i, :len(req.prompt)] = req.prompt
                self._set_sampling_row(slot, req)
            banks, aix = self._adapter_args(rows)
            first, self.caches = self._prefill(
                self.params, banks, aix, jnp.asarray(toks), self.caches,
                jnp.asarray(vlen), jnp.asarray(rows),
                jnp.asarray(self.temp[rows]), jnp.asarray(self.top_k[rows]),
                jnp.asarray(self.top_p[rows]), jnp.asarray(self.seed[rows]))
            first = np.asarray(first)
            now = time.perf_counter()
            for i, (slot, req) in enumerate(group):
                self.slots[slot] = req
                self._emit_token(req, int(first[i]), now)
                self.pos[slot] = int(vlen[i])
                self.next_tok[slot] = int(first[i])
                self.count[slot] = 1
                self.stats["prefill_tokens"] += int(vlen[i])
                self._finish_if_done(slot)
        self.stats["prefill_s"] += time.perf_counter() - t0

    def _admit_paged(self) -> None:
        """Admit queued requests into free slots by RESERVING pages —
        prefill itself happens chunk-by-chunk in `_prefill_tick`. The
        radix cache is consulted first: matched full-page prefixes attach
        by reference (refcount bump, zero prefill) and the prefill cursor
        starts past them. A request the pool cannot satisfy (even after
        LRU eviction of unreferenced prefix-cache pages) goes back to the
        scheduler and admission stops for this tick — running requests
        will free pages as they retire."""
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free or not len(self.sched):
            return
        t0 = time.perf_counter()
        while free:
            req = self.sched.pop(t0)
            if req is None:
                break
            if req.terminal:
                continue
            # paged admission populates self.slots inside the loop, so
            # active-slot pinning already covers this round's admissions
            row = self._acquire_adapter(req, [])
            if row is None:
                self.sched.add(req)        # every bank row pinned: wait
                self.stats["deferred"] += 1
                break
            prompt = req.prompt
            pg = self.page_size
            need = pages_needed(len(prompt) + req.sampling.max_new, pg)
            shared: list[int] = []
            if self.radix is not None:
                # cap shared pages so at least ONE prompt token is left to
                # prefill — the final chunk must produce next-token logits
                # tenant-namespaced: a prefix prefilled under one adapter
                # is NOT the same KV under another (or under the bare base)
                shared = self.radix.match(
                    prompt, namespace=req.tenant)[:(len(prompt) - 1) // pg]
                for p in shared:       # protect from our own eviction below
                    self.pool.ref(p)
            fresh = need - len(shared)
            if self.pool.free_pages < fresh and self.radix is not None:
                self.radix.evict(fresh - self.pool.free_pages)
            alloc = self.pool.alloc(fresh)
            if alloc is None:
                for p in shared:
                    self.pool.unref(p)
                self.sched.add(req)        # not enough pages: wait
                self.stats["deferred"] += 1
                break
            slot = free.pop(0)
            self.adapter_ix[slot] = row
            pages = shared + alloc
            self.tables[slot, :] = 0
            self.tables[slot, :len(pages)] = pages
            self.slot_pages[slot] = pages
            self._prealloc[slot] = need
            self.slots[slot] = req
            self._set_sampling_row(slot, req)
            self._cursor[slot] = len(shared) * pg
            self.pos[slot] = 0
            self.count[slot] = 0
            self.stats["prefix_hit_tokens"] += len(shared) * pg
        self.stats["prefill_s"] += time.perf_counter() - t0

    def _prefill_tick(self) -> None:
        """Advance chunked prefill: up to `prefill_chunks_per_tick` chunks
        across the slots currently prefilling, round-robin so a long
        prompt cannot starve a short one. Each chunk is one fixed-shape
        (1, prefill_chunk) jitted call that writes K/V through the slot's
        page table; the final chunk samples the request's first token."""
        if not self.paged:
            return
        waiting = [s for s in range(self.max_slots)
                   if self.slots[s] is not None and self._cursor[s] is not None]
        if not waiting:
            return
        decoding = any(self.slots[s] is not None and self._cursor[s] is None
                       for s in range(self.max_slots))
        if decoding and self._tick % self.prefill_every:
            return      # stride only matters when there is someone to starve
        t0 = time.perf_counter()
        order = sorted(waiting, key=lambda s: (s - self._pf_rr) % self.max_slots)
        for slot in order[:self.prefill_chunks_per_tick]:
            self._pf_rr = (slot + 1) % self.max_slots
            req = self.slots[slot]
            cur = self._cursor[slot]
            end = min(cur + self.prefill_chunk, len(req.prompt))
            toks = np.zeros((1, self.prefill_chunk), np.int32)
            toks[0, :end - cur] = req.prompt[cur:end]
            # slice the table to a power-of-2 HISTORY bucket: the chunk
            # attends (and writes) only positions < end, every shape in the
            # paged attention flows from the table width, and masked
            # columns contribute exactly 0 — so early chunks of a long
            # prompt cost O(history so far), bitwise-identical to the
            # full-width gather, at one executable per bucket (log2 many)
            n_hist = min(self.pages_per_slot,
                         1 << (pages_needed(end, self.page_size) - 1)
                         .bit_length())
            banks, aix = self._adapter_args([slot])
            first, self.caches = self._prefill_chunk(
                self.params, banks, aix, jnp.asarray(toks), self.caches,
                jnp.asarray([cur], np.int32),
                jnp.asarray([end - cur], np.int32),
                jnp.asarray(self.tables[slot:slot + 1, :n_hist]),
                jnp.asarray(self.temp[slot:slot + 1]),
                jnp.asarray(self.top_k[slot:slot + 1]),
                jnp.asarray(self.top_p[slot:slot + 1]),
                jnp.asarray(self.seed[slot:slot + 1]))
            self.stats["prefill_tokens"] += end - cur
            self.stats["prefill_chunks"] += 1
            if end < len(req.prompt):
                self._cursor[slot] = end
                continue
            # prompt complete: publish full pages for prefix reuse, then
            # emit the sampled first token and hand the slot to decode
            self._cursor[slot] = None
            if self.radix is not None:
                n_full = len(req.prompt) // self.page_size
                self.radix.insert(req.prompt,
                                  self.slot_pages[slot][:n_full],
                                  namespace=req.tenant)
            now = time.perf_counter()
            self._emit_token(req, int(np.asarray(first)[0]), now)
            self.pos[slot] = len(req.prompt)
            self.next_tok[slot] = int(np.asarray(first)[0])
            self.count[slot] = 1
            self._finish_if_done(slot)
        self.stats["prefill_s"] += time.perf_counter() - t0

    def _decode_all(self) -> None:
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and (not self.paged
                                        or self._cursor[i] is None)]
        if not active:
            return
        t0 = time.perf_counter()
        if self.paged:
            # still-prefilling rows ride along dead: route their lockstep
            # writes to the trash page, not into pages their prefill owns
            tbl = self.tables.copy()
            for s in range(self.max_slots):
                if self._cursor[s] is not None:
                    tbl[s, :] = 0
            table = jnp.asarray(tbl)
        else:
            table = None
        nxt, self.caches = self._decode(*self._decode_args(table))
        nxt = np.asarray(nxt, np.int32)
        self.stats["decode_steps"] += 1
        now = time.perf_counter()
        for slot in active:
            req = self.slots[slot]
            self._emit_token(req, int(nxt[slot]), now)
            self.pos[slot] += 1
            self.next_tok[slot] = int(nxt[slot])
            self.count[slot] += 1
            self.stats["decode_tokens"] += 1
            self._finish_if_done(slot)
        self.stats["decode_s"] += time.perf_counter() - t0

    def compiled_decode(self):
        """The compiled program a dense decode tick runs for the current
        slot state (``.as_text()`` shows which kernels it launches)."""
        return self._decode.lower(*self._decode_args()).compile()

    def _decode_args(self, table=None) -> tuple:
        banks, aix = self._adapter_args()
        return (self.params, banks, aix, jnp.asarray(self.next_tok[:, None]),
                self.caches, jnp.asarray(self.pos), table,
                jnp.asarray(self.temp), jnp.asarray(self.top_k),
                jnp.asarray(self.top_p), jnp.asarray(self.seed),
                jnp.asarray(self.count))

    def _spec_pages(self, active: list[int]) -> np.ndarray:
        """Paged-mode draft coverage: per-slot draft length after making
        sure pages exist under every position the draft + verify will
        write (pos .. pos + draft_len). A draft near the end of its budget
        may need pages BEYOND the admission reservation (the verify block
        overruns `prompt + max_new` even though emission never does) —
        those are allocated here and released by ``_spec_release`` the
        same tick. Pool exhaustion shrinks the draft to the covered
        region instead of deferring the whole tick."""
        draft_len = np.zeros(self.max_slots, np.int32)
        pg = self.page_size
        for slot in active:
            pos = int(self.pos[slot])
            dl = min(self.spec_k, self.max_cache - 1 - pos)
            need = pages_needed(pos + dl + 1, pg)
            have = len(self.slot_pages[slot])
            if need > have:
                want = need - have
                if self.pool.free_pages < want and self.radix is not None:
                    self.radix.evict(want - self.pool.free_pages)
                grab = min(want, self.pool.free_pages)
                alloc = self.pool.alloc(grab) if grab else None
                if alloc:
                    self.tables[slot, have:have + len(alloc)] = alloc
                    self.slot_pages[slot].extend(alloc)
                    have += len(alloc)
                if have < need:
                    # shrink the draft to what the held pages cover
                    dl = min(dl, have * pg - 1 - pos)
                    self.stats["spec_page_shrinks"] += 1
            draft_len[slot] = max(dl, 0)
        return draft_len

    def _spec_release(self) -> None:
        """Return every page past a live slot's admission reservation to
        the pool and zero its table tail — the rollback half of the paged
        draft path. Emission is capped at max_new, so a slot never needs
        those pages again; finished slots already released everything via
        ``_free_slot``."""
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            keep = self._prealloc[slot]
            extra = self.slot_pages[slot][keep:]
            if not extra:
                continue
            for p in extra:
                self.pool.unref(p)
            self.slot_pages[slot] = self.slot_pages[slot][:keep]
            self.tables[slot, keep:] = 0

    def _spec_decode_all(self) -> None:
        """One speculative tick over every decoding slot: draft ``spec_k``
        tokens through the cheap subspace view, verify all of them (plus
        the current token) in ONE batched f32 forward, emit the accepted
        prefix + the corrected/bonus token. Per-row draft lengths are
        clamped by CACHE CAPACITY only (max_cache - 1 - pos), not by the
        request budget — the host stops emitting at max_new/EOS, and the
        overrun KV is never read (dense) or its pages are released
        (paged). Dead and still-prefilling rows ride along at draft
        length 0 exactly as they ride through ``_decode_all``."""
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and (not self.paged
                                        or self._cursor[i] is None)]
        if not active:
            return
        t0 = time.perf_counter()
        k = self.spec_k
        if self.paged:
            draft_len = self._spec_pages(active)
            # dead/prefilling rows write to the trash page; ALL rows get
            # one extra trash column so a past-capacity position index
            # (which gather CLIPS, not drops) can never alias a live page
            tbl = self.tables.copy()
            for s in range(self.max_slots):
                if self.slots[s] is None or self._cursor[s] is not None:
                    tbl[s, :] = 0
            table = jnp.asarray(np.concatenate(
                [tbl, np.zeros((self.max_slots, 1), np.int32)], axis=1))
        else:
            draft_len = np.zeros(self.max_slots, np.int32)
            for slot in active:
                draft_len[slot] = max(
                    0, min(k, self.max_cache - 1 - int(self.pos[slot])))
            table = None

        temp = jnp.asarray(self.temp)
        tk = jnp.asarray(self.top_k)
        tp = jnp.asarray(self.top_p)
        seeds = jnp.asarray(self.seed)
        counts = jnp.asarray(self.count)
        dlen = jnp.asarray(draft_len)
        pos0 = jnp.asarray(self.pos)

        # -- draft: k cheap decode steps, all device-resident -------------
        cur = jnp.asarray(self.next_tok[:, None])
        toks_cols = [cur]
        q_cols = []
        for i in range(k):
            # rows done drafting park at the sentinel position max_cache:
            # dense scatter drops it, the padded trash column absorbs it
            p_i = jnp.where(i < dlen, pos0 + i, self.max_cache)
            nxt, q, self.caches = self._draft_step(
                self.draft_params, toks_cols[-1], self.caches, p_i, table,
                temp, tk, tp, seeds, counts + i)
            toks_cols.append(nxt[:, None])
            q_cols.append(q[:, None])
        draft_toks = jnp.concatenate(toks_cols[1:], axis=1)       # (B, k)
        draft_q = jnp.concatenate(q_cols, axis=1)                 # (B, k, V)

        # -- verify: one batched f32 forward + device-side rejection ------
        ver_toks = jnp.concatenate(toks_cols, axis=1)             # (B, k+1)
        n_acc, out, self.caches = self._verify(
            self.params, ver_toks, self.caches, pos0, table,
            draft_toks, draft_q, dlen, temp, tk, tp, seeds, counts)
        n_acc = np.asarray(n_acc, np.int32)
        out = np.asarray(out, np.int32)

        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1
        now = time.perf_counter()
        for slot in active:
            req = self.slots[slot]
            self.stats["spec_draft_tokens"] += int(draft_len[slot])
            self.stats["spec_accepted_tokens"] += int(n_acc[slot])
            req.accepted_counts.append(int(n_acc[slot]))
            emitted = 0
            for j in range(int(n_acc[slot]) + 1):
                self._emit_token(req, int(out[slot, j]), now)
                emitted += 1
                if req.hit_stop:
                    break
            self.pos[slot] += emitted
            self.next_tok[slot] = int(out[slot, emitted - 1])
            self.count[slot] += emitted
            self.stats["decode_tokens"] += emitted
            self._finish_if_done(slot)
        if self.paged:
            self._spec_release()
        self.stats["decode_s"] += time.perf_counter() - t0

    # -- driving ------------------------------------------------------------

    def step(self) -> None:
        """One engine tick: enforce deadlines, admit whatever fits, advance
        chunked prefill (paged mode), then decode every active slot by one
        token. Accumulates wall_s so summary() rates are correct for
        callers driving step() directly, not just run()."""
        t0 = time.perf_counter()
        if self.paged:
            self._tick += 1
        self._evict(t0)
        self._admit()
        self._prefill_tick()
        if self.spec_k:
            self._spec_decode_all()
        else:
            self._decode_all()
        self.stats["wall_s"] += time.perf_counter() - t0

    def run(self) -> None:
        """Drain queue + slots to completion."""
        while self.busy:
            self.step()

    # -- reporting ----------------------------------------------------------

    def reset_stats(self) -> None:
        """Zero all counters/timers (e.g. after warmup runs)."""
        for k in self.stats:
            self.stats[k] = type(self.stats[k])()

    def cache_bytes(self) -> int:
        """Device bytes of the decode caches: dense reserves
        slots x max_cache per layer, paged reserves total_pages x
        page_size per full-attention layer (the decoupling the paged pool
        buys — see utils/memprof.kv_cache_bytes for the formula)."""
        from repro.utils.memprof import array_bytes
        return int(sum(array_bytes(a) for a in jax.tree.leaves(self.caches)))

    def summary(self) -> dict:
        """Counters plus derived rates. Phase throughputs use each phase's
        own wall time (prefill_s / decode_s) so they measure the phase,
        not the mix; requests_s uses total engine time."""
        s = dict(self.stats)
        s["prefill_tok_s"] = s["prefill_tokens"] / max(s["prefill_s"], 1e-9)
        s["decode_tok_s"] = s["decode_tokens"] / max(s["decode_s"], 1e-9)
        s["requests_s"] = s["completed"] / max(s["wall_s"], 1e-9)
        s["weight_bytes"] = self.weight_report["total_bytes"]
        s["weight_mib"] = self.weight_report["total_bytes"] / 2**20
        s["quantized"] = self.quantized
        s["scheduler"] = getattr(self.sched, "name", type(self.sched).__name__)
        s["paged"] = self.paged
        s["cache_bytes"] = self.cache_bytes()
        if self.mesh is not None:
            s["mesh_devices"] = int(self.mesh.devices.size)
            s["slots_per_device"] = self.max_slots // int(
                self.mesh.devices.size)
            s["cache_bytes_per_device"] = (s["cache_bytes"]
                                           // int(self.mesh.devices.size))
        if self.paged:
            s["page_size"] = self.page_size
            s["total_pages"] = self.pool.total_pages
            s["pages_in_use"] = self.pool.pages_in_use
            s["prefix_cache_pages"] = (self.radix.n_nodes
                                       if self.radix is not None else 0)
        if self.adapters is not None:
            # base-vs-adapter accounting split (utils/memprof.py):
            # weight_bytes above is the RESIDENT BASE; the banks are the
            # only per-tenant device cost, store bytes the per-tenant
            # disk cost
            t = self.adapters.summary()
            t["bytes_by_tenant"] = self.adapters.store.bytes_by_tenant()
            s["tenancy"] = t
            s["adapter_bank_bytes"] = t["bank_bytes"]
        if self.spec_k:
            s["spec_k"] = self.spec_k
            s["draft_source"] = self.draft_source
            s["acceptance_rate"] = (s["spec_accepted_tokens"]
                                    / max(s["spec_draft_tokens"], 1))
            # mean emitted tokens per verify step (accepted + corrected /
            # bonus), the speedup numerator the paper's Tab. 2 reports
            s["tokens_per_verify"] = (s["decode_tokens"]
                                      / max(s["spec_steps"], 1))
        return s
