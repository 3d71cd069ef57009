"""Deterministic synthetic data pipelines.

The container is offline, so every dataset here is procedurally generated —
but with STRUCTURE, not iid noise, so models measurably learn:

* SyntheticLM  — a Markov token stream with per-sequence latent "topics":
  next-token distribution is a mixture of a global bigram table and a
  topic-specific unigram boost. Bigram rows are drawn per previous token,
  never materialized as a (V, V) table, so full vocabularies fit. CE
  should fall well below log(V) when the model learns the bigram
  structure (integration tests assert this).
* SyntheticVision — class-conditional patch prototypes + noise (the
  ViT fine-tuning stand-in for CIFAR-style tasks).
* SyntheticAudio — frame embeddings with class-dependent spectral envelope.

Determinism & fault tolerance: batches are a pure function of (seed, step),
so restart-from-checkpoint replays the exact stream with no reader state to
save; skip-ahead is O(1). Sharding: each host slices its batch rows by
process_index (multi-host data loading without a distributed filesystem).
"""
from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SyntheticLM:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_topics: int = 8
    # Tenant skew: when set, ~(1 - tenant_offmix) of rows sample the
    # tenant's favorite topic (a stable hash of the id) instead of uniform,
    # so a per-user adapter has a real distribution shift to learn
    # (repro/tenancy/finetune.py) while the bigram backbone — and hence
    # everything a GLOBAL model learns — is shared across tenants.
    tenant: str | None = None
    tenant_offmix: float = 0.15
    # topic table (n_topics, V), built once per stream in __post_init__
    topic: jax.Array = field(init=False, repr=False, compare=False)

    def __post_init__(self):
        k_topic = jax.random.split(jax.random.PRNGKey(self.seed))[1]
        object.__setattr__(self, "topic", jax.random.normal(
            k_topic, (self.n_topics, self.vocab_size)) * 2.0)

    def for_tenant(self, uid: str) -> "SyntheticLM":
        """This stream, skewed toward tenant ``uid``'s topic. Deterministic
        in (seed, step, uid); ``uid=None``-equivalent is the base stream."""
        return replace(self, tenant=uid)

    def batch(self, step: int, batch_size: int | None = None) -> dict:
        """Batch for a global step: {tokens (B,S), labels (B,S)}."""
        fav = -1 if self.tenant is None else \
            zlib.crc32(self.tenant.encode()) % self.n_topics
        return _markov_batch(self.seed, step, self.topic, fav,
                             self.tenant_offmix,
                             batch=batch_size or self.global_batch,
                             seq_len=self.seq_len)


@functools.partial(jax.jit, static_argnames=("batch", "seq_len"))
def _markov_batch(seed, step, topic, fav, offmix, *, batch: int,
                  seq_len: int) -> dict:
    """SyntheticLM's stream as one compiled program. The bigram row of the
    previous token is drawn on the fly from a key folded with that token,
    so each row has the distribution of a row of a dense (V, V) table
    while memory stays O(B*V) — a dense table at V=151936 is 92 GB."""
    n_topics, vocab = topic.shape
    k_bigram = jax.random.split(jax.random.PRNGKey(seed))[0]
    key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), step)
    kt, ks, kc = jax.random.split(key, 3)
    topics = jax.random.randint(kt, (batch,), 0, n_topics)
    offmix_rows = jax.random.uniform(jax.random.fold_in(kt, 1),
                                     (batch,)) < offmix
    topics = jnp.where((fav < 0) | offmix_rows, topics, fav)
    start = jax.random.randint(ks, (batch,), 0, vocab)

    def gen_row(carry, k):
        tok, tvec = carry
        row = jax.random.normal(jax.random.fold_in(k_bigram, tok), (vocab,))
        nxt = jax.random.categorical(k, row * 2.0 + tvec)
        return (nxt, tvec), nxt

    keys = jax.random.split(kc, seq_len * batch).reshape(seq_len, batch, 2)

    def gen_seq(s0, tvec, kk):
        (_, _), toks = jax.lax.scan(gen_row, (s0, tvec), kk)
        return toks

    toks = jax.vmap(gen_seq, in_axes=(0, 0, 1))(start, topic[topics], keys)
    tokens = jnp.concatenate([start[:, None], toks[:, :-1]], axis=1)
    return {"tokens": tokens.astype(jnp.int32),
            "labels": toks.astype(jnp.int32)}


@dataclass(frozen=True)
class SyntheticVision:
    n_classes: int
    n_patches: int
    patch_dim: int
    global_batch: int
    seed: int = 0
    noise: float = 1.0

    def _protos(self):
        key = jax.random.PRNGKey(self.seed)
        return jax.random.normal(key, (self.n_classes, self.n_patches, self.patch_dim))

    def batch(self, step: int, batch_size: int | None = None) -> dict:
        b = batch_size or self.global_batch
        protos = self._protos()
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed + 1), step)
        kl, kn = jax.random.split(key)
        labels = jax.random.randint(kl, (b,), 0, self.n_classes)
        patches = protos[labels] + self.noise * jax.random.normal(
            kn, (b, self.n_patches, self.patch_dim))
        return {"patches": patches, "labels": labels}


@dataclass(frozen=True)
class SyntheticAudio:
    vocab_size: int
    enc_seq: int
    d_model: int
    seq_len: int
    global_batch: int
    seed: int = 0

    def batch(self, step: int, batch_size: int | None = None) -> dict:
        b = batch_size or self.global_batch
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        kf, kt = jax.random.split(key)
        frames = jax.random.normal(kf, (b, self.enc_seq, self.d_model))
        toks = jax.random.randint(kt, (b, self.seq_len + 1), 0, self.vocab_size)
        return {"frames": frames,
                "tokens": toks[:, :-1].astype(jnp.int32),
                "labels": toks[:, 1:].astype(jnp.int32)}


def host_shard(batch: dict, process_index: int, process_count: int) -> dict:
    """Slice this host's rows (row-contiguous sharding over the batch dim).

    The batch dim must divide evenly: a silent floor-division here would
    DROP the remainder rows on every host — data loss that surfaces only
    as a mysteriously-smaller effective batch."""
    def slc(x):
        if x.shape[0] % process_count:
            raise ValueError(
                f"batch dim {x.shape[0]} (shape {tuple(x.shape)}) is not "
                f"divisible by process_count={process_count}: "
                f"{x.shape[0] % process_count} row(s) would be silently "
                "dropped — pick a global batch that divides across hosts")
        per = x.shape[0] // process_count
        return x[process_index * per:(process_index + 1) * per]
    return jax.tree.map(slc, batch)
