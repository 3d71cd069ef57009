"""Memory-aware cross-entropy.

The naive ``logits.astype(f32) -> logsumexp -> softmax-grad`` materializes
TWO fp32 (B, S, V) tensors; at train_4k with a 152k-262k vocab that is
multiple GiB/device (measured — EXPERIMENTS.md §Perf). This custom-VJP CE
keeps logits in their storage dtype, runs reductions in fp32 (numerics), and
emits the backward softmax in the LOGITS dtype:

  fwd residuals: logits (bf16), lse (f32, (B,S)), labels, mask
  bwd: d_logits = (softmax(logits) - onehot) * g / n_valid   (bf16)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils.scopes import XENT, scoped


@jax.custom_vjp
@scoped(XENT)
def masked_xent(logits: jax.Array, labels: jax.Array, mask: jax.Array):
    """Mean CE over mask>0 positions. logits (B,S,V); labels (B,S) int."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return ((lse - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@scoped(XENT)
def _fwd(logits, labels, mask):
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    n = jnp.maximum(mask.sum(), 1.0)
    loss = ((lse - gold) * mask).sum() / n
    return loss, (logits, lse, labels, mask, n)


@scoped(XENT)
def _bwd(res, g):
    logits, lse, labels, mask, n = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    scale = (g * mask / n)[..., None]
    d = ((p - onehot) * scale).astype(logits.dtype)
    return d, None, None


masked_xent.defvjp(_fwd, _bwd)
