"""A decoder language model, ``program.family`` ``lm`` (the default).

The program's ``init_lm``, ``init_lm_states`` and ``lm_loss``. A row is
``seq`` token ids drawn uniformly from the vocabulary, each labelled with
the next one; a row's operations are ``flops.lm_sequence_flops``.
"""
from __future__ import annotations

import flops


def init_params(cfg: dict, mcfg, key, dtype):
    from repro.models.lm import init_lm

    return init_lm(key, mcfg, dtype)


def init_states(cfg: dict, mcfg, key, batch: int, seq: int, dtype):
    from repro.models.lm import init_lm_states

    return init_lm_states(key, mcfg, batch, seq, dtype)


def loss(cfg: dict):
    from repro.models.lm import lm_loss

    return lm_loss


def draw_batches(cfg: dict, mix: dict, n: int, key) -> dict:
    """``n`` batches stacked on a leading axis, each row different."""
    import jax
    import jax.numpy as jnp

    t = jax.random.randint(key, (n, mix["batch"], mix["seq"] + 1), 0,
                           cfg["model"]["vocab_size"], jnp.int32)
    return {"tokens": t[..., :-1], "labels": t[..., 1:]}


def step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward operations of a step (backward twice the
    forward: the input and the weight gradient of every matmul)."""
    return 3 * batch * flops.lm_sequence_flops(cfg, seq)
