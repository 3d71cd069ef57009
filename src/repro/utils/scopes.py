"""Names of the device-time scopes in the train and serve steps.

Each name is a ``jax.named_scope`` set where that layer's work happens. It
lands in every compiled op's ``metadata={op_name=".../<scope>/..."}``
(forward ops, ops rematerialised under ``jax.checkpoint``, and the bwd
rules of custom VJPs alike), so a profile's ops can be told apart by layer.
A scope is trace-time metadata only: the compiled code is the same with or
without it. docs/training.md ("Reading a profile") says what each covers.
"""
from __future__ import annotations

import functools

import jax

ASI_UPKEEP = "wasi.asi_upkeep"    # power iteration, CholeskyQR, core projection
MATMUL = "wasi.matmul"            # factored / dense-with-ASI forward, h~ sketch
GRAD = "wasi.grad"                # bwd rules of the WASI custom VJPs
ATTENTION = "attn.core"           # scores, mask, softmax, combine
EMBED = "lm.embed"                # embedding gather (its scatter gradient)
HEAD = "lm.head"                  # logits against the (tied) vocabulary matrix
XENT = "lm.xent"                  # cross-entropy forward and backward
OPTIMIZER = "train.optimizer"     # global-norm clip and the optimizer update
WSI_REFRESH = "train.wsi_refresh"  # the taken branch of the factor refresh

ALL = (ASI_UPKEEP, MATMUL, GRAD, ATTENTION, EMBED, HEAD, XENT, OPTIMIZER,
       WSI_REFRESH)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``. A
    fresh scope per call, so recursive functions nest correctly."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
