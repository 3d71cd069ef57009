"""Share of the train step's device time under the ``lm.embed``,
``lm.head`` and ``lm.xent`` scopes: the tied vocabulary matrix's gather
and logits, their gradients, and the softmax cross-entropy. Percent of the
step program's op time in the traced window (scopes.py)."""
import scopes


def read(ctx):
    return scopes.share(ctx, ("lm.embed", "lm.head", "lm.xent"))
