"""Share of the train step's device time under the ``attn.core`` scope:
scores, mask, softmax and combine, forward, rematerialised and backward
(not the projections, not RoPE). Percent of the step program's op time in
the traced window (scopes.py)."""
import scopes


def read(ctx):
    return scopes.share(ctx, ("attn.core",))
