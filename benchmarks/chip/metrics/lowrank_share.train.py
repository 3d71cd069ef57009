"""Share of the train step's device time under the ``wasi.matmul`` and
``wasi.grad`` scopes: the factored forward with its rank-K sketch, and the
custom VJPs' backward (dx, and dL, dR by f_LR from the Tucker factors).
Percent of the step program's op time in the traced window (scopes.py)."""
import scopes


def read(ctx):
    return scopes.share(ctx, ("wasi.matmul", "wasi.grad"))
