"""Share of the train step's device time under the ``wasi.asi_upkeep``
scope: the activation subspace's power iteration, its CholeskyQR and the
projection onto the Tucker core, at every linear (twice under remat).
Percent of the step program's op time in the traced window (scopes.py)."""
import scopes


def read(ctx):
    return scopes.share(ctx, ("wasi.asi_upkeep",))
