"""Programs JAX traced inside the measured window (each is a compile, or
at best a fetch from the persistent cache); 0 when set-up warmed every
shape the traffic reaches."""


def read(ctx):
    return float(ctx["result"]["counters"]["compiles_in_window"])
