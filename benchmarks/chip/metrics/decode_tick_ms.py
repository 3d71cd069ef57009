"""Wall time of one engine decode tick over the window: the engine's own
``decode_s / decode_steps`` counters (its decode blocks on the sampled
tokens, so this is the tick's whole host-to-host time), ms."""


def read(ctx):
    c = ctx["result"]["counters"]
    if not c.get("decode_steps"):
        return None
    return 1e3 * c["decode_s"] / c["decode_steps"]
