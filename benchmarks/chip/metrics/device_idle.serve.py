"""Share of the traced serving window in which no operation ran on the
device (1 - union of op intervals / window), percent."""


def read(ctx):
    t = ctx["trace"]
    if not t.ops or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
