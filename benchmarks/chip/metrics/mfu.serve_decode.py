"""Model FLOP/s utilization of serving over the traced part of the window:
forward operations at the plan's ranks (flops.py) of every token the
engine processed there (prefill tokens at half their prompt's length of
context, decoded tokens at their prompt plus half their output, taken as
the mix's means), over traced seconds times the bf16 peak, percent."""
import flops


def read(ctx):
    res, cfg = ctx["result"], ctx["cfg"]
    c = res["counters"]
    tr, secs = c["traced"], c["traced_s"]
    reqs = res["requests"]
    if not secs or not reqs:
        return None
    p = sum(r[1] for r in reqs) / len(reqs)
    o = sum(r[2] for r in reqs) / len(reqs)
    ops = tr["prefill_tokens"] * flops.lm_token_flops(cfg, p / 2) \
        + tr["decode_tokens"] * flops.lm_token_flops(cfg, p + o / 2)
    return 100.0 * ops / (secs * ctx["peak"]["bf16_flops"])
