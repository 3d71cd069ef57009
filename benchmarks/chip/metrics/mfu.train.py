"""Model FLOP/s utilization of the train step: forward and backward
operations per token at the plan's ranks (flops.py; rematerialisation and
subspace upkeep not counted) times tokens per second over the traced part
of the window, over the chip's bf16 peak. Percent."""
import flops


def read(ctx):
    c, mix = ctx["result"]["counters"], ctx["mix"]
    if not c["traced_steps"] or not c["traced_s"]:
        return None
    per_token = flops.train_step_flops(ctx["cfg"], mix["batch"], mix["seq"]) \
        / (mix["batch"] * mix["seq"])
    tok_s = c["traced_steps"] * mix["batch"] * mix["seq"] / c["traced_s"]
    return 100.0 * tok_s * per_token / ctx["peak"]["bf16_flops"]
