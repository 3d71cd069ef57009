"""Roofline share of the fused low-rank forward kernel inside the engine's
decode program, percent: the least time of its calls over their device
time in the trace. Each call's shapes are read from its HLO operands,
x (M, I), R^T (I, K), L^T (K, O); its least time is the larger of
2*M*K*(I+O) operations over the bf16 peak and its unpadded bytes over HBM
bandwidth (flops.py)."""
import re

import flops

KERNEL = r"lowrank_matmul_fused[.\d]* = .* custom-call\("
PROGRAM = r"^jit__decode\("
SHAPES = re.compile(r"custom-call\((\w+)\[(\d+),(\d+)\]\S* %[^,]+, "
                    r"\w+\[(\d+),(\d+)\]\S* %[^,]+, \w+\[(\d+),(\d+)\]")
ITEMSIZE = {"bf16": 2, "f32": 4, "f16": 2}


def read(ctx):
    t, peak = ctx["trace"], ctx["peak"]
    least = secs = 0.0
    for op in t.ops_in(KERNEL, PROGRAM):
        m = SHAPES.search(op.label)
        if m is None:
            continue
        dt, rows, i, _, k, _, o = m.groups()
        c = flops.lowrank_fwd(int(rows), int(i), int(k), int(o),
                              ITEMSIZE[dt])
        least += flops.least_time_s(c["flops"], c["bytes"], peak)
        secs += (op.end - op.start) * 1e-9
    if secs <= 0:
        return None
    return 100.0 * least / secs
