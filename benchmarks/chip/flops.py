"""Operations and bytes of the work the benchmark times, from shapes alone.

Everything here is arithmetic on the configuration file and the call
shapes; nothing reads the program. A matmul of (M, I) by (I, O) is
2*M*I*O operations.

* :func:`lowrank_fwd` — one call of the fused low-rank forward
  y = (x R^T) L^T, x (M, I), R (K, I), L (O, K).
* :func:`lm_token_flops` — forward operations of one token of a decoder
  language model whose linears follow the resolved plan ranks, with its
  attention over ``ctx`` earlier positions and the vocabulary head.
* :func:`train_step_flops` — forward plus backward (3x forward) of a
  training step; rematerialisation and subspace upkeep are not counted.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lowrank_fwd(m: int, i: int, k: int, o: int, itemsize: int = 2) -> dict:
    """Operations and unpadded HBM bytes of one fused low-rank forward:
    read x, R, L once, write y once (the rank-K sketch stays on chip)."""
    return {"flops": 2 * m * k * (i + o),
            "bytes": itemsize * (m * i + k * i + o * k + m * o)}


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])


def linear_flops(site: dict) -> int:
    """Forward operations per token of one linear site of the plan:
    2*K*(I+O) factored, 2*I*O dense."""
    i, o, k = site["in"], site["out"], site["rank"]
    return 2 * k * (i + o) if k else 2 * i * o


def lm_token_flops(cfg: dict, ctx: float) -> float:
    """Forward operations of one token attending over ``ctx`` positions
    (itself included): every layer's linears at the plan ranks, the
    attention scores and the weighted sum of values, and the tied or
    untied vocabulary head over the padded table."""
    m = cfg["model"]
    sites = cfg["plan"]["sites"]
    per_layer = sum(linear_flops(s) for s in sites.values())
    heads, head_dim = m["num_attention_heads"], m["head_dim"]
    attn = 2 * 2 * heads * head_dim * ctx          # q.k and p.v
    head = 2 * m["hidden_size"] * cfg["plan"]["padded_vocab"]
    return m["num_hidden_layers"] * (per_layer + attn) + head


def lm_sequence_flops(cfg: dict, seq: int) -> float:
    """Forward operations of a causal sequence of ``seq`` tokens: token t
    attends over t + 1 positions, so the mean context is (seq + 1) / 2."""
    return seq * lm_token_flops(cfg, (seq + 1) / 2)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward operations of one training step (backward is
    twice the forward: the input and the weight gradient of every matmul)."""
    return 3 * batch * lm_sequence_flops(cfg, seq)
