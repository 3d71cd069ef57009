"""Operations and bytes of the work the benchmark times, from shapes alone.

Everything here is arithmetic on the configuration file and the call
shapes; nothing reads the program. A matmul of (M, I) by (I, O) is
2*M*I*O operations.

* :func:`lowrank_fwd` — one call of the fused low-rank forward
  y = (x R^T) L^T, x (M, I), R (K, I), L (O, K).
* :func:`lm_token_flops` — forward operations of one token of a decoder
  language model whose linears follow the resolved plan ranks, summed
  layer by layer over the parts of each layer's kind (:data:`PARTS`),
  with the vocabulary head.
* :func:`stack_flops` — forward operations per token of every layer.
* :func:`train_step_flops` — forward plus backward (3x forward) of a
  training step, as the configuration's family counts it from the above
  (``families/<family>.py``); rematerialisation and subspace upkeep are
  not counted.

A layer's parts:

* ``attention`` — the ``attn/*`` sites, and scores and weighted sum over
  ``ctx`` positions: 2*2*H*Dh*ctx;
* ``mlp`` — the ``mlp/*`` sites;
* ``mamba2`` — the ``ssm/*`` sites, and the chunked SSD at chunk Q,
  counted whole, with one group of B and C: 2*Q*N (C B^T) + 2*Q*H*P (the
  intra-chunk combine) + 4*H*P*N (chunk states in and out), where H*P is
  the inner width ``expand`` * hidden and N ``d_state``. The short
  convolution and the gated norm are elementwise and not counted.
"""
from __future__ import annotations

import json
from pathlib import Path

import model

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


# the parts each of the program's block kinds computes; a configuration's
# ``program.parts`` names those of a kind this table lacks
PARTS = {"dense": ("attention", "mlp"), "enc": ("attention", "mlp"),
         "mamba2": ("mamba2",)}
SITES = {"attention": "attn/", "mlp": "mlp/", "mamba2": "ssm/"}


def _sites(cfg: dict, part: str) -> int:
    return sum(linear_flops(s) for name, s in cfg["plan"]["sites"].items()
               if name.startswith(SITES[part]))


def part_flops(cfg: dict, part: str, ctx: float) -> float:
    """Forward operations per token of one layer part (see above)."""
    m = cfg["model"]
    if part == "attention":
        heads = m["num_attention_heads"]
        head_dim = m.get("head_dim", m["hidden_size"] // heads)
        return _sites(cfg, part) + 2 * 2 * heads * head_dim * ctx
    if part == "mlp":
        return _sites(cfg, part)
    if part == "mamba2":
        ssm = cfg["program"]["ssm"]
        q, n = ssm["chunk"], ssm["d_state"]
        inner = ssm["expand"] * m["hidden_size"]          # H * P
        return _sites(cfg, part) + 2 * q * n + 2 * q * inner + 4 * inner * n
    raise KeyError(f"no operation count for layer part {part!r}")


def layer_flops(cfg: dict, kind: str, ctx: float) -> float:
    """Forward operations per token of one layer of ``kind``; a kind that
    neither :data:`PARTS` nor the configuration's ``program.parts`` names
    is an error, never 0."""
    parts = dict(PARTS, **cfg["program"].get("parts", {}))
    if kind not in parts:
        raise KeyError(f"no parts known for layer kind {kind!r}; give them "
                       "under program.parts")
    return sum(part_flops(cfg, p, ctx) for p in parts[kind])


def stack_flops(cfg: dict, ctx: float) -> float:
    """Forward operations per token of every layer of the stack."""
    return sum(g["repeat"] * sum(layer_flops(cfg, k, ctx)
                                 for k in g["pattern"])
               for g in model.layer_groups(cfg))


def lm_token_flops(cfg: dict, ctx: float) -> float:
    """Forward operations of one token attending over ``ctx`` positions
    (itself included): every layer's parts, and the tied or untied
    vocabulary head over the padded table."""
    head = 2 * cfg["model"]["hidden_size"] * cfg["plan"]["padded_vocab"]
    return stack_flops(cfg, ctx) + head


def lm_sequence_flops(cfg: dict, seq: int) -> float:
    """Forward operations of a causal sequence of ``seq`` tokens: token t
    attends over t + 1 positions, so the mean context is (seq + 1) / 2."""
    return seq * lm_token_flops(cfg, (seq + 1) / 2)


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward + backward operations of one training step of ``batch`` rows
    of ``seq`` tokens, as the configuration's family counts them
    (``families/<family>.py``)."""
    return model.family(cfg).step_flops(cfg, batch, seq)
