"""The train step's device time by named scope.

The program sets a ``jax.named_scope`` where each layer's work happens
(``repro/utils/scopes.py``); XLA keeps it in every compiled op's
``metadata={op_name=".../<scope>/..."}``. A TPU trace's ops carry their HLO
instruction names but not that metadata, so the two are joined here:

* the map: the step the cell's driver jits, as its ``lowered(cfg, mix)``
  gives it on abstract arguments (no weights on the device), compiled
  through the persistent cache the run has filled; its
  ``Compiled.as_text()`` gives {instruction name: op_name}. Built once
  per cell and process, whichever reader asks first;
* the attribution: every op of the step program (``XLA Modules`` events
  named ``jit_step(...)``) inside ``Trace.window``, each interval clipped
  to the window, loops, conditionals and calls left out (their bodies'
  ops are counted themselves, as in ``Trace.top_ops``). An op goes to the
  innermost scope of the cell's table on its op_name path, to ``other`` on
  none, and to ``unmapped`` when its name is not in the map. A fusion
  carries the metadata XLA gives it, that of its root. The table is
  :data:`SCOPES`, the program's base scope names, and those a
  configuration's ``scopes`` key adds for its own layers.

A share is a bucket's seconds over all the step program's op seconds in
the window, in percent. No share is read when ``unmapped`` passes 1 % of
that time (a map of another program), or when the program sets no scopes.
"""
from __future__ import annotations

import json
import re
import sys

import harness
import trace_reduce

# the yardstick: the program's base scope names (tests hold each in the
# program's list); a configuration adds its own under ``scopes``
SCOPES = ("wasi.asi_upkeep", "wasi.matmul", "wasi.grad", "attn.core",
          "lm.embed", "lm.head", "lm.xent", "train.optimizer",
          "train.wsi_refresh")
OTHER, UNMAPPED = "other", "unmapped"
PROGRAM = r"^jit_step\("
UNMAPPED_LIMIT = 0.01

INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a transform applied outside the scopes wraps the first of them:
# "jvp(lm.head)", "transpose(jvp(lm.head))"
TRANSFORMED = re.compile(r"^[\w-]+\((.*)\)$")
HIT_EVENT = "/jax/compilation_cache/cache_hits"

_maps: dict = {}
_last: dict = {}


def names_of(text: str) -> dict:
    """{instruction name: op_name path ("" without metadata)} of every
    instruction in an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            p = OP_NAME.search(line)
            out[m.group(1)] = p.group(1) if p else ""
    return out


def table(cfg: dict) -> tuple:
    """The scope names a cell of this configuration reads: :data:`SCOPES`
    and the configuration's own ``scopes``."""
    return SCOPES + tuple(s for s in cfg.get("scopes", ()) if s not in SCOPES)


def scope_of(path: str, scopes: tuple = SCOPES) -> str:
    """The innermost scope of ``scopes`` on an op_name path."""
    for part in reversed(path.split("/")):
        while (m := TRANSFORMED.match(part)):
            part = m.group(1)
        if part in scopes:
            return part
    return OTHER


def lowered_step(cfg: dict, mix: dict):
    """The step the cell's driver jits, lowered on abstract arguments."""
    return harness.driver(mix["kind"]).lowered(cfg, mix)


def _compile_without_cache(cfg: dict, mix: dict):
    """A fresh compile: a cached executable can come from a build of the
    same code without scopes (the cache's key leaves metadata out)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered_step(cfg, mix).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def step_map(cfg: dict, mix: dict) -> tuple[dict, dict]:
    """(instruction map, how it was built) of the cell's train step."""
    key = json.dumps([cfg, mix], sort_keys=True)
    if key in _maps:
        return _maps[key]
    import jax

    hits = []

    def on_event(event, **_):
        if event == HIT_EVENT:
            hits.append(event)

    t0 = harness.now()
    jax.monitoring.register_event_listener(on_event)
    try:
        names = names_of(lowered_step(cfg, mix).compile().as_text())
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    info = {"compile_s": harness.now() - t0, "cache_hit": bool(hits),
            "recompiled": False}
    if not any(scope_of(p, table(cfg)) != OTHER for p in names.values()):
        names = names_of(_compile_without_cache(cfg, mix).as_text())
        info.update(recompiled=True, compile_s=harness.now() - t0)
    info["instructions"] = len(names)
    _maps[key] = names, info
    return _maps[key]


def attribute(trace, names: dict, program: str = PROGRAM,
              missing: dict | None = None, scopes: tuple = SCOPES) -> dict:
    """Seconds of the step program's ops in the trace's window, by bucket
    (every scope of ``scopes``, ``other``, ``unmapped``); the seconds of
    each unmapped name go to ``missing`` if given."""
    lo, hi = trace.window
    out = dict.fromkeys(scopes + (OTHER, UNMAPPED), 0.0)
    for o in trace.ops_in("", program):
        if o.name.split(" ")[1:2] in trace_reduce.CONTAINERS:
            continue
        dt = min(o.end, hi) - max(o.start, lo)
        if dt <= 0:
            continue
        name = o.label.partition(" = ")[0].strip().lstrip("%")
        bucket = scope_of(names[name], scopes) if name in names else UNMAPPED
        out[bucket] += dt * 1e-9 / trace.devices
        if bucket == UNMAPPED and missing is not None:
            missing[name] = missing.get(name, 0.0) + dt * 1e-9
    return out


def shares(ctx) -> dict | None:
    """Percent of the step program's op time in the window by bucket, or
    None (no step ops, a program without scopes, or too much unmapped).
    Read once per trace; the first read prints the whole table to stderr."""
    trace = ctx["trace"]
    if _last.get("trace") is trace:
        return _last["shares"]
    _last.update(trace=trace, shares=None)
    try:
        import repro.utils.scopes  # noqa: F401  (a program that sets them)
    except ImportError:
        return None
    if not trace.ops_in("", PROGRAM):
        return None
    names, info = step_map(ctx["cfg"], ctx["mix"])
    missing: dict = {}
    secs = attribute(trace, names, missing=missing,
                     scopes=table(ctx["cfg"]))
    total = sum(secs.values())
    if total <= 0:
        return None
    pct = {k: 100.0 * v / total for k, v in secs.items()}
    worst = sorted(missing, key=missing.get, reverse=True)[:5]
    print("scope_shares " + json.dumps(
        {"seconds": secs, "percent": pct, "total_s": total, "map": info,
         "unmapped_names": worst}), file=sys.stderr, flush=True)
    if secs[UNMAPPED] > UNMAPPED_LIMIT * total:
        return None
    _last["shares"] = pct
    return pct


def share(ctx, scopes: tuple) -> float | None:
    """The summed share of ``scopes``, percent (None as :func:`shares`)."""
    pct = shares(ctx)
    if pct is None:
        return None
    return sum(pct[s] for s in scopes)
