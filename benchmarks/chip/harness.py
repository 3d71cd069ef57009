"""The benchmark's shared machinery: cells, configurations, seeds, the
device check, the compile cache, the profiler and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration file under ``configs/`` and a traffic file under
``traffic/``. The traffic file's ``kind`` names the driver module under
``drivers/`` that runs it; each per-layer metric is read by
``metrics/<name>.py``; the cell's limits are ``limits/<cell>.json``.
Nothing here changes when a cell is added.

A configuration file holds ``name``, ``model`` (the published config's
keys), ``wasi`` (the method's settings), ``plan`` (the ranks the program
must resolve) and ``program``, with ``dtype``, ``norm``, ``mlp_act``,
``remat`` and ``block``. Whatever is particular to its model it says in
keys of its own, each optional, whose absence gives a decoder language
model of one uniform layer kind checked against ``reference/qwen2.py``:

* ``program.family``: the module ``families/<family>.py`` (``lm``, the
  default, or ``vit`` with ``program.n_classes``): the program's
  parameter and state templates and loss, what a batch holds, and a
  step's operations;
* ``program.groups``: ``[{"pattern": [kind, ...], "repeat": n}, ...]`` in
  place of one group of ``program.block``; the layers must sum to
  ``model.num_hidden_layers``;
* ``program.ssm``, ``program.moe``: the program's ``SsmConfig`` and
  ``MoeConfig``;
* ``program.fields``: other ``ModelConfig`` fields by name (a name that
  is no field is an error);
* ``program.parts``: the parts (``attention``, ``mlp``, ``mamba2``) of a
  layer kind that ``flops.PARTS`` lacks;
* ``init``: {leaf name or path tail: rule} for leaves the default rules
  draw wrongly (``model._by_rule`` lists the rules);
* ``reference``: the plain reference, ``reference/<name>.py``, with
  ``train_reference`` and ``leaf_norms`` (default ``qwen2``);
* ``scopes``: scope names the configuration's program sets beyond
  ``scopes.SCOPES``, read into the cell's table of device-time shares.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC_FILE = ROOT / "BENCHMARK.json"
# fixed path inside the checkout: the directory is part of the persistent
# cache's key, so it must not move between runs
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or a chip without peaks."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    return json.loads(SPEC_FILE.read_text())


def cell(spec: dict, name: str) -> dict:
    """The workload entry ``name`` with its configuration file and traffic
    file read in."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = dict(by_name[name])
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    w["cfg"] = json.loads((ROOT / conf["file"]).read_text())
    w["mix"] = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    w["limits"] = json.loads((HERE / "limits" / f"{name}.json").read_text())
    w["end_to_end"] = [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])]
    w["per_layer"] = [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])]
    return w


def driver(kind: str):
    return load_module(HERE / "drivers" / f"{kind}.py")


# -- seeds --------------------------------------------------------------------

def jax_key(seed: int, stream: int = 0):
    """A PRNG key for ``seed`` (any non-negative int, also past 32 bits)
    and a named sub-stream."""
    import jax

    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)


def np_rng(seed: int, stream: int = 0):
    import numpy as np

    return np.random.default_rng([seed, stream])


# -- device ---------------------------------------------------------------------

def require_chip(chips: int) -> dict:
    """The devices the cell runs on, or :class:`NoChip`."""
    import jax

    import flops

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        flops.peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Allocator peak on the fullest of the cell's chips (0 where the
    backend keeps no statistics, as the CPU)."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0)))
    return max(peaks)


def use_compile_cache() -> None:
    """JAX's persistent cache in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program in it, however short
    its compile, so that a second run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the programs JAX traces while ``active`` (a trace is the first
    step of every compile, also of one the persistent cache serves)."""

    EVENT = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1


class HostWatch:
    """What the host did during a window, to tell a stall's cause: the
    collector's pauses, the process's context switches and page faults,
    the machine's load, and the longest gap between units of work
    (``mark()`` after each). Starting it first collects and freezes what
    set-up made, so that a collection in the window scans only what the
    window made; ``stop()`` hands it back to the collector."""

    def __init__(self):
        import gc

        gc.collect()
        gc.freeze()
        self.pauses, self.marks, self._gc0 = [], [], 0.0
        gc.callbacks.append(self._on_gc)
        self.ru0, self.load0 = self._usage(), os.getloadavg()[0]

    @staticmethod
    def _usage():
        import resource

        return resource.getrusage(resource.RUSAGE_SELF)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc0 = now()
        else:
            self.pauses.append((now() - self._gc0, info["generation"]))

    def mark(self) -> None:
        self.marks.append(now())

    def stop(self) -> dict:
        import gc

        gc.callbacks.remove(self._on_gc)
        gc.unfreeze()
        ru = self._usage()
        gaps = [b - a for a, b in zip(self.marks, self.marks[1:])]
        longest = max(range(len(gaps)), key=gaps.__getitem__) if gaps else 0
        return {
            "gc_pauses": len(self.pauses),
            "gc_pause_max_s": max((p for p, _ in self.pauses), default=0.0),
            "gc_pause_sum_s": sum(p for p, _ in self.pauses),
            "gc_gen2": sum(1 for _, g in self.pauses if g == 2),
            "cpu_user_s": ru.ru_utime - self.ru0.ru_utime,
            "cpu_sys_s": ru.ru_stime - self.ru0.ru_stime,
            "ctx_involuntary": ru.ru_nivcsw - self.ru0.ru_nivcsw,
            "ctx_voluntary": ru.ru_nvcsw - self.ru0.ru_nvcsw,
            "page_faults_major": ru.ru_majflt - self.ru0.ru_majflt,
            "load1_start": self.load0, "load1_end": os.getloadavg()[0],
            "gap_median_s": sorted(gaps)[len(gaps) // 2] if gaps else 0.0,
            "gap_max_s": gaps[longest] if gaps else 0.0,
            "gap_max_at": longest,
            "gaps": len(gaps),
        }


# -- profiler -------------------------------------------------------------------

_SPANS = False     # True while a profiler's traced part of a window runs


def span(name: str):
    """A host span in the profiler's trace, while the traced part of a
    window runs (a no-op otherwise)."""
    import contextlib

    import jax

    if not _SPANS:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """The JAX profiler over ``seconds`` of a window, from ``start_s`` into
    it, when a trace directory is given (a whole window's trace would be
    too large to write and read back). ``poll(elapsed)`` after each unit of
    work starts and stops it. The profiler starts ``settle_s`` before the
    traced part: its first second or two can hold the device back (on a
    TPU v5e, one traced training run in three showed the device idle 1.7 s
    there, the host waiting on a step), so the harness's spans, which bound
    the part ``trace_reduce`` reads, begin only after it."""

    def __init__(self, trace_dir: Path | None, start_s: float,
                 seconds: float, settle_s: float = 2.0):
        self.dir, self.start_s, self.seconds = trace_dir, start_s, seconds
        self.settle_s = settle_s
        self.on, self.counting, self.done = False, False, trace_dir is None
        self.t0, self.traced_s = 0.0, 0.0

    def poll(self, elapsed: float, sync=None) -> bool:
        """Start the profiler once the window is ``start_s`` old, the traced
        part ``settle_s`` later, and stop both ``seconds`` after that (once
        ``sync`` is ready). True when the traced part starts or stops."""
        import jax

        global _SPANS
        if self.done:
            return False
        if not self.on:
            if elapsed >= self.start_s:
                jax.profiler.start_trace(str(self.dir))
                self.on, self.t0 = True, now()
            return False
        if not self.counting:
            if now() - self.t0 < self.settle_s:
                return False
            self.counting, self.t0, _SPANS = True, now(), True
            return True
        if now() - self.t0 < self.seconds:
            return False
        self.stop(sync)
        return True

    def stop(self, sync=None) -> None:
        global _SPANS
        if not self.on:
            return
        import jax

        if sync is not None:
            jax.block_until_ready(sync)
        if self.counting:
            self.traced_s = now() - self.t0
        _SPANS = False
        jax.profiler.stop_trace()
        self.on, self.counting, self.done = False, False, True


def xplane_file(trace_dir: Path) -> str:
    files = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return files[-1]


# -- statistics -----------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("empty sample")
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


# -- result line ----------------------------------------------------------------

def emit(result: dict, checks: list[dict]) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, then the result line as the last line of stdout."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    result = dict(result)
    # a NaN or infinite reading is written as a string: JSON has no such
    # number, and the line must stay readable when a run goes wrong
    result["checks"] = {c["name"]: {"value": c["value"] if math.isfinite(
        c["value"]) else str(c["value"]), "limit": c["limit"]}
        for c in checks}
    print(json.dumps(result), flush=True)


def check(name: str, value: float, limit: float) -> dict:
    """A number compared with its upper limit; NaN fails."""
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def now() -> float:
    return time.perf_counter()
