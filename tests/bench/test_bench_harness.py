"""The benchmark's harness on the CPU, without the chip: the open-loop
schedule, the latency arithmetic, and the refusal to run off the chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from bench_tiny import BENCH, tiny_serve  # noqa: E402

import harness  # noqa: E402

ROOT = BENCH.parents[1]


def _serve():
    return harness.driver("serve_open_loop")


def test_schedule_is_a_function_of_the_seed():
    sv, mix = _serve(), tiny_serve()["mix"]
    a = sv.schedule(mix, 5.0, 2**33 + 7, 256)
    b = sv.schedule(mix, 5.0, 2**33 + 7, 256)
    c = sv.schedule(mix, 5.0, 11, 256)
    assert a == b
    assert a != c
    assert all(0 <= r["due"] < 5.0 for r in a)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)


def test_every_seed_serves_the_same_sizes_in_another_order():
    sv, mix = _serve(), tiny_serve()["mix"]
    a = sv.schedule(mix, 5.0, 1, 256)
    b = sv.schedule(mix, 5.0, 2, 256)
    assert len(a) == len(b) == round(mix["rate_rps"] * 5.0)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def _handle(first, last, n):
    return SimpleNamespace(_req=SimpleNamespace(
        first_token_at=first, last_token_at=last, generated=[0] * n))


def test_latencies_count_from_due_time():
    sv = _serve()
    t0 = 100.0
    # due at 1.0 s into the window, submitted late, first token at 1.25 s
    out = [({"due": 1.0, "max_new": 5}, _handle(101.25, 101.65, 5), 0.2)]
    ttft, tpot, failed = sv.latencies(out, t0, end=120.0)
    assert ttft == [pytest.approx(250.0)]
    assert tpot == [pytest.approx(100.0)]
    assert failed == 0


def test_p95_counts_an_unfinished_request_as_missing():
    sv = _serve()
    t0 = 0.0
    out = [({"due": float(i), "max_new": 2}, _handle(i + 0.01, i + 0.02, 2),
            0.0) for i in range(9)]
    out.append(({"due": 0.0, "max_new": 2}, _handle(0.0, 0.0, 0), 0.0))
    ttft, _, failed = sv.latencies(out, t0, end=80.0)
    assert failed == 1
    # the unfinished request waits until the drain ends: it is the p95
    assert harness.percentile(ttft, 95) == pytest.approx(80_000.0)
    assert harness.percentile(ttft[:-1], 95) == pytest.approx(10.0)


def test_warm_lengths_reach_every_history_bucket():
    sv, mix = _serve(), tiny_serve()["mix"]
    e = mix["engine"]
    seen = set()
    for n in sv.warm_lengths(mix):
        for end in list(range(e["prefill_chunk"], n, e["prefill_chunk"])) + [n]:
            seen.add(1 << (sv.pages_needed(end, e["page_size"]) - 1)
                     .bit_length())
    want = set()
    for n in range(mix["prompt"]["min"], mix["prompt"]["max"] + 1):
        for end in list(range(e["prefill_chunk"], n, e["prefill_chunk"])) + [n]:
            want.add(1 << (sv.pages_needed(end, e["page_size"]) - 1)
                     .bit_length())
    assert seen == want


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2-train-wasi", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_run_exits_nonzero_without_a_chip():
    r = _run(ROOT, {})
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "no accelerator" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert _no_result(r.stdout)


def test_traced_part_starts_after_the_profiler_settles(tmp_path):
    import contextlib
    import time

    prof = harness.Profiler(tmp_path, start_s=0.0, seconds=0.2,
                            settle_s=0.3)
    changes, span_kinds = [], []
    t0 = harness.now()
    while not prof.done:
        changes.append(prof.poll(harness.now() - t0))
        span_kinds.append(isinstance(harness.span("bench.x"),
                                     contextlib.nullcontext))
        time.sleep(0.02)
    assert changes.count(True) == 2
    first = changes.index(True)
    # no harness span while the profiler settles, one in every poll after
    assert all(span_kinds[:first])
    assert not any(span_kinds[first:-1])
    assert span_kinds[-1]
    assert 0.2 <= prof.traced_s < 1.0
    assert harness.xplane_file(tmp_path)


def test_host_watch_names_the_longest_gap_and_the_collections():
    import gc
    import time

    watch = harness.HostWatch()
    assert gc.get_freeze_count() > 0
    watch.mark()
    time.sleep(0.05)
    watch.mark()
    watch.mark()
    gc.collect()
    got = watch.stop()
    assert gc.get_freeze_count() == 0
    assert got["gaps"] == 2 and got["gap_max_at"] == 0
    assert got["gap_max_s"] >= 0.05
    assert got["gc_pauses"] >= 1 and got["gc_gen2"] >= 1
