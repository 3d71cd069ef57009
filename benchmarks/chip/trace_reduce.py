"""From a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads, with ``jax.profiler.ProfileData`` alone:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, each op with its interval and its HLO text (which names the op,
  its opcode, its operand shapes and, for a Pallas kernel, the kernel);
* device programs: the ``XLA Modules`` line of the same planes;
* the benchmark's own host spans: events named ``bench.*`` on any host
  line (``jax.profiler.TraceAnnotation`` in the drivers).

Device times are moved onto the host's clock first (see ``_align``). The
traced window runs from the first host span's start to the last one's
end. A device is busy where any of its ops runs (the union of their
intervals); idle time is the rest of the window, and each idle gap is
labelled by the host span that overlaps it most.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
# the host runtime's event for handing a program to the device
ENQUEUE = "DoEnqueueProgram"


@dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns
    label: str = ""
    device: int = 0


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Trace:
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    devices: int = 1
    window: tuple = (0, 0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int):
        lo, hi = self.window
        return [(max(s, lo), min(e, hi)) for s, e in _union(
            (o.start, o.end) for o in self.ops if o.device == device)
            if min(e, hi) > max(s, lo)]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        tot = sum(e - s for d in range(self.devices)
                  for s, e in self.busy_intervals(d))
        return tot * 1e-9 / self.devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def gaps(self, device: int = 0):
        """Idle intervals of ``device`` inside the window."""
        lo, hi = self.window
        cur, out = lo, []
        for s, e in self.busy_intervals(device):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            out.append((cur, hi))
        return out

    def span_at(self, s: int, e: int) -> str:
        """The host span that overlaps [s, e) most ("none" if none does)."""
        best, name = 0, "none"
        for sp in self.spans:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best:
                best, name = ov, sp.name
        return name

    def top_gaps(self, k: int = 10):
        """The ``k`` longest idle gaps of device 0, labelled."""
        g = sorted(self.gaps(0), key=lambda t: t[0] - t[1])[:k]
        return [[self.span_at(s, e), (e - s) * 1e-9] for s, e in g]

    def gap_time_by_span(self) -> dict:
        """Idle seconds of device 0 by the host span they fall under."""
        out: dict = {}
        for s, e in self.gaps(0):
            n = self.span_at(s, e)
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9
        return out

    def top_ops(self, k: int = 10):
        """The ``k`` op names with the most device time, over all devices;
        loops and calls, whose bodies' ops are listed themselves, left out."""
        tot: dict = {}
        for o in self.ops:
            if o.name.split(" ")[1:2] in CONTAINERS:
                continue
            tot[o.name] = tot.get(o.name, 0) + (o.end - o.start)
        best = sorted(tot.items(), key=lambda t: -t[1])[:k]
        return [[n, v * 1e-9 / self.devices] for n, v in best]

    def modules_matching(self, pattern: str):
        rx = re.compile(pattern)
        return [m for m in self.modules if rx.search(m.name)]

    def ops_in(self, op_pattern: str, module_pattern: str | None = None):
        """The ops whose HLO text matches ``op_pattern``, inside programs
        whose name matches ``module_pattern``."""
        rx = re.compile(op_pattern)
        ops = [o for o in self.ops if rx.search(o.label)]
        if module_pattern is None:
            return ops
        mods = sorted(self.modules_matching(module_pattern),
                      key=lambda m: (m.device, m.start))
        keys = [(m.device, m.start) for m in mods]
        out = []
        for o in ops:
            i = bisect.bisect_right(keys, (o.device, o.start)) - 1
            if i >= 0 and mods[i].device == o.device and o.end <= mods[i].end:
                out.append(o)
        return out

    def op_time(self, op_pattern: str, module_pattern: str | None = None):
        """(seconds, count) of :meth:`ops_in`."""
        ops = self.ops_in(op_pattern, module_pattern)
        return sum(o.end - o.start for o in ops) * 1e-9, len(ops)


CONTAINERS = (["while"], ["conditional"], ["call"])
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# an op's name is its HLO text: "%name = <shape> opcode(operands), ..."
OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_\-]*)\(")
SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def op_key(text: str) -> str:
    """Short name of an op for the breakdown: the HLO instruction's name,
    its opcode and its result's first shape, e.g.
    ``%lowrank_matmul_fused.1 custom-call bf16[32,4864]``."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    if m is None:
        return name[:80]
    shape = SHAPE.search(rest)
    return f"{name} {m.group(1)}" + (f" {shape.group(0)}" if shape else "")


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return str(v)
    return None


def _align(t: Trace, enqueued: dict) -> None:
    """Put the device's clock on the host's. The trace's device times can
    lie a millisecond or more off the host's; a program cannot start before
    the host enqueued it, and on an idle device it starts at once, so the
    shift is the one that makes the earliest start-minus-enqueue zero."""
    lags = [m.start - enqueued[m.label] for m in t.modules
            if m.label in enqueued]
    if not lags:
        return
    shift = -min(lags)
    for e in t.ops + t.modules:
        e.start += shift
        e.end += shift


def reduce(path: str, devices: int = 1) -> Trace:
    """Read the trace at ``path``; ``devices`` is the number of chips the
    run used (planes ``/device:TPU:0`` and up)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    t = Trace(devices=devices)
    dev_planes = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                        key=lambda p: int(DEVICE_PLANE.match(p.name).group(2)))
    for di, plane in enumerate(dev_planes[:devices]):
        for line in plane.lines:
            if line.name == "XLA Ops":
                t.ops.extend(Event(op_key(e.name), e.start_ns,
                                   e.start_ns + e.duration_ns, e.name, di)
                             for e in line.events)
            elif line.name == "XLA Modules":
                t.modules.extend(Event(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       _stat(e, "run_id") or "", di)
                                 for e in line.events)
    enqueued = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    t.spans.append(Event(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
                elif e.name == ENQUEUE:
                    rid = _stat(e, "run_id")
                    if rid is not None:
                        enqueued[rid] = e.start_ns
    _align(t, enqueued)
    if t.spans:
        t.window = (min(s.start for s in t.spans), max(s.end for s in t.spans))
    elif t.ops:
        t.window = (min(o.start for o in t.ops), max(o.end for o in t.ops))
    return t
