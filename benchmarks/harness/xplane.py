"""From the profiler's `.xplane.pb` to busy/idle, kernel time, exposed
collectives and the breakdown.  Read with nothing but JAX
(`jax.profiler.ProfileData`).

What a TPU trace holds (looked at by hand, PR 25, with
`tools/trace_summary.py`): one plane per chip, `/device:TPU:<n>`; on it a
line `XLA Modules` with one event per run of a compiled program
(`jit_train_step(<fingerprint>)`) and a line `XLA Ops` with one event per
HLO operation, named by the whole instruction text (a Mosaic kernel's
instruction carries the kernel's name: `%flash_packed_fwd.3 = ...`), a
`while` (the scan over the stacked blocks) spanning the operations of its
body.  (`Steps` repeats the modules; `Async XLA Ops` holds copies that run
beside the operations and is not read.)  Host threads are lines of the
plane `/host:CPU`; the benchmark's own spans are
`jax.profiler.TraceAnnotation`s whose names start with `bench.`, on the
same clock.

Everything below works on plain tuples so that it can be checked on a
synthetic trace (tests/benchmarks/test_bench_xplane.py).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # an operation's own name: "flash_packed_fwd.3"
    start: float            # ns
    end: float              # ns
    text: str = ""          # the event's string stats, joined

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: List[Event]


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def own_name(event_name: str) -> str:
    """A device operation's event is named by its whole HLO instruction,
    "%fusion.3 = bf16[...] fusion(... %flash_packed_fwd.3 ...)": keep the
    name left of " = ", or an operand's name would match as the kernel's."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        text = " ".join(str(v) for _, v in e.stats if isinstance(v, str))
        out.append(Event(own_name(e.name), float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns), text))
    return out


def from_profile_data(data) -> Trace:
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    modules += _events(line)
            devices.append(DeviceTrace(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(HOST_SPAN_PREFIX)]
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return Trace(devices, spans)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile_data(ProfileData.from_file(find_xplane(log_dir)))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union(a) that no interval of b covers."""
    out, b = [], union(b)
    for lo, hi in union(a):
        for blo, bhi in b:
            if bhi <= lo or blo >= hi:
                continue
            if blo > lo:
                out.append((lo, blo))
            lo = max(lo, bhi)
            if lo >= hi:
                break
        if lo < hi:
            out.append((lo, hi))
    return out


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each operation's own time: its duration less what the operations
    nested inside it cover (a `while` holds its body's operations)."""
    out, stack = [], []           # stack of [event, covered by children]
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            done, covered = stack.pop()
            out.append((done, done.dur - covered))
        if stack:
            stack[-1][1] += min(e.end, stack[-1][0].end) - e.start
        stack.append([e, 0.0])
    while stack:
        done, covered = stack.pop()
        out.append((done, done.dur - covered))
    return out


def leaves(ops: Sequence[Event]) -> List[Event]:
    """Operations that hold no other operation."""
    return [e for e, own in self_times(ops) if own >= e.dur - 1e-6]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DeviceWindow:
    name: str
    lo: float
    hi: float
    steps: int
    ops: List[Event]               # clipped to whole steps
    busy: List[Interval]

    @property
    def window(self) -> float:
        return self.hi - self.lo


def device_window(dev: DeviceTrace, step_module: str) -> Optional[DeviceWindow]:
    """The whole runs of the step's program that the trace holds on this
    chip: the window runs from the first one's start to the last one's end."""
    runs = sorted((m for m in dev.modules if step_module in m.name),
                  key=lambda m: m.start)
    if not runs:
        return None
    lo, hi = runs[0].start, max(m.end for m in runs)
    ops = [e for e in dev.ops if e.start >= lo and e.end <= hi]
    # a `while` spans its body's operations and the gaps between them: only
    # operations that hold no other count as the device running something
    busy = clip(union([(e.start, e.end) for e in leaves(ops)]), lo, hi)
    return DeviceWindow(dev.name, lo, hi, len(runs), ops, busy)


def windows(trace: Trace, step_module: str) -> List[DeviceWindow]:
    found = [device_window(d, step_module) for d in trace.devices]
    return [w for w in found if w is not None and w.ops]


def busy_and_window_s(ws: Sequence[DeviceWindow]) -> Tuple[float, float]:
    """Seconds in which an operation ran, and the window's length, averaged
    over the chips."""
    n = len(ws)
    return (sum(total(w.busy) for w in ws) / n / 1e9,
            sum(w.window for w in ws) / n / 1e9)


def idle_share(ws: Sequence[DeviceWindow]) -> float:
    """1 - busy / window on the worst chip."""
    return max(1.0 - total(w.busy) / w.window for w in ws)


def matches(e: Event, names: Sequence[str]) -> bool:
    return any(n in e.name or n in e.text for n in names)


def kernel_ms_per_step(ws: Sequence[DeviceWindow],
                       names: Sequence[str]) -> Optional[float]:
    """Summed device time of the events that carry one of `names`, per
    step, on the slowest chip.  None where no such event ran."""
    per_dev = []
    for w in ws:
        hit = [e for e in leaves(w.ops) if matches(e, names)]
        if hit:
            per_dev.append(sum(e.dur for e in hit) / w.steps / 1e6)
    return max(per_dev) if per_dev else None


def exposed_collective_ms_per_step(ws: Sequence[DeviceWindow]) -> Optional[float]:
    """Time inside collective operations during which no other operation
    runs on that chip, per step, worst chip.  None without collectives."""
    per_dev = []
    for w in ws:
        flat = leaves(w.ops)
        coll = [(e.start, e.end) for e in flat if COLLECTIVE.match(e.name)]
        if not coll:
            continue
        rest = [(e.start, e.end) for e in flat if not COLLECTIVE.match(e.name)]
        per_dev.append(total(subtract(coll, rest)) / w.steps / 1e6)
    return max(per_dev) if per_dev else None


def top_ops(ws: Sequence[DeviceWindow], n: int = 10) -> List[list]:
    """[name, seconds] of the operations with the most own time, summed by
    name over the window, on the first chip."""
    by_name: Dict[str, float] = {}
    for e, own in self_times(ws[0].ops):
        by_name[e.name] = by_name.get(e.name, 0.0) + own
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(w: DeviceWindow, host_spans: Sequence[Event],
              n: int = 10) -> List[list]:
    """[what the host was doing, seconds] for the longest gaps in which
    nothing ran on the first chip: the benchmark's host span that overlaps
    the gap most, or "no_span"."""
    gaps = subtract([(w.lo, w.hi)], w.busy)
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_cover = "no_span", 0.0
        for s in host_spans:
            cover = min(hi, s.end) - max(lo, s.start)
            if cover > best_cover:
                best, best_cover = s.name[len(HOST_SPAN_PREFIX):], cover
        out.append([best, (hi - lo) / 1e9])
    return out
