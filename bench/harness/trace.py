"""Profiler trace -> what the per-layer metrics read.

The run traces its measured window with ``jax.profiler`` and marks it
with a host span ``bench.window``; the ticks, submissions and waits
inside it are host spans ``bench.*`` too.  ``reduce`` reads the
``.xplane.pb`` and returns, over the window:

* ``busy_s``: the union of the intervals in which an operation ran on
  the device (per device, averaged over the devices);
* ``ops``: device time per operation (instruction name), innermost
  operations only;
* ``programs``: device time per compiled program (``XLA Modules``),
  with each program split by whether a given kernel ran inside it;
* ``kernel_s``: device time of the operations that match a kernel name;
* ``idle``: idle time on the device, by the innermost ``bench.*`` host
  span that was open in the middle of each gap.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
Interval = Tuple[float, float]


@dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    window: Interval
    host: List[Event]
    devices: Dict[str, Device]


@dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: Dict[str, float]
    programs: Dict[str, float]
    kernel_s: Dict[str, float]
    idle: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def latest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_name(text: str) -> str:
    """An operation's instruction name: a TPU trace names each operation
    by its whole HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read the host spans and the device lines of one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Event] = []
    devices: Dict[str, Device] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops += [Event(op_name(e.name), e.start_ns, e.end_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules += [Event(e.name, e.start_ns, e.end_ns)
                                    for e in line.events]
    wins = [e for e in host if e.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span in the trace")
    w = max(wins, key=lambda e: e.dur)
    return Trace((w.start, w.end), host, devices)


def _clip(evs: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, t in busy:
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def leaves(evs: List[Event]) -> List[Event]:
    """The operations that hold no other: a control-flow operation (a
    loop over layers) spans the operations it runs on the same line."""
    evs = sorted(evs, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1].start >= e.end]


def _span_at(host: List[Event], t: float) -> str:
    """The innermost bench.* span (other than the window) open at t."""
    best = None
    for e in host:
        if e.name != WINDOW_SPAN and e.start <= t < e.end:
            if best is None or e.dur < best.dur:
                best = e
    return best.name if best else "host (no bench span)"


def reduce(tr: Trace, kernels: Dict[str, str]) -> Summary:
    """``kernels``: label -> substring that names the kernel's
    operations.  A program counts under ``<program> [<label>]`` when an
    operation of that kernel ran inside it."""
    lo, hi = tr.window
    n = max(1, len(tr.devices))
    busy = 0.0
    ops: Dict[str, float] = {}
    programs: Dict[str, float] = {}
    kernel_s = {k: 0.0 for k in kernels}
    idle: Dict[str, float] = {}
    for dev in tr.devices.values():
        dops = _clip(dev.ops, lo, hi)
        merged = union([(e.start, e.end) for e in dops])
        busy += sum(t - s for s, t in merged)
        for e in leaves(dops):
            ops[e.name] = ops.get(e.name, 0.0) + e.dur / 1e9
            for k, pat in kernels.items():
                if pat in e.name:
                    kernel_s[k] += e.dur / 1e9
        kstarts = sorted(e.start for e in dops
                         if any(p in e.name for p in kernels.values()))
        for m in _clip(dev.modules, lo, hi):
            tags = [k for k, p in kernels.items() if _any_in(kstarts, m)]
            key = m.name + (f" [{','.join(tags)}]" if tags else "")
            programs[key] = programs.get(key, 0.0) + m.dur / 1e9
        for s, t in gaps(merged, lo, hi):
            name = _span_at(tr.host, (s + t) / 2)
            idle[name] = idle.get(name, 0.0) + (t - s) / 1e9
    scale = 1.0 / n
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 * scale,
        ops={k: v * scale for k, v in ops.items()},
        programs={k: v * scale for k, v in programs.items()},
        kernel_s={k: v * scale for k, v in kernel_s.items()},
        idle={k: v * scale for k, v in idle.items()})


def _any_in(starts: List[float], m: Event) -> bool:
    import bisect
    i = bisect.bisect_left(starts, m.start)
    return i < len(starts) and starts[i] < m.end


def breakdown(s: Summary, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device operations that
    took most time, and idle time by what the host was doing."""
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(s.idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
