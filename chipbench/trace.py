"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

One device plane per chip (``/device:TPU:<i>``). On each, the ``XLA Ops``
line holds one event per executed operation and ``XLA Modules`` one per
executed program. The traced window is the host annotation
``chipbench.window`` that the job wraps around it, so device and host
events are read on one clock:

* busy seconds: the union of op intervals inside the window (nested ops
  count once), averaged over the chips used;
* module seconds: summed durations of the programs whose name contains a
  given part (``fit_one``, ``_solve_all_classes``), averaged over chips;
* collective seconds: summed durations of all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all ops, averaged over chips;
* the breakdown: the ten operations that took most time, and the ten
  longest idle gaps of chip 0, each named by the host event that best
  covers it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
# control-flow ops span the ops they run; leave them out of the op ranking
_CONTAINERS = re.compile(r"^(while|conditional|call)\b")

Interval = Tuple[int, int]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class Device:
    name: str
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Tuple[str, int, int]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[str, int, int]]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, dev: Device) -> List[Interval]:
        return union(clip([(a, b) for _, a, b in dev.ops], *self.window))

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self._busy(d))
                   for d in self.devices) * 1e-9 / len(self.devices)

    def module_s(self, part: str) -> float:
        """Mean over chips of the seconds spent in programs named ``part``."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for n, a, b in d.modules if part in n)
                   for d in self.devices) * 1e-9 / len(self.devices)

    def module_count(self, part: str) -> int:
        return max((sum(1 for n, _, _ in d.modules if part in n)
                    for d in self.devices), default=0)

    @property
    def collective_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(sum(b - a for n, a, b in d.ops if COLLECTIVE.search(n))
                   for d in self.devices) * 1e-9 / len(self.devices)

    def top_ops(self, k: int = 10) -> List[list]:
        tot: Dict[str, int] = {}
        for d in self.devices:
            for n, a, b in d.ops:
                if not _CONTAINERS.match(n):
                    tot[n] = tot.get(n, 0) + (b - a)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns * 1e-9 / len(self.devices)] for n, ns in ranked]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps of chip 0 in the window, each named
        by the host event that best covers it: the most overlap, weighted
        by the share of the event inside the gap (overlap**2 / duration),
        so an enclosing loop does not outrank the dispatch inside it."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self._busy(self.devices[0])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            best, name = 0.0, "host: no event"
            for n, s, e in self.host:
                ov = min(b, e) - max(a, s)
                if ov > 0 and n != WINDOW and ov * ov / (e - s) > best:
                    best, name = ov * ov / (e - s), n
            out.append([name, (b - a) * 1e-9])
        return out


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction's name, as the device trace spells each op in full."""
    if text.startswith("%") and " = " in text:
        return text[1:text.index(" = ")]
    return text


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Read one ``.xplane.pb``. Devices are the planes named
    ``device_prefix<i>``; host events are every event of the host planes.
    The window is the ``chipbench.window`` annotation (else the span of all
    device events)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[Device] = []
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            dev = Device(plane.name)
            for line in plane.lines:
                target = {"XLA Ops": dev.ops,
                          "XLA Modules": dev.modules}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    target.append((op_name(ev.name), s,
                                   s + int(ev.duration_ns)))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    host.append((ev.name, s, s + int(ev.duration_ns)))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[-1]))
    marks = [(s, e) for n, s, e in host if n == WINDOW]
    if marks:
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    else:
        spans = [(a, b) for d in devices for _, a, b in d.ops]
        window = ((min(a for a, _ in spans), max(b for _, b in spans))
                  if spans else (0, 0))
    return Trace(devices, host, window)
