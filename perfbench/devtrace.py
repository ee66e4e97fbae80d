"""Reduce a profiler trace of the chip-holding worker to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, with device
tracing alone (see ``hook/sitecustomize.py``).  Each device is a plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation that
ran, and its line ``XLA Modules`` one per program run.  The traced window
starts and ends with the worker idle, so every device event lies inside it;
its length is taken on the host clock by the hook.

- busy: the union of a device's operation intervals; the idle share is
  1 - busy / window, averaged over the devices used;
- operation time: the summed durations of one operation, named by the
  program it ran in and its HLO instruction (``jit_matmul/fusion.5``);
- idle gaps: the intervals between busy intervals, each named by the
  programs on the device before and after it (``jit_add -> jit_matmul``:
  the device waited between two programs; what the worker's host did then
  is not in the trace).
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclass
class Device:
    name: str
    ops: List[Tuple[int, int, str]] = field(default_factory=list)
    modules: List[Tuple[int, int, str]] = field(default_factory=list)


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def read(path: Path) -> List[Device]:
    """The device planes of one trace file, in device order."""
    from jax.profiler import ProfileData

    devices = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not _DEVICE.match(plane.name):
            continue
        dev = Device(plane.name)
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                events = sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                                for ev in line.events)
                if line.name == OPS_LINE:
                    dev.ops = events
                else:
                    dev.modules = events
        devices.append(dev)
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return devices


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals: Sequence[Interval]) -> int:
    """Length of the union of intervals."""
    return sum(e - s for s, e in merge(intervals))


def op_names(dev: Device) -> List[str]:
    """Each operation of ``dev`` named ``<program>/<instruction>``: the
    program whose run contains its start, and the HLO instruction's name."""
    starts = [m[0] for m in dev.modules]
    out = []
    for s, _, text in dev.ops:
        inst = text.split(" = ", 1)[0].lstrip("%").strip()
        i = bisect.bisect_right(starts, s) - 1
        prog = "?"
        if i >= 0 and dev.modules[i][0] <= s < dev.modules[i][1]:
            prog = dev.modules[i][2].split("(", 1)[0]
        out.append(f"{prog}/{inst}")
    return out


def gaps(ops: Sequence[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """(label, ns) of every idle interval between busy intervals; ``ops``
    are (start, end, ``program/instruction``) sorted by start, and the label
    names the programs before and after the gap."""
    out = []
    end, before = None, None
    for s, e, name in ops:
        prog = name.split("/", 1)[0]
        if end is not None and s > end:
            out.append((f"{before} -> {prog}", s - end))
        if end is None or e >= end:
            end, before = e, prog
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: List[float]  # per device
    op_s: Dict[str, float]  # per operation name, summed over devices
    gaps: List[Tuple[str, float]]  # longest idle gaps first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def reduce(devices: Sequence[Device], window_ns: int,
           used: Optional[Sequence[int]] = None,
           top_gaps: int = 10) -> Reduction:
    """Device metrics over a traced window of ``window_ns``; ``used`` lists
    the indices of the devices that count (default all)."""
    devs = [d for i, d in enumerate(devices) if used is None or i in used]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy, op_s, all_gaps = [], {}, []
    for d in devs:
        busy.append(busy_ns([(s, e) for s, e, _ in d.ops]) / 1e9)
        named = [(s, e, n) for (s, e, _), n in zip(d.ops, op_names(d))]
        for s, e, name in named:
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        all_gaps += gaps(named)
    all_gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=window_ns / 1e9, busy_s=busy, op_s=op_s,
                     gaps=[(n, ns / 1e9) for n, ns in all_gaps[:top_gaps]])

