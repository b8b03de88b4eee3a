"""From a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with JAX alone:

* the device planes are those named ``/device:TPU:<n>``; the device is
  busy while an event of its ``XLA Modules`` line (a program) or of its
  ``XLA Ops`` line (an operation in it) lasts;
* busy time is the union of those intervals inside the window, averaged
  over the devices that ran anything;
* a kernel's time is the summed duration of the ``XLA Modules`` events of
  the jitted program it runs as (``kernel_cost.PROGRAMS``);
* the window is the harness's own ``window`` annotation on the host;
* each idle gap of the device is charged to the innermost host annotation
  of the harness that covers the middle of the gap (``host`` where none
  does), and the charges are summed by name.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from .kernel_cost import PROGRAMS

Interval = Tuple[int, int]

#: Host annotations the harness writes, innermost first when they nest.
ANNOTATIONS = ("kernel.read_sweep", "kernel.sync_mask", "cluster.get_many",
               "cluster.put_many", "deliver", "ae.round", "flush", "window")
TOP = 10


def op_kind(hlo: str) -> str:
    """``%copy-start.2 = (s32[...]) copy-start(...)`` -> ``copy-start``: the
    instruction's name without its HLO text and numeric suffix."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    stem, _, suffix = name.rpartition(".")
    return stem if stem and suffix.isdigit() else name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _Spans:
    """Host annotations by name, each list sorted and non-overlapping (one
    thread writes them, and those of one name do not nest)."""

    def __init__(self, spans: Dict[str, List[Interval]]):
        self._by = {n: sorted(v) for n, v in spans.items()}
        self._starts = {n: [s for s, _ in v] for n, v in self._by.items()}

    def innermost(self, t: int) -> str:
        best: Optional[Tuple[int, str]] = None
        for name, starts in self._starts.items():
            i = bisect_right(starts, t) - 1
            if i >= 0:
                s, e = self._by[name][i]
                if e >= t and (best is None or e - s < best[0]):
                    best = (e - s, name)
        return best[1] if best else "host"


def reduce_planes(planes) -> Dict[str, object]:
    """The reduction over already-loaded planes (``ProfileData.planes``)."""
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    modules: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.start_ns
                                                     + e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        s = int(e.start_ns)
                        spans[e.name].append((s, s + int(e.duration_ns)))
    if not spans.get("window"):
        raise ValueError("trace holds no window annotation")
    lo = min(s for s, _ in spans["window"])
    hi = max(e for _, e in spans["window"])
    window_ns = hi - lo
    host = _Spans({n: v for n, v in spans.items() if n != "window"})

    busy_ns: List[int] = []
    idle: Dict[str, float] = defaultdict(float)
    op_time: Dict[str, float] = defaultdict(float)
    for dev, evs in ops.items():
        if not evs:
            continue
        busy = union(clip([(s, e) for _, s, e in evs + modules.get(dev, [])],
                          lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in evs:
            if e > lo and s < hi:
                op_time[op_kind(name)] += (min(e, hi) - max(s, lo)) / 1e9
        for s, e in gaps(busy, lo, hi):
            idle[host.innermost((s + e) // 2)] += (e - s) / 1e9
    n_dev = max(1, len(busy_ns))
    kernel_s: Dict[str, float] = {}
    for kind, program in PROGRAMS.items():
        total = 0
        for evs in modules.values():
            total += sum(min(e, hi) - max(s, lo) for name, s, e in evs
                         if program in name and e > lo and s < hi)
        kernel_s[kind] = total / 1e9 / n_dev
    return {
        "busy_s": sum(busy_ns) / 1e9 / n_dev,
        "window_s": window_ns / 1e9,
        "devices": len(busy_ns),
        "kernel_s": kernel_s,
        "breakdown": {
            "device_ops": [[n, s / n_dev] for n, s in sorted(
                op_time.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[n, s / n_dev] for n, s in sorted(
                idle.items(), key=lambda x: -x[1])[:TOP]],
        },
    }


def reduce_trace(path: Path) -> Dict[str, object]:
    """Reduce the one ``.xplane.pb`` under ``path`` (a directory the
    profiler wrote, or the file itself)."""
    import jax
    path = Path(path)
    files = [path] if path.is_file() else sorted(path.glob("**/*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one trace under {path}, found "
                         f"{len(files)}")
    return reduce_planes(jax.profiler.ProfileData.from_file(
        str(files[0])).planes)
