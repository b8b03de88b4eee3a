"""Device idle time of a traced window, charged to the program's own spans.

``trace_reduce`` charges each idle gap of the device to the innermost of
the harness's annotations over the gap's middle.  This is a second charge
of the same gaps, to the spans the program itself wrote
(``repro.trace.NAMES``), and it splits each gap at span boundaries: every
instant of it goes to the innermost program span open then, or to
``UNCOVERED`` where none is.  A gap on the served path lasts several
milliseconds and holds many spans, so its middle alone would name one of
them for all of it.  The window, the device planes and the busy time are
read as ``trace_reduce`` reads them; nothing of its result changes.

``traced_run`` is one traced run of a cell with both charges; the script
``program_spans.py`` beside ``run.py`` prints it.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Collection, Dict, List, Optional, Sequence, Tuple

from .trace_reduce import Interval, clip, gaps, union

#: The charge of idle time that falls under no program span.
UNCOVERED = "(no program span)"

Segment = Tuple[int, int, Optional[str]]


def innermost_segments(spans: Sequence[Tuple[int, int, str]], lo: int,
                       hi: int) -> List[Segment]:
    """``[lo, hi)`` cut into ``(start, end, name)`` pieces, ``name`` the
    innermost of the nested ``(start, end, name)`` spans open there
    (``None`` where none is)."""
    out: List[Segment] = []
    stack: List[Tuple[int, str]] = []
    t = lo

    def emit(upto: int) -> None:
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack else None))
            t = upto

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return out


def idle_by_program_span(planes, names: Collection[str]
                         ) -> Dict[str, object]:
    """``idle_by_program_span``: ``[[span, s], ...]`` largest first, with
    ``UNCOVERED`` among them; ``idle_s``, the device's idle seconds in the
    window; and ``uncovered_share``, the share of them under no program
    span (``None`` where the device was never idle)."""
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Interval]] = defaultdict(list)
    spans: List[Tuple[int, int, str]] = []
    window: List[Interval] = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is not None:
                    into[plane.name] += [
                        (int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    if e.name == "window":
                        window.append((s, s + int(e.duration_ns)))
                    elif e.name in names:
                        spans.append((s, s + int(e.duration_ns), e.name))
    if not window:
        raise ValueError("trace holds no window annotation")
    lo = min(s for s, _ in window)
    hi = max(e for _, e in window)
    segments = innermost_segments(spans, lo, hi)
    idle: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for dev, evs in ops.items():
        if not evs:
            continue
        n_dev += 1
        i = 0
        for s, e in gaps(union(clip(evs + modules[dev], lo, hi)), lo, hi):
            while segments[i][1] <= s:
                i += 1
            j = i
            while j < len(segments) and segments[j][0] < e:
                a, b, name = segments[j]
                idle[name or UNCOVERED] += (min(b, e) - max(a, s)) / 1e9
                j += 1
    n_dev = max(1, n_dev)
    idle_s = sum(idle.values()) / n_dev
    return {
        "idle_by_program_span": [[n, s / n_dev] for n, s in sorted(
            idle.items(), key=lambda x: -x[1])],
        "idle_s": idle_s,
        "uncovered_share": (idle.get(UNCOVERED, 0.0) / n_dev / idle_s
                            if idle_s else None),
    }


def traced_run(bench, workload: str, seed: int, seconds: float,
               **run_cell_kw) -> Tuple[Dict[str, object], Dict[str, object]]:
    """One traced run of a cell (``run_cell`` with ``trace=True``), whose
    profile is reduced a second time, before the harness removes it, with
    the idle gaps charged to the program's spans.  Returns the run's result
    and ``{"idle_by_program_span", "idle_s", "uncovered_share",
    "program"}``, where ``program`` is the program's span and counter
    table over the window."""
    import jax
    from repro import trace

    from . import trace_reduce
    from .harness import run_cell

    seen: Dict[str, object] = {}
    before = trace.snapshot()
    reduce = trace_reduce.reduce_trace

    def reduce_twice(path):
        out = reduce(path)
        files = sorted(Path(path).glob("**/*.xplane.pb"))
        planes = jax.profiler.ProfileData.from_file(str(files[0])).planes
        seen.update(idle_by_program_span(planes, trace.NAMES))
        seen["program"] = trace.delta(before, trace.snapshot())
        return out

    trace_reduce.reduce_trace = reduce_twice
    try:
        result = run_cell(bench, workload, seed, seconds, True,
                          **run_cell_kw)
    finally:
        trace_reduce.reduce_trace = reduce
    return result, seen
