"""Program spans and counters, on the profiler's clock.

The store marks each phase of its served and repair paths with a named
span, and counts a few quantities that have no span of their own:

    from repro import trace

    with trace.span(trace.PACKED_GATHER):
        ...
    trace.count(trace.SCHED_OPS_FLUSHED, len(ops))

Tracing is **on** while ``enable()`` is in force or while a JAX profile
is being taken (``jax.profiler.start_trace`` ... ``stop_trace``), and
**off** otherwise, which is the default:

* off, ``span`` returns one shared no-op after a flag check and the
  profiler's own is-tracing check; no clock is read and nothing is
  allocated; ``count`` returns at once;
* on, a span enters ``jax.profiler.TraceAnnotation``, so it lands on the
  profile's host plane on the same clock as the ``/device:TPU:<n>`` planes
  (each device idle gap can be put down to the innermost span that covers
  it), and adds its call, total ns and self ns (total less the time of its
  child spans) to an in-memory table keyed by name.  ``count`` adds to the
  same table.

The table is read with ``snapshot()``; ``delta(a, b)`` is what happened
between two snapshots, and ``reset()`` empties it.  Spans are recorded as
totals, never one by one.  Spans mark phases (one per batch, group,
message or pass), never one per key.  Spans entered during an
``OpScheduler`` flush carry that flush's sequence number as the
annotation's ``flush`` metadata, so one flush's spans share an identifier
on the trace.

The store's paths run on one thread, and the self-time stack assumes so.

Every span and counter name is a constant below, listed in ``NAMES`` with
the layer it belongs to and the per-layer metric of the chip benchmark
that reads it ("trace only" where none does).
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from jax.profiler import TraceAnnotation

# -- names ------------------------------------------------------------------

SCHED_FLUSH = "sched.flush"
SCHED_ADMIT = "sched.admit"
SCHED_PLAN = "sched.plan"
SCHED_COMPLETE = "sched.complete"
SCHED_QUEUE_WAIT_NS = "sched.queue_wait_ns"
SCHED_OPS_FLUSHED = "sched.ops_flushed"
CODEC_ENCODE = "codec.encode"
CODEC_DECODE = "codec.decode"
PLANE_GET_ADMIT = "plane.get.admit"
PLANE_GET_RESULT = "plane.get.result"
PLANE_GET_REPAIR = "plane.get.repair"
PLANE_PUT_ADMIT = "plane.put.admit"
PLANE_PUT_UPDATE = "plane.put.update"
PLANE_PUT_REPLICATE = "plane.put.replicate"
PLANE_STACK_TENSORS = "plane.stack.tensors"
PLANE_STACK_LAUNCHES = "plane.stack.launches"
PLANE_STACK_CELLS = "plane.stack.cells"
PLANE_STACK_LAUNCHED_CELLS = "plane.stack.launched_cells"
PLANE_STACK_COMPARISONS = "plane.stack.comparisons"
PACKED_GATHER = "packed.gather"
PACKED_MASK = "packed.mask"
PACKED_CEILING = "packed.ceiling"
PACKED_SCATTER = "packed.scatter"
KERNEL_FRONT = "kernel.front"
KERNEL_FRONT_COLD = "kernel.front.cold"
KERNEL_PAD = "kernel.pad"
KERNEL_DISPATCH = "kernel.dispatch"
KERNEL_FETCH = "kernel.fetch"
NET_DELIVER_SCAN = "net.deliver.scan"
NET_APPLY = "net.apply"
AE_DIGEST = "ae.digest"
AE_PAYLOAD = "ae.payload"
AE_APPLY = "ae.apply"
AE_STACK_PAYLOADS = "ae.stack.payloads"
AE_STACK_LAUNCHES = "ae.stack.launches"
REPLICA_APPLY_PARTS = "replica.apply.parts"
REPLICA_APPLY_STORES = "replica.apply.stores"

_TRACE_ONLY = "trace only"
#: name -> (kind, layer, per-layer metric that reads it).
NAMES: Dict[str, Tuple[str, str, str]] = {
    SCHED_FLUSH: ("span", "serving plane", _TRACE_ONLY),
    SCHED_ADMIT: ("span", "serving plane", _TRACE_ONLY),
    SCHED_PLAN: ("span", "serving plane", _TRACE_ONLY),
    SCHED_COMPLETE: ("span", "serving plane", _TRACE_ONLY),
    SCHED_QUEUE_WAIT_NS: ("counter", "serving plane",
                          "queue_wait_ms_per_op"),
    SCHED_OPS_FLUSHED: ("counter", "serving plane", "queue_wait_ms_per_op"),
    CODEC_ENCODE: ("span", "client codec", _TRACE_ONLY),
    CODEC_DECODE: ("span", "client codec", _TRACE_ONLY),
    PLANE_GET_ADMIT: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_GET_RESULT: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_GET_REPAIR: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_PUT_ADMIT: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_PUT_UPDATE: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_PUT_REPLICATE: ("span", "cluster plane", _TRACE_ONLY),
    PLANE_STACK_TENSORS: ("counter", "cluster plane", "tensors_per_launch"),
    PLANE_STACK_LAUNCHES: ("counter", "cluster plane", "tensors_per_launch"),
    PLANE_STACK_CELLS: ("counter", "cluster plane", _TRACE_ONLY),
    PLANE_STACK_LAUNCHED_CELLS: ("counter", "cluster plane", _TRACE_ONLY),
    PLANE_STACK_COMPARISONS: ("counter", "cluster plane",
                              "stack_comparison_padding"),
    PACKED_GATHER: ("span", "packed store", "gather_ms_per_op"),
    PACKED_MASK: ("span", "packed store", _TRACE_ONLY),
    PACKED_CEILING: ("span", "packed store", _TRACE_ONLY),
    PACKED_SCATTER: ("span", "packed store", _TRACE_ONLY),
    KERNEL_FRONT: ("span", "kernel front ends", "kernel_front_ms_per_call"),
    KERNEL_FRONT_COLD: ("span", "kernel front ends",
                        "kernel_front_ms_per_call"),
    KERNEL_PAD: ("span", "kernel front ends", _TRACE_ONLY),
    KERNEL_DISPATCH: ("span", "kernel front ends", _TRACE_ONLY),
    KERNEL_FETCH: ("span", "kernel front ends", _TRACE_ONLY),
    NET_DELIVER_SCAN: ("span", "simulated network", "deliver_scan_ms_per_op"),
    NET_APPLY: ("span", "simulated network", _TRACE_ONLY),
    AE_DIGEST: ("span", "anti-entropy", "ae_digest_ms_per_repaired_key"),
    AE_PAYLOAD: ("span", "anti-entropy", _TRACE_ONLY),
    AE_APPLY: ("span", "anti-entropy", "ae_apply_ms_per_repaired_key"),
    AE_STACK_PAYLOADS: ("counter", "anti-entropy", "ae_payloads_per_launch"),
    AE_STACK_LAUNCHES: ("counter", "anti-entropy", "ae_payloads_per_launch"),
    REPLICA_APPLY_PARTS: ("counter", "cluster plane",
                          "parts_per_store_apply"),
    REPLICA_APPLY_STORES: ("counter", "cluster plane",
                           "parts_per_store_apply"),
}

# -- state ------------------------------------------------------------------

_enabled = False
_profiling = TraceAnnotation.is_enabled
_clock = time.perf_counter_ns
#: name -> [calls, total ns, self ns]
_spans: Dict[str, List[int]] = {}
_counters: Dict[str, int] = {}
#: child-time accumulators of the open spans, innermost last
_stack: List[int] = []
_flush = 0


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "annotation", "t0")

    def __init__(self, name: str):
        self.name = name
        self.annotation = (TraceAnnotation(name, flush=_flush) if _flush
                           else TraceAnnotation(name))

    def __enter__(self):
        self.annotation.__enter__()
        _stack.append(0)
        self.t0 = _clock()
        return self

    def __exit__(self, *exc):
        dt = _clock() - self.t0
        child = _stack.pop()
        if _stack:
            _stack[-1] += dt
        row = _spans.get(self.name)
        if row is None:
            row = _spans[self.name] = [0, 0, 0]
        row[0] += 1
        row[1] += dt
        row[2] += dt - child
        self.annotation.__exit__(*exc)
        return False


def active() -> bool:
    """Whether spans and counters are being recorded now."""
    return _enabled or _profiling()


def span(name: str):
    """A context manager that marks one phase (see the module docstring)."""
    if _enabled or _profiling():
        return _Span(name)
    return _NO_SPAN


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while tracing is on."""
    if _enabled or _profiling():
        _counters[name] = _counters.get(name, 0) + n


def set_flush(seq: int) -> None:
    """Tag the spans that follow with flush ``seq`` (0: no flush)."""
    global _flush
    _flush = seq


def enable() -> None:
    """Record spans and counters until ``disable()``, profile or not."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Empty the table (open spans still close into it)."""
    _spans.clear()
    _counters.clear()


def snapshot() -> Dict[str, Dict[str, object]]:
    """A copy of the table: ``{"spans": {name: {"calls", "total_ns",
    "self_ns"}}, "counters": {name: n}}``."""
    return {"spans": {n: {"calls": r[0], "total_ns": r[1], "self_ns": r[2]}
                      for n, r in _spans.items()},
            "counters": dict(_counters)}


def delta(a: Dict[str, Dict[str, object]], b: Dict[str, Dict[str, object]]
          ) -> Dict[str, Dict[str, object]]:
    """What the table gained from snapshot ``a`` to the later ``b``; names
    that did not move are left out."""
    spans = {}
    for name, row in b["spans"].items():
        old = a["spans"].get(name, {})
        d = {k: v - old.get(k, 0) for k, v in row.items()}
        if d["calls"]:
            spans[name] = d
    counters = {name: n - a["counters"].get(name, 0)
                for name, n in b["counters"].items()
                if n != a["counters"].get(name, 0)}
    return {"spans": spans, "counters": counters}


__all__ = ["NAMES", "active", "span", "count", "set_flush", "enable",
           "disable", "reset", "snapshot", "delta"]
