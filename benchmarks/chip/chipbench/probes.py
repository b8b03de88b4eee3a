"""Spans and counters the benchmark wraps around the program at run time.

Nothing here edits the program: the wrappers are installed on the objects
of one run from the benchmark's own files and removed after it.

* ``cluster.get_many`` / ``cluster.put_many`` — host time of the cluster
  plane (``plane_ms_per_op``), spans ``cluster.get_many`` and
  ``cluster.put_many``;
* ``OpScheduler._run_flush`` — span ``flush``;
* ``KVCluster.delta_antientropy_round`` — span ``ae.round``;
* ``KVCluster.deliver_replication`` — span ``deliver``;
* the two bucket caches behind the package names the store resolves at
  call time (``repro.kernels.dvv_ops.dvv_sync_mask_bucketed`` and
  ``dvv_read_sweep_bucketed``) — spans ``kernel.sync_mask`` and
  ``kernel.read_sweep``, and the logical ``[N, K, R]`` of every call,
  before any padding, from which the kernels' bytes are counted.

Spans go to the profiler's trace only in a traced run; the counters are
always kept.
"""
from __future__ import annotations

import contextlib
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Tuple

KERNELS = {"sync_mask": "dvv_sync_mask_bucketed",
           "read_sweep": "dvv_read_sweep_bucketed"}


class _RecordingKernel:
    """Stands in for one bucket cache: records, annotates, delegates."""

    def __init__(self, probes: "Probes", kind: str, inner: Any):
        self._probes = probes
        self._kind = kind
        self.inner = inner

    def __call__(self, vvs, dot_ids, dot_ns, valid):
        n, k, r = vvs.shape
        self._probes.kernel_shapes[self._kind][(int(n), int(k), int(r))] += 1
        self._probes.dtypes.setdefault(self._kind, tuple(
            getattr(a, "dtype", None) for a in (vvs, dot_ids, dot_ns, valid)))
        with self._probes.span(f"kernel.{self._kind}"):
            return self.inner(vvs, dot_ids, dot_ns, valid)


class Probes:
    def __init__(self, *, annotate: bool):
        self.annotate = annotate
        self.plane_s = 0.0
        self.plane_calls = 0
        self.kernel_shapes: Dict[str, Counter] = {
            kind: Counter() for kind in KERNELS}
        #: argument dtypes of each kernel's first call, for the warm-up
        self.dtypes: Dict[str, Tuple[Any, ...]] = {}
        self._undo: List[Callable[[], None]] = []

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, obj: Any, attr: str, name: str, timed: bool) -> None:
        inner = getattr(obj, attr)

        def wrapper(*args, **kwargs):
            with self.span(name):
                if not timed:
                    return inner(*args, **kwargs)
                t = time.perf_counter()
                try:
                    return inner(*args, **kwargs)
                finally:
                    self.plane_s += time.perf_counter() - t
                    self.plane_calls += 1

        setattr(obj, attr, wrapper)
        self._undo.append(lambda: delattr(obj, attr))

    def install_cluster(self, cluster) -> None:
        self._wrap(cluster, "get_many", "cluster.get_many", True)
        self._wrap(cluster, "put_many", "cluster.put_many", True)
        self._wrap(cluster, "delta_antientropy_round", "ae.round", False)
        self._wrap(cluster, "deliver_replication", "deliver", False)

    def install_scheduler(self, scheduler) -> None:
        self._wrap(scheduler, "_run_flush", "flush", False)

    def install_kernels(self) -> None:
        import repro.kernels.dvv_ops as pkg
        for kind, attr in KERNELS.items():
            inner = getattr(pkg, attr)
            setattr(pkg, attr, _RecordingKernel(self, kind, inner))
            self._undo.append(
                lambda attr=attr, inner=inner: setattr(pkg, attr, inner))

    def caches(self) -> Dict[str, Any]:
        """The bucket caches the store is calling, by kernel."""
        import repro.kernels.dvv_ops as pkg
        out = {}
        for kind, attr in KERNELS.items():
            obj = getattr(pkg, attr)
            out[kind] = getattr(obj, "inner", obj)
        return out

    def counters(self) -> Dict[str, Any]:
        """A snapshot; window values are differences of two of them."""
        caches = self.caches()
        return {
            "plane_s": self.plane_s,
            "plane_calls": self.plane_calls,
            "kernel_hits": {k: c.hits for k, c in caches.items()},
            "kernel_misses": {k: c.misses for k, c in caches.items()},
            "kernel_shapes": {k: Counter(v)
                              for k, v in self.kernel_shapes.items()},
        }

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()


def window_delta(before: Dict[str, Any], after: Dict[str, Any]
                 ) -> Dict[str, Any]:
    return {
        "plane_s": after["plane_s"] - before["plane_s"],
        "plane_calls": after["plane_calls"] - before["plane_calls"],
        "kernel_hits": {k: after["kernel_hits"][k] - before["kernel_hits"][k]
                        for k in after["kernel_hits"]},
        "kernel_misses": {k: after["kernel_misses"][k]
                          - before["kernel_misses"][k]
                          for k in after["kernel_misses"]},
        "kernel_shapes": {k: after["kernel_shapes"][k]
                          - before["kernel_shapes"][k]
                          for k in after["kernel_shapes"]},
    }
