"""Bytes and operations of the DVV kernels, from the logical shape of a call.

A call's shape is the ``[N, K, R]`` the store handed the bucket cache:
``N`` keys, ``K`` clock slots per key, ``R`` replica ids.  Padding to the
shape bucket, and the layout the kernel uses on the chip, are the
program's business, so neither enters these counts: a later change to
padding or fusion cannot make them stale.

* survival mask (``dvv_sync_mask_pallas``): reads the clocks ``int32[N,K,R]``
  (4NKR), the dot ids and counters ``int32[N,K]`` (8NK) and the valid bits
  ``bool[N,K]`` (NK), and writes the mask ``bool[N,K]`` (NK);
* read sweep (``dvv_read_sweep_pallas``): the same, plus the ceiling it
  writes, ``int32[N,R]`` (4NR).

Operations are clock comparisons: every ordered pair of a key's slots
compares ``R`` counters, ``N*K*K*R`` in all.  The kernels do int32 vector
work, for which the chip's published table gives no peak, so their
roofline is bounded by bytes alone.
"""
from __future__ import annotations

from typing import Mapping, Tuple

Shape = Tuple[int, int, int]

#: The jitted program each kernel runs as, as the profiler names it.
PROGRAMS = {"sync_mask": "dvv_sync_mask_pallas",
            "read_sweep": "dvv_read_sweep_pallas"}


def mask_bytes(n: int, k: int, r: int) -> int:
    return 4 * n * k * r + 8 * n * k + 2 * n * k


def read_sweep_bytes(n: int, k: int, r: int) -> int:
    return mask_bytes(n, k, r) + 4 * n * r


BYTES = {"sync_mask": mask_bytes, "read_sweep": read_sweep_bytes}


def comparisons(n: int, k: int, r: int) -> int:
    return n * k * k * r


def total_bytes(kind: str, shapes: Mapping[Shape, int]) -> int:
    """Bytes of every call of ``kind``; ``shapes`` counts calls by shape."""
    fn = BYTES[kind]
    return sum(fn(*s) * c for s, c in shapes.items())


def total_comparisons(shapes: Mapping[Shape, int]) -> int:
    return sum(comparisons(*s) * c for s, c in shapes.items())


def roofline_pct(kind: str, shapes: Mapping[Shape, int], kernel_s: float,
                 hbm_bytes_per_s: float) -> float:
    """Least time the chip could take for these calls' bytes, as a share
    of the time the trace measured for them."""
    return 100.0 * total_bytes(kind, shapes) / hbm_bytes_per_s / kernel_s


def window_roofline(w: Mapping, kind: str):
    """A kernel's roofline share over one traced window: bytes of every
    call of ``kind`` in it over the kernel's program time in the trace.
    ``None`` where the trace saw no time for the kernel."""
    tr, peaks = w.get("trace"), w.get("peaks")
    shapes = w["kernel_shapes"].get(kind)
    if not tr or not peaks or not shapes:
        return None
    kernel_s = tr["kernel_s"].get(kind, 0.0)
    if kernel_s <= 0:
        return None
    return roofline_pct(kind, shapes, kernel_s,
                        float(peaks["hbm_bytes_per_s"]))
