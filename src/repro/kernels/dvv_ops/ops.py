"""Jitted public wrappers over the DVV Pallas kernels.

On a TPU backend the compiled kernels run.  On the CPU backend — the test
path — they run in interpret mode (the kernel body executes as ordinary
XLA ops, correct but slow).  Any other backend is an error: there is no
quiet fallback from the device path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from ... import trace
from ...core.batched import BucketedSyncMask, bucket_shape, pad_sync_args
from .dvv_ops import dvv_leq_pallas, dvv_read_sweep_pallas, \
    dvv_sync_mask_pallas


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"DVV kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} is neither")


def dvv_leq(vx, ix, nx, vy, iy, ny):
    """Batched history-inclusion: bool[N]."""
    return dvv_leq_pallas(vx, ix, nx, vy, iy, ny, interpret=_interpret())


def dvv_sync_mask(vvs, dot_ids, dot_ns, valid):
    """Fused per-key survival sweep: bool[N, K] (see dvv_sync_mask_pallas).

    Drop-in for ``core.batched.sync_mask`` — this is the ``mask_fn`` the
    packed store's bulk anti-entropy hands its grouped clock tensor to.
    """
    return dvv_sync_mask_pallas(jnp.asarray(vvs), jnp.asarray(dot_ids),
                                jnp.asarray(dot_ns), jnp.asarray(valid),
                                interpret=_interpret())


#: Shape-bucketed front end over the fused kernel: pads [N, K, R] to the
#: power-of-two bucket (core.batched.bucket_shape) so every delta round —
#: whatever its size — reuses one of a handful of warm compilations instead
#: of re-tracing ``pallas_call`` at a fresh shape.  Pad rows are invalid and
#: provably inert (tests/test_delta_sync.py).  ``jit=False``: the pallas
#: wrapper is already jitted; bucketing is what makes its cache hit.
dvv_sync_mask_bucketed = BucketedSyncMask(dvv_sync_mask, jit=False)


def dvv_read_sweep(vvs, dot_ids, dot_ns, valid):
    """Fused quorum-read sweep: survival + per-key §5.4 ceiling, one pass.

    The read plane's device-side primitive: the fused Pallas survival
    kernel produces the mask, and the ceiling ⌈S⌉ of each key's *surviving*
    rows falls out of the same resident tensor via ``merge_context`` (a
    masked column max with the dots folded in) in the same jitted program
    (``dvv_read_sweep_pallas``) — no second gather of the clock rows.
    Returns ``(mask bool[N, K], ceil int32[N, R])``; semantics equal
    ``core.batched.sync_mask_np`` + ``grouped_ceiling_np`` over the
    surviving rows (conformance-tested in tests/test_read_path.py).
    Production reads enter through ``dvv_read_sweep_bucketed`` below.
    """
    return dvv_read_sweep_pallas(jnp.asarray(vvs), jnp.asarray(dot_ids),
                                 jnp.asarray(dot_ns), jnp.asarray(valid),
                                 interpret=_interpret())


class BucketedReadSweep:
    """Shape-bucketed front end over ``dvv_read_sweep`` — the §6.4 cache
    trick applied to the read plane.  Quorum merges arrive as arbitrary
    small ``[N, K, R]`` tensors; padding to the power-of-two bucket keeps
    the pallas survival kernel's compilation cache warm across all of
    them.  Pad rows are invalid (inert for both mask and ceiling — an
    invalid row contributes nothing to ``merge_context``) and pad replica
    columns come back as zero ceilings, sliced off on return.  This is
    the ``sweep_fn`` that ``KVCluster.get_many(use_kernel=True)`` hands
    ``quorum_merge_many``."""

    def __init__(self):
        self._seen: set = set()
        self.hits = 0
        self.misses = 0

    def __call__(self, vvs, dot_ids, dot_ns, valid):
        vvs = np.asarray(vvs)
        dot_ids = np.asarray(dot_ids)
        dot_ns = np.asarray(dot_ns)
        valid = np.asarray(valid)
        N, K, R = vvs.shape
        if N == 0 or K == 0:
            return np.zeros((N, K), bool), np.zeros((N, R), np.int64)
        key = bucket_shape(N, K, R)
        if key in self._seen:
            self.hits += 1
            name = trace.KERNEL_FRONT
        else:
            self.misses += 1
            self._seen.add(key)
            name = trace.KERNEL_FRONT_COLD
        with trace.span(name):
            with trace.span(trace.KERNEL_PAD):
                args = pad_sync_args(vvs, dot_ids, dot_ns, valid, key)
            with trace.span(trace.KERNEL_DISPATCH):
                mask, ceil = dvv_read_sweep(*args)
            with trace.span(trace.KERNEL_FETCH):
                mask, ceil = np.asarray(mask), np.asarray(ceil)
            return mask[:N, :K], ceil[:N, :R].astype(np.int64)

    def cache_info(self) -> dict:
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": round(self.hits / total, 4) if total else 0.0,
                "buckets": sorted(self._seen)}


#: Module-level instance (one shared bucket cache, like
#: ``dvv_sync_mask_bucketed``).
dvv_read_sweep_bucketed = BucketedReadSweep()


def dvv_dominates(vx, ix, nx, vy, iy, ny):
    """x dominates y ⟺ y ≤ x."""
    return dvv_leq(vy, iy, ny, vx, ix, nx)


def dvv_concurrent(vx, ix, nx, vy, iy, ny):
    a = dvv_leq(vx, ix, nx, vy, iy, ny)
    b = dvv_leq(vy, iy, ny, vx, ix, nx)
    return ~a & ~b


def antientropy_obsolete(vx, ix, nx, vy, iy, ny):
    """Anti-entropy sweep primitive: for each key k, is the local version
    x_k *strictly dominated* by the incoming y_k (and hence discardable)?
    Strict: x ≤ y ∧ ¬(y ≤ x)."""
    le = dvv_leq(vx, ix, nx, vy, iy, ny)
    ge = dvv_leq(vy, iy, ny, vx, ix, nx)
    return le & ~ge
