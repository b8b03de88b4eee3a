"""DVV Pallas kernel sweeps: shapes vs the pure-jnp and numpy reference
oracles (interpret=True executes the kernel bodies on CPU)."""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DVV
from repro.core import batched as B
from repro.kernels.dvv_ops import (
    dvv_concurrent, dvv_dominates, dvv_leq, dvv_sync_mask,
)
from repro.kernels.dvv_ops.ref import concurrent_ref, leq_ref, sync_mask_ref


# ---------------------------------------------------------------------------
# dvv_ops
# ---------------------------------------------------------------------------

def _rand_clock(rng, universe):
    comps = []
    for r in universe:
        if rng.random() < 0.6:
            m = rng.randint(0, 6)
            if m > 0:
                comps.append([r, m, 0])
    if comps and rng.random() < 0.7:
        i = rng.randrange(len(comps))
        comps[i][2] = comps[i][1] + rng.randint(1, 3)
    return DVV(tuple(tuple(c) for c in comps if c[1] > 0 or c[2] > 0))


@pytest.mark.parametrize("n_replicas", [1, 3, 5, 9])
@pytest.mark.parametrize("n", [1, 17, 300])
def test_dvv_leq_kernel_sweep(n_replicas, n):
    rng = random.Random(n_replicas * 1000 + n)
    universe = [f"r{i}" for i in range(n_replicas)]
    xs = [_rand_clock(rng, universe) for _ in range(n)]
    ys = [_rand_clock(rng, universe) for _ in range(n)]
    vx, ix, nx = B.encode_batch(xs, universe)
    vy, iy, ny = B.encode_batch(ys, universe)
    args = [jnp.asarray(a) for a in (vx, ix, nx, vy, iy, ny)]
    got = np.asarray(dvv_leq(*args))
    ref = np.asarray(leq_ref(*args))
    pure = np.array([x.leq(y) for x, y in zip(xs, ys)])
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got, pure)


def test_dvv_concurrent_and_dominates_consistency():
    rng = random.Random(0)
    universe = ["a", "b", "c"]
    xs = [_rand_clock(rng, universe) for _ in range(200)]
    ys = [_rand_clock(rng, universe) for _ in range(200)]
    vx, ix, nx = B.encode_batch(xs, universe)
    vy, iy, ny = B.encode_batch(ys, universe)
    args = [jnp.asarray(a) for a in (vx, ix, nx, vy, iy, ny)]
    conc = np.asarray(dvv_concurrent(*args))
    ref = np.asarray(concurrent_ref(*args))
    np.testing.assert_array_equal(conc, ref)
    dom = np.asarray(dvv_dominates(*args))
    pure_dom = np.array([x.dominates(y) for x, y in zip(xs, ys)])
    np.testing.assert_array_equal(dom, pure_dom)


@pytest.mark.parametrize("n_replicas", [1, 3, 9])
@pytest.mark.parametrize("n_keys,max_versions", [
    (1, 1), (19, 4), (150, 6), (700, 2), (530, 8), (150, 16)])
def test_dvv_sync_mask_fused_kernel_sweep(n_replicas, n_keys, max_versions):
    """The fused pairwise-dominance kernel equals the jnp sync_mask
    reference on randomized per-key clock sets (incl. invalid padding).
    The last three cases cover K = 2, 8, 16 with N not a multiple of the
    key block, across one and several grid steps."""
    rng = random.Random(n_replicas * 7919 + n_keys + max_versions)
    universe = [f"r{i}" for i in range(n_replicas)]
    vvs = np.zeros((n_keys, max_versions, n_replicas), np.int32)
    dids = np.full((n_keys, max_versions), B.NO_DOT, np.int32)
    dns = np.zeros((n_keys, max_versions), np.int32)
    valid = np.zeros((n_keys, max_versions), bool)
    for i in range(n_keys):
        for j in range(rng.randint(0, max_versions)):
            clock = _rand_clock(rng, universe)
            vvs[i, j], dids[i, j], dns[i, j] = B.encode(clock, universe)
            valid[i, j] = True
    args = [jnp.asarray(a) for a in (vvs, dids, dns, valid)]
    got = np.asarray(dvv_sync_mask(*args))
    ref = np.asarray(sync_mask_ref(*args))
    np.testing.assert_array_equal(got, ref)
    np_ref = B.sync_mask_np(vvs, dids, dns, valid)
    np.testing.assert_array_equal(got, np_ref)


def _wide_clock_sets(seed, n, k, r):
    """Seeded ``[n, k, r]`` clock sets with every kind of slot: fresh
    clocks (a dot on half of them), exact copies of an earlier slot,
    clocks dominated by an earlier slot (lowered ranges, no dot), and
    invalid slots that still hold clocks."""
    rng = np.random.default_rng(seed)
    vvs = np.zeros((n, k, r), np.int32)
    dids = np.full((n, k), B.NO_DOT, np.int32)
    dns = np.zeros((n, k), np.int32)
    for i in range(n):
        for j in range(k):
            kind = rng.random() if j else 1.0
            if kind < 0.25:                                 # equal
                src = rng.integers(j)
                vvs[i, j], dids[i, j], dns[i, j] = \
                    vvs[i, src], dids[i, src], dns[i, src]
            elif kind < 0.5:                                # dominated
                src = rng.integers(j)
                vvs[i, j] = rng.integers(0, vvs[i, src] + 1)
            else:                                           # fresh
                vvs[i, j] = rng.integers(0, 6, r)
                if rng.random() < 0.5:
                    dids[i, j] = rng.integers(r)
                    dns[i, j] = vvs[i, j, dids[i, j]] + rng.integers(1, 4)
    valid = rng.random((n, k)) < 0.85
    return vvs, dids, dns, valid


@pytest.mark.parametrize("kernel", ["sync_mask", "read_sweep"])
@pytest.mark.parametrize("r", [3, 8])
@pytest.mark.parametrize("k", [33, 64, 100, 128])
def test_dvv_survival_kernel_wide_sets_equal_numpy(kernel, k, r):
    """Past 32 slots a key (a hot cart's siblings, a quorum read's merged
    versions) still gets exactly the numpy reference's survivors, and the
    read sweep exactly the survivors' ceilings."""
    from repro.kernels.dvv_ops.dvv_ops import dvv_read_sweep_pallas, \
        dvv_sync_mask_pallas

    n = 24
    vvs, dids, dns, valid = _wide_clock_sets(k * 10 + r, n, k, r)
    want = B.sync_mask_np(vvs, dids, dns, valid)
    assert 0 < want.sum() < valid.sum()       # some slots are dominated
    args = [jnp.asarray(a) for a in (vvs, dids, dns, valid)]
    if kernel == "sync_mask":
        got = np.asarray(dvv_sync_mask_pallas(*args, interpret=True))
    else:
        got, ceil = dvv_read_sweep_pallas(*args, interpret=True)
        got, ceil = np.asarray(got), np.asarray(ceil)
        rows = np.repeat(np.arange(n), k)[want.reshape(-1)]
        want_ceil = B.grouped_ceiling_np(
            vvs.reshape(-1, r)[want.reshape(-1)],
            dids.reshape(-1)[want.reshape(-1)],
            dns.reshape(-1)[want.reshape(-1)], rows, n)
        np.testing.assert_array_equal(ceil, want_ceil)
    np.testing.assert_array_equal(got, want)
