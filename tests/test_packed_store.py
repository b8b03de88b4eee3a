"""Conformance: the array-resident packed store is observationally equal to
the object store on randomized PUT/GET/sync/partition schedules.

Twin KVClusters — one with ``packed=True`` (int32 arrays resident, the
default for DVV), one with ``packed=False`` (Python ``DVV`` objects, the
reference semantics) — execute identical schedules; after every phase all
per-node version sets, values, sibling counts and metadata sizes must
match.  Schedules include *dynamic universe growth*: coordinators outside
the initial replica set join mid-run, forcing replica-id interning and
column growth in the packed store.

Runs deterministically on fixed seeds; when hypothesis is available the
same driver is additionally fuzzed.
"""
import random

import numpy as np
import pytest

from repro.core import DVV_MECHANISM
from repro.core import batched as B
from repro.store import (
    KVCluster, PackedPayload, PackedVersionStore, SimNetwork, Unavailable,
)
from repro.store.bulk import bulk_receive_antientropy, bulk_sync

KEYS = tuple(f"k{i}" for i in range(6))


# ---------------------------------------------------------------------------
# The schedule driver (shared by deterministic and hypothesis runs).
# ---------------------------------------------------------------------------

def _drive(packed: bool, seed: int, ops: int = 120, *,
           grow_universe: bool = True) -> KVCluster:
    """Run one randomized schedule; identical seeds ⇒ identical schedules."""
    rng = random.Random(seed)
    nodes = ("a", "b", "c", "d")
    # Universe growth: only the first two nodes coordinate for the first
    # half of the run; c and d appear later, growing every packed store's
    # replica universe mid-flight.
    c = KVCluster(nodes, DVV_MECHANISM, network=SimNetwork(seed=seed),
                  packed=packed)
    contexts = {}
    for i in range(ops):
        active = nodes if (not grow_universe or i > ops // 2) else nodes[:2]
        key, node = rng.choice(KEYS), rng.choice(active)
        p = rng.random()
        if p < 0.25:
            try:
                contexts[(node, key)] = c.get(key, via=node).context
            except Unavailable:
                pass
        elif p < 0.70:
            ctx = contexts.get((node, key), frozenset()) \
                if rng.random() < 0.6 else frozenset()
            c.put(key, f"v{i}", context=ctx, via=node, coordinator=node)
        elif p < 0.80:
            c.deliver_replication()
        elif p < 0.90:
            c.antientropy_round()
        elif p < 0.95:
            halves = set(rng.sample(nodes, 2))
            c.network.partition(halves, set(nodes) - halves)
        else:
            c.network.heal()
    c.network.heal()
    c.deliver_replication()
    c.antientropy_round()
    return c


def _assert_equal(c_packed: KVCluster, c_obj: KVCluster, tag) -> None:
    for n in c_packed.nodes:
        for k in KEYS:
            vp = c_packed.nodes[n].versions(k)
            vo = c_obj.nodes[n].versions(k)
            assert vp == vo, (tag, n, k, vp, vo)
            assert (c_packed.nodes[n].metadata_size(k)
                    == c_obj.nodes[n].metadata_size(k)), (tag, n, k)
        assert c_packed.nodes[n].is_packed
        assert not c_obj.nodes[n].is_packed


@pytest.mark.parametrize("seed", [0, 1, 7, 42, 1234])
def test_packed_equals_object_on_random_schedules(seed):
    c_packed = _drive(True, seed)
    c_obj = _drive(False, seed)
    _assert_equal(c_packed, c_obj, seed)


def test_universe_growth_mid_run():
    """New coordinators join mid-run; packed column growth must be exact."""
    c_packed = _drive(True, 99, ops=200, grow_universe=True)
    c_obj = _drive(False, 99, ops=200, grow_universe=True)
    _assert_equal(c_packed, c_obj, "grow")
    # all four replicas actually minted events
    some = c_packed.nodes["a"].backend.packed
    assert some.n_replicas >= 4


# ---------------------------------------------------------------------------
# Bulk anti-entropy: arrays in, arrays out; kernel path equals reference.
# ---------------------------------------------------------------------------

def _diverged(packed: bool, seed: int = 5) -> KVCluster:
    rng = random.Random(seed)
    nodes = ("a", "b", "c")
    c = KVCluster(nodes, DVV_MECHANISM, network=SimNetwork(seed=seed),
                  packed=packed)
    for i in range(60):
        c.put(rng.choice(KEYS), f"v{i}", via=rng.choice(nodes),
              coordinator=rng.choice(nodes))
    c.network.queue.clear()   # drop replication: maximum divergence
    return c


def test_packed_payload_roundtrip_and_equality():
    c = _diverged(True)
    p1 = c.nodes["a"].antientropy_payload()
    p2 = c.nodes["a"].antientropy_payload()
    assert isinstance(p1, PackedPayload)
    assert p1 == p2
    assert len(p1) == c.nodes["a"].backend.packed.total_versions()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_bulk_antientropy_packed_matches_object(use_kernel):
    cp = _diverged(True)
    co = _diverged(False)
    # packed → arrays end to end; object → per-key object sync
    payload_p = cp.nodes["a"].antientropy_payload()
    payload_o = co.nodes["a"].antientropy_payload()
    changed_p = bulk_receive_antientropy(cp.nodes["b"], payload_p,
                                         use_kernel=use_kernel)
    changed_o = co.nodes["b"].receive_antientropy(payload_o)
    assert changed_p == changed_o
    for k in KEYS:
        assert cp.nodes["b"].versions(k) == co.nodes["b"].versions(k), k
    # convergence: re-applying the same payload changes nothing
    assert bulk_receive_antientropy(cp.nodes["b"],
                                    cp.nodes["a"].antientropy_payload(),
                                    use_kernel=use_kernel) == 0


def test_bulk_sync_object_entrypoint_empty_and_disjoint():
    assert bulk_sync({}, {}) == {}
    c = _diverged(False)
    only_local = {k: c.nodes["a"].versions(k) for k in KEYS[:2]}
    out = bulk_sync(only_local, {})
    for k in KEYS[:2]:
        assert out[k] == only_local[k]


def test_bulk_sync_empty_universe_zero_clock():
    """Dotless/zero clocks through the public bulk_sync must not crash on an
    empty replica universe (R=0 staging store)."""
    from repro.core.dvv import DVV
    from repro.store import Version

    z = Version(DVV.zero(), "a")
    out = bulk_sync({}, {"k": frozenset({z})})
    assert out["k"] == frozenset({z})
    out2 = bulk_sync({"k": frozenset({z})}, {"k": frozenset({z})})
    assert out2["k"] == frozenset({z})


def test_bulk_sync_prunes_dominated_locals_without_incoming():
    """sync() semantics hold per key even when a key has no incoming rows:
    an internally dominated local set is reduced to its antichain."""
    from repro.core.dvv import DVV
    from repro.store import Version

    low = Version(DVV((("a", 0, 1),)), "old")
    high = Version(DVV((("a", 1, 2),)), "new")
    out = bulk_sync({"k": frozenset({low, high})}, {})
    assert out["k"] == frozenset({high})
    # and mixed: one key with incoming, one without — both pruned
    out2 = bulk_sync({"k": frozenset({low, high}), "j": frozenset({low})},
                     {"j": frozenset({high})})
    assert out2["k"] == frozenset({high})
    assert out2["j"] == frozenset({high})


def test_apply_payload_with_duplicate_keys_does_not_double_insert():
    c = _diverged(True)
    store = c.nodes["a"].backend.packed
    dup = store.payload([KEYS[0], KEYS[0], KEYS[1]])
    dst = c.nodes["b"].backend.packed
    before = dst.total_versions()
    dst.apply_payload(dup)
    after = {k: dst.versions(k) for k in KEYS[:2]}
    dst.apply_payload(dup)   # idempotent — and no duplicate slots
    assert {k: dst.versions(k) for k in KEYS[:2]} == after
    for k in KEYS[:2]:
        assert len(dst.versions(k)) == len({v.clock for v in dst.versions(k)})
    assert dst.total_versions() <= before + len(dup)


def test_bulk_receive_on_object_backend_uses_batched_path():
    """Object-backend DVV nodes must honor use_kernel (batched sweep), and
    agree with the per-key object walk."""
    co = _diverged(False)
    ref = _diverged(False)
    payload = co.nodes["a"].antientropy_payload()
    changed_k = bulk_receive_antientropy(co.nodes["b"], payload,
                                         use_kernel=True)
    changed_o = ref.nodes["b"].receive_antientropy(
        ref.nodes["a"].antientropy_payload())
    assert changed_k == changed_o
    for k in KEYS:
        assert co.nodes["b"].versions(k) == ref.nodes["b"].versions(k), k


def test_steady_state_antientropy_is_array_native():
    """The acceptance criterion: zero per-key DVV encode/decode in the
    steady-state bulk path — verified by monkeypatching the codec."""
    import repro.core.batched as batched

    cp = _diverged(True)
    payload = cp.nodes["a"].antientropy_payload()
    assert isinstance(payload, PackedPayload)

    calls = {"encode": 0, "decode": 0}
    real_encode, real_decode = batched.encode, batched.decode
    enc = cp.nodes["b"].backend.packed.encode_clock

    def count_encode(*a, **kw):
        calls["encode"] += 1
        return real_encode(*a, **kw)

    def count_decode(*a, **kw):
        calls["decode"] += 1
        return real_decode(*a, **kw)

    batched.encode, batched.decode = count_encode, count_decode
    cp.nodes["b"].backend.packed.encode_clock = None  # would raise if used
    try:
        bulk_receive_antientropy(cp.nodes["b"], payload)
        bulk_receive_antientropy(cp.nodes["b"], payload, use_kernel=True)
    finally:
        batched.encode, batched.decode = real_encode, real_decode
        cp.nodes["b"].backend.packed.encode_clock = enc
    assert calls == {"encode": 0, "decode": 0}


# ---------------------------------------------------------------------------
# PackedVersionStore unit behaviour.
# ---------------------------------------------------------------------------

def test_compaction_preserves_state():
    c = _diverged(True, seed=11)
    store = c.nodes["a"].backend.packed
    before = {k: store.versions(k) for k in KEYS}
    store.compact(force=True)
    assert {k: store.versions(k) for k in KEYS} == before
    assert store.n_dead == 0
    assert store.valid[: store.n_slots].all()


def test_slot_capacity_and_column_growth():
    s = PackedVersionStore()
    # force growth well past both initial capacities
    for i in range(300):
        r = f"replica{i % 13}"
        rix = s.intern_replica(r)
        vv = np.zeros(s.n_replicas, np.int32)
        vv[rix] = i // 13
        s.sync_key(f"key{i % 7}", vv[None, :],
                   np.asarray([rix], np.int32),
                   np.asarray([i // 13 + 1], np.int32), [f"v{i}"])
    assert s.n_replicas == 13
    assert s.total_keys() == 7
    # every stored clock still satisfies the one-dot invariant n > m
    live = np.flatnonzero(s.valid[: s.n_slots])
    at = s.dot_id[live]
    assert (s.dot_n[live] > s.vv[live, at]).all()


def test_numpy_twin_matches_jnp_sync_mask():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    N, K, R = 23, 4, 5
    vvs = rng.integers(0, 6, (N, K, R)).astype(np.int32)
    dids = rng.integers(-1, R, (N, K)).astype(np.int32)
    dns = np.where(
        dids >= 0,
        np.take_along_axis(vvs, np.clip(dids, 0, None)[..., None],
                           axis=-1)[..., 0] + rng.integers(1, 4, (N, K)),
        0).astype(np.int32)
    valid = rng.random((N, K)) < 0.8
    ref = np.asarray(B.sync_mask(jnp.asarray(vvs), jnp.asarray(dids),
                                 jnp.asarray(dns), jnp.asarray(valid)))
    got = B.sync_mask_np(vvs, dids, dns, valid)
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# Hypothesis fuzzing of the same driver (optional dependency).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=100_000),
           st.booleans())
    def test_packed_equals_object_fuzzed(seed, grow):
        c_packed = _drive(True, seed, grow_universe=grow)
        c_obj = _drive(False, seed, grow_universe=grow)
        _assert_equal(c_packed, c_obj, (seed, grow))
except ImportError:     # deterministic seeds above still run
    pass


# ---------------------------------------------------------------------------
# Stacked kernel launches: one device call per 32 keys of a plane call.
# ---------------------------------------------------------------------------

NODES5 = ("a", "b", "c", "d", "e")


def _sharded(seed: int = 3, n_keys: int = 40):
    """Five nodes, replication 3, eight shards; every key loaded, some
    with concurrent siblings left by coordinators outside its replica set,
    so write batches meet resident rows to keep and to drop."""
    rng = random.Random(seed)
    c = KVCluster(NODES5, DVV_MECHANISM, replication=3, shards=8,
                  network=SimNetwork(seed=seed))
    keys = [f"p{i}" for i in range(n_keys)]
    c.put_many({k: (f"base-{k}", None) for k in keys}, via="a")
    for k in keys[::3]:
        c.put(k, f"fork-{k}", via="a", coordinator=rng.choice(NODES5))
    c.deliver_replication()
    return c, keys


def _store_state(st: PackedVersionStore):
    n, R = st.n_slots, st.n_replicas
    return (tuple(st.replica_ids), tuple(st.keys), n,
            st.vv[:n, :R].tolist(), st.dot_id[:n].tolist(),
            st.dot_n[:n].tolist(), st.key_ix[:n].tolist(),
            st.valid[:n].tolist(), list(st.values[:n]), st.wall[:n].tolist(),
            st.digest_root(), st.value_root())


def _rmw_items(c: KVCluster, keys, tag: str):
    """One write per key: odd keys with the context of a read, even keys
    blind (they keep the forks as siblings)."""
    ctx = c.get_many(keys[1::2], via="a")
    return {k: (f"{tag}-{k}", ctx[k].context if k in ctx else None)
            for k in keys}


def _kernel_launches(kind: str) -> int:
    import repro.kernels.dvv_ops as pkg
    cache = getattr(pkg, f"dvv_{kind}_bucketed")
    return cache.hits + cache.misses


def test_put_many_kernel_stacked_equals_reference():
    """put_many on the kernel mints and stages every (coordinator, shard)
    batch, masks them in shared launches and only then replicates: every
    store and the network's queue, in order, must come out as on the numpy
    plane."""
    ker, keys = _sharded()
    ref, _ = _sharded()
    acks_k = ker.put_many(_rmw_items(ker, keys, "x"), via="a",
                          use_kernel=True)
    acks_r = ref.put_many(_rmw_items(ref, keys, "x"), via="a")
    assert len({a.coordinator for a in acks_k.values()}) >= 2
    assert acks_k == acks_r
    assert list(ker.network.queue) == list(ref.network.queue)
    for n in NODES5:
        for st_k, st_r in zip(ker.nodes[n].shard_stores,
                              ref.nodes[n].shard_stores):
            assert _store_state(st_k) == _store_state(st_r), n
            assert st_k.check_digests()


@pytest.mark.parametrize("n", [1, 31, 32, 33, 65])
@pytest.mark.parametrize("op", ["get", "put"])
def test_plane_call_makes_one_launch_per_32_keys(op, n):
    """A get_many/put_many of n keys on the kernel makes ceil(n/32)
    launches, however many shards and quorum groups the keys span."""
    c, keys = _sharded(n_keys=n)
    kind = "read_sweep" if op == "get" else "sync_mask"
    before = _kernel_launches(kind)
    if op == "get":
        c.get_many(keys, via="a", use_kernel=True)
    else:
        c.put_many(_rmw_items(c, keys, "y"), via="a", use_kernel=True)
    assert _kernel_launches(kind) - before == -(-n // 32)


def _stacked_count(sizes) -> int:
    """Launches one push's staged tensors of ``sizes`` keys take: the keys
    of tensors of at most ``STACK_ROWS`` share stacks, a larger tensor
    launches alone."""
    small = sum(n for n in sizes if n <= B.STACK_ROWS)
    return -(-small // B.STACK_ROWS) + sum(
        1 for n in sizes if n > B.STACK_ROWS)


@pytest.fixture
def pushes(monkeypatch):
    """Per delta push of a cluster: the key counts of the tensors its
    receiver staged and the sync-mask launches it made on the kernel."""
    import repro.store.cluster as cluster_mod
    out, sizes = [], []
    stage = PackedVersionStore.stage_payload
    real = cluster_mod._delta_antientropy

    def staging(self, payload):
        staged = stage(self, payload)
        if staged is not None:
            sizes.append(staged.tensor[0].shape[0])
        return staged

    def push(*args, **kw):
        sizes.clear()
        before = _kernel_launches("sync_mask")
        stats = real(*args, **kw)
        out.append((list(sizes), _kernel_launches("sync_mask") - before,
                    stats))
        return stats

    monkeypatch.setattr(PackedVersionStore, "stage_payload", staging)
    monkeypatch.setattr(cluster_mod, "_delta_antientropy", push)
    return out


def test_delta_round_stacks_shard_payloads_per_push(pushes):
    """A delta push on the kernel stages every shard's payload, then masks
    them in shared launches of up to 32 keys: fewer launches than shard
    payloads, and the stores come out as on the numpy plane."""
    c, keys = _sharded()
    ref, _ = _sharded()
    for cl in (c, ref):
        cl.network.partition({"a", "b"}, {"c", "d", "e"})
        cl.put_many({k: (f"z-{k}", None) for k in keys}, via="c")
        cl.network.heal()
        cl.network.queue.clear()
    stats = c.delta_antientropy_round(use_kernel=True)
    assert len(pushes) == len(stats)
    for sizes, launches, st in pushes:
        assert launches == _stacked_count(sizes)
        assert launches <= sum(1 for p in st.per_shard if p.payload_slots)
    pairs = sum(1 for st in stats for p in st.per_shard if p.payload_slots)
    assert pairs > 1
    assert sum(launches for _, launches, _ in pushes) < pairs
    del pushes[:]
    assert ref.delta_antientropy_round() == stats
    for n in NODES5:
        for st_k, st_r in zip(c.nodes[n].shard_stores,
                              ref.nodes[n].shard_stores):
            assert _store_state(st_k) == _store_state(st_r), n


def _per_shard_push(src, dst, mask_fn):
    """The reference push: each shard's round planned and applied on its
    own, one ``apply_payload`` (one mask call) per shard payload."""
    from dataclasses import replace

    from repro.store import bulk
    per, probe = [], 0
    for s, (ss, ds) in enumerate(zip(src.backend.stores,
                                     dst.backend.stores)):
        if ss.digest_root() == ds.digest_root() \
                and ss.value_root() == ds.value_root():
            probe += 32
            continue
        payload, stats = bulk._plan_store_round(ss, ds, shard=s)
        if payload is not None:
            stats = replace(stats, changed=ds.apply_payload(
                payload, mask_fn=mask_fn))
        per.append(stats)
    return bulk._aggregate_stats(per, probe)


def _partitioned_writes(c: KVCluster, rng: random.Random, keys) -> None:
    """One step of a random schedule: a random split, writes on either
    side (blind ones leave siblings), heal, replication dropped."""
    nodes = list(NODES5)
    rng.shuffle(nodes)
    cut = rng.randint(1, 4)
    c.network.partition(set(nodes[:cut]), set(nodes[cut:]))
    for side in (nodes[:cut], nodes[cut:]):
        chosen = rng.sample(keys, rng.randint(1, 48))
        via = rng.choice(side)
        try:
            ctx = c.get_many(chosen[::2], via=via) if rng.random() < 0.5 \
                else {}
        except Unavailable:
            ctx = {}
        try:
            c.put_many({k: (f"{via}-{rng.random():.6f}",
                            ctx[k].context if k in ctx else None)
                        for k in chosen}, via=via)
        except Unavailable:
            pass
    c.network.heal()
    c.network.queue.clear()


@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("seed", [11, 12])
def test_stacked_delta_push_equals_per_shard_apply(monkeypatch, seed,
                                                   shards):
    """Delta pushes with a recording numpy ``mask_fn`` standing in for the
    kernel, and on the numpy plane, against the per-shard reference on a
    twin cluster: every shard store slot for slot and every push's stats
    (``per_shard`` too).  The first push ships a node's whole share of
    600 keys, so some shard payloads are above ``STACK_ROWS`` keys: each
    of those launches alone, at its own shape."""
    import repro.kernels.dvv_ops as dvv_ops
    from repro.store.bulk import delta_antientropy

    calls = []

    def recording(vvs, *rest):
        calls.append(vvs.shape[0])
        return B.sync_mask_np(vvs, *rest)

    monkeypatch.setattr(dvv_ops, "dvv_sync_mask_bucketed", recording)

    def build():
        c = KVCluster(NODES5, DVV_MECHANISM, replication=3, shards=shards,
                      network=SimNetwork(seed=seed))
        keys = [f"q{i}" for i in range(600)]
        c.network.partition({"e"}, set(NODES5) - {"e"})
        c.put_many({k: (f"base-{k}", None) for k in keys}, via="a")
        c.network.heal()
        c.network.queue.clear()
        return c, keys

    (stk, keys), (ref, _) = build(), build()
    large_seen, launches, payload_calls = False, 0, 0
    for step in range(5):
        if step:
            _partitioned_writes(stk, random.Random(seed * 10 + step), keys)
            _partitioned_writes(ref, random.Random(seed * 10 + step), keys)
        on_kernel = step % 2 == 0
        for a in NODES5:
            for b in NODES5:
                if a == b:
                    continue
                calls.clear()
                got = delta_antientropy(stk.nodes[a], stk.nodes[b],
                                        use_kernel=on_kernel)
                sizes = list(calls)
                if not on_kernel:
                    assert sizes == []     # the numpy plane calls no kernel
                want = _per_shard_push(ref.nodes[a], ref.nodes[b],
                                       recording if on_kernel else None)
                assert got == want, (step, a, b)
                if not on_kernel:
                    continue
                ref_sizes = calls[len(sizes):]
                assert len(sizes) == _stacked_count(ref_sizes)
                assert len(sizes) <= len(ref_sizes)
                launches += len(sizes)
                payload_calls += len(ref_sizes)
                large = [n for n in ref_sizes if n > B.STACK_ROWS]
                assert sorted(n for n in sizes if n > B.STACK_ROWS) == \
                    sorted(large)
                large_seen |= bool(large)
        for n in NODES5:
            for st_s, st_r in zip(stk.nodes[n].shard_stores,
                                  ref.nodes[n].shard_stores):
                assert _store_state(st_s) == _store_state(st_r), (step, n)
                assert st_s.check_digests()
    assert large_seen and launches < payload_calls


def test_stacked_launches_equal_one_call_per_tensor():
    """The stacking helper against one call per tensor, on random tensors
    of different N, K and R (chunks that split tensors and mix widths)."""
    from repro.store.packed import stacked_launches

    rng = np.random.default_rng(5)
    tensors = []
    for n, k, r in [(3, 2, 3), (40, 5, 4), (0, 1, 2), (7, 1, 6), (29, 3, 2)]:
        vvs = rng.integers(0, 4, (n, k, r)).astype(np.int32)
        dids = rng.integers(-1, r, (n, k)).astype(np.int32)
        has = dids != B.NO_DOT
        dns = np.where(has, np.take_along_axis(
            vvs, np.clip(dids, 0, None)[..., None], axis=-1)[..., 0] + 1,
            0).astype(np.int32)
        tensors.append((vvs, dids, dns, rng.random((n, k)) < 0.8))
    calls = []

    def sweep(vvs, dids, dns, valid):
        calls.append(vvs.shape)
        mask = B.sync_mask_np(vvs, dids, dns, valid)
        ceil = np.stack([B.grouped_ceiling_np(
            vvs[i][mask[i]], dids[i][mask[i]], dns[i][mask[i]],
            np.zeros(int(mask[i].sum()), np.int64), 1)[0]
            for i in range(len(vvs))]) if len(vvs) else \
            np.zeros((0, vvs.shape[2]), np.int64)
        return mask, ceil

    got = stacked_launches(sweep, tensors, ceilings=True)
    assert [s[0] for s in calls] == [32, 32, 15]
    assert all(s[0] <= 32 for s in calls)
    masks = stacked_launches(lambda *a: sweep(*a)[0], tensors)
    for t, (mask, ceil), m in zip(tensors, got, masks):
        want_mask, want_ceil = sweep(*t)
        assert np.array_equal(mask, want_mask)
        assert np.array_equal(m, want_mask)
        assert np.array_equal(ceil, want_ceil)
