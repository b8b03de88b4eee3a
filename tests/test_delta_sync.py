"""Conformance for delta anti-entropy (DESIGN.md §6).

Twin packed clusters execute identical randomized PUT/GET/partition/heal
schedules — one converges with digest-diffed *delta* rounds, the other with
the one-shot full-payload round (the conformance reference).  After every
schedule the stores must be byte-identical: equal version sets, metadata
sizes, and digest trees per node.  Schedules include mid-run
replica-universe growth, and a forced digest-collision probe documents the
probabilistic guarantee plus the full-round safety net.

Also covered here: the digest tree itself (incremental == recomputed,
width folding, diff descent), ``payload(key_ranges=...)`` slicing, and the
shape-bucketed jit-cached ``sync_mask`` (pad-row inertness, cache hits).
"""
import random

import numpy as np
import pytest

from repro.core import DVV_MECHANISM
from repro.core import batched as B
from repro.kernels.dvv_ops import dvv_sync_mask_bucketed
from repro.store import KVCluster, SimNetwork, Unavailable
from repro.store.bulk import bulk_receive_antientropy, delta_antientropy, \
    delta_plan
from repro.store.packed import PackedPayload, PackedVersionStore, key_bucket

KEYS = tuple(f"k{i}" for i in range(6))


# ---------------------------------------------------------------------------
# Schedule driver: identical seeds ⇒ identical schedules; only the
# anti-entropy flavour differs between the twins.
# ---------------------------------------------------------------------------

def _drive(delta: bool, seed: int, ops: int = 120, *,
           grow_universe: bool = True, use_kernel: bool = False) -> KVCluster:
    rng = random.Random(seed)
    nodes = ("a", "b", "c", "d")
    c = KVCluster(nodes, DVV_MECHANISM, network=SimNetwork(seed=seed))

    def round_():
        if delta:
            c.delta_antientropy_round(use_kernel=use_kernel)
        else:
            c.antientropy_round()

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
            round_()
        elif p < 0.95:
            halves = set(rng.sample(nodes, 2))
            c.network.partition(halves, set(nodes) - halves)
        else:
            c.network.heal()
    c.network.heal()
    c.deliver_replication()
    round_()
    round_()          # both flavours need two push rounds for all-pairs
    return c


def _assert_byte_identical(c_delta: KVCluster, c_full: KVCluster, tag):
    for n in c_delta.nodes:
        sd = c_delta.nodes[n].backend.packed
        sf = c_full.nodes[n].backend.packed
        for k in KEYS:
            assert c_delta.nodes[n].versions(k) == \
                c_full.nodes[n].versions(k), (tag, n, k)
            assert c_delta.nodes[n].metadata_size(k) == \
                c_full.nodes[n].metadata_size(k), (tag, n, k)
        # digest trees agree (possibly at different widths — fold)
        w = min(sd.n_buckets, sf.n_buckets)
        assert len(sd.sync_digest().diff(sf.sync_digest())) == 0, (tag, n)
        np.testing.assert_array_equal(
            sd.sync_digest().fold(w).leaves, sf.sync_digest().fold(w).leaves)
        # and the incremental state matches a from-scratch recompute
        assert sd.check_digests(), (tag, n)
        assert sf.check_digests(), (tag, n)


@pytest.mark.parametrize("seed", [0, 3, 11, 42])
def test_delta_rounds_equal_full_rounds(seed):
    c_delta = _drive(True, seed)
    c_full = _drive(False, seed)
    _assert_byte_identical(c_delta, c_full, seed)


def test_delta_rounds_with_universe_growth_and_kernel():
    c_delta = _drive(True, 99, ops=160, grow_universe=True, use_kernel=True)
    c_full = _drive(False, 99, ops=160, grow_universe=True)
    _assert_byte_identical(c_delta, c_full, "grow+kernel")
    assert c_delta.nodes["a"].backend.packed.n_replicas >= 4


def test_converged_round_ships_nothing():
    c = _drive(True, 7)
    stats = c.delta_antientropy_round()
    assert stats and all(s.buckets_divergent == 0 for s in stats)
    assert all(s.payload_slots == 0 and s.payload_bytes == 0 for s in stats)
    assert all(not s.fallback for s in stats)


def test_delta_stats_accounting():
    """Divergence on one key ⇒ one divergent bucket, a sliced payload far
    below the full payload, and payload bytes reported."""
    c = _drive(True, 13)
    c.network.partition({"a"}, {"b", "c", "d"})
    c.put(KEYS[0], "lonely-write", via="a", coordinator="a")
    c.network.heal()
    full = c.nodes["a"].backend.packed.payload()
    st = c.delta_antientropy("a", "b")
    assert st.buckets_divergent == 1
    assert st.changed == 1
    assert 0 < st.payload_slots < len(full)
    assert 0 < st.payload_bytes < full.nbytes()
    assert st.digest_bytes > 0
    # dst converged; a second round is a pure digest no-op
    st2 = c.delta_antientropy("a", "b")
    assert (st2.buckets_divergent, st2.payload_slots, st2.changed) == (0, 0, 0)


def _bulk_store(n_keys, n_replicas=8, seed=0):
    """A store of ``n_keys`` keys, one version each, from one payload."""
    rng = np.random.default_rng(seed)
    vv = rng.integers(0, 5, (n_keys, n_replicas)).astype(np.int32)
    dot_id = rng.integers(0, n_replicas, n_keys).astype(np.int32)
    dot_n = (vv[np.arange(n_keys), dot_id] + 1).astype(np.int32)
    store = PackedVersionStore()
    for i in range(n_replicas):
        store.intern_replica(f"r{i}")
    store.apply_payload(PackedPayload(
        tuple(f"r{i}" for i in range(n_replicas)),
        tuple(f"key{i}" for i in range(n_keys)), vv, dot_id, dot_n,
        np.arange(n_keys, dtype=np.int32),
        tuple(f"B{i}" for i in range(n_keys))))
    return store


def _advance(local, divergence, seed=1):
    """A clone of ``local`` on which a ``divergence`` fraction of the keys
    carries a newer version (each dominates the resident one)."""
    rng = np.random.default_rng(seed)
    remote = local.clone()
    n_keys = len(local.keys)
    n_div = max(1, int(round(n_keys * divergence)))
    div = np.sort(rng.choice(n_keys, n_div, replace=False))
    R = local.n_replicas
    rows = np.flatnonzero(local.valid[: local.n_slots])
    by_key = np.full(n_keys, -1, np.int64)
    by_key[local.key_ix[rows]] = rows          # one live slot per key here
    src = by_key[div]
    vv = local.vv[src, :R].copy()
    # fold the old dot in (n = m+1 is contiguous), then mint a fresh dot
    vv[np.arange(n_div), local.dot_id[src]] = local.dot_n[src]
    dot_id = rng.integers(0, R, n_div).astype(np.int32)
    dot_n = (vv[np.arange(n_div), dot_id] + 1).astype(np.int32)
    remote.apply_payload(PackedPayload(
        tuple(local.replica_ids), tuple(local.keys[int(k)] for k in div),
        vv, dot_id, dot_n, np.arange(n_div, dtype=np.int32),
        tuple(f"D{int(k)}" for k in div)))
    return remote, n_div


@pytest.mark.parametrize("divergence", [0.001, 0.01, 0.1])
def test_delta_payload_follows_divergence(divergence):
    """On a fixed 10,000-key store a delta round costs O(divergence), not
    O(store): its payload holds every divergent key and otherwise only the
    keys sharing a divergent digest bucket — at most ``1 + 2 * keys per
    bucket`` slots per divergent key — and one round converges the
    receiver to what the full-payload round gives."""
    local = _bulk_store(10_000)
    remote, n_div = _advance(local, divergence)
    ranked, width, _ = delta_plan(remote, local.sync_digest())
    payload = remote.payload(key_ranges=ranked, ranges_width=width)
    per_key = 1 + 2 * len(local.keys) / local.n_buckets
    assert n_div <= len(payload) <= n_div * per_key, (n_div, len(payload))
    full = remote.payload()
    assert payload.nbytes() < full.nbytes() * divergence * per_key
    delta_dst, full_dst = local.clone(), local.clone()
    delta_dst.apply_payload(payload)
    full_dst.apply_payload(full)
    assert len(delta_dst.sync_digest().diff(remote.sync_digest())) == 0
    assert delta_dst.digest_root() == full_dst.digest_root()
    assert delta_dst.value_root() == full_dst.value_root()


def test_capped_bidirectional_rounds_converge():
    """``max_ranges`` caps one push, including on receiver-ahead ranges a
    push cannot fix — but bidirectional rounds drain those from the other
    side, so repeated capped rounds converge (bounded by bucket count)."""
    c = _drive(True, 17)
    c.network.partition({"a", "b"}, {"c", "d"})
    rng = random.Random(17)
    for i in range(20):
        side = ("a", "c")[i % 2]
        c.put(rng.choice(KEYS), f"cap{i}", via=side, coordinator=side)
    c.network.heal()
    c.deliver_replication()
    for _ in range(c.nodes["a"].backend.packed.n_buckets):
        stats = c.delta_antientropy_round(max_ranges=1)
        if all(s.buckets_divergent == 0 for s in stats):
            break
    else:
        pytest.fail("capped rounds did not converge")
    a = c.nodes["a"].backend.packed
    for n in ("b", "c", "d"):
        other = c.nodes[n].backend.packed
        assert len(a.sync_digest().diff(other.sync_digest())) == 0, n
        for k in KEYS:
            assert c.nodes[n].versions(k) == c.nodes["a"].versions(k), (n, k)


def test_delta_fallback_on_object_backend():
    c = KVCluster(("a", "b"), DVV_MECHANISM, packed=False,
                  network=SimNetwork(seed=5))
    for i in range(20):
        c.put(KEYS[i % 3], f"v{i}", via="a", coordinator="a")
    c.network.queue.clear()
    st = c.delta_antientropy("a", "b")
    assert st.fallback
    for k in KEYS[:3]:
        assert c.nodes["b"].versions(k) == c.nodes["a"].versions(k)


# ---------------------------------------------------------------------------
# Digest tree unit behaviour.
# ---------------------------------------------------------------------------

def _loaded_store(n_keys: int, seed: int = 0) -> PackedVersionStore:
    rng = np.random.default_rng(seed)
    s = PackedVersionStore()
    for i in range(4):
        s.intern_replica(f"r{i}")
    for i in range(n_keys):
        col = int(rng.integers(0, 4))
        vv = np.zeros(s.n_replicas, np.int32)
        vv[col] = int(rng.integers(0, 4))
        s.sync_key(f"key{i}", vv[None, :], np.asarray([col], np.int32),
                   np.asarray([vv[col] + 1], np.int32), [f"v{i}"])
    return s


def test_digest_incremental_matches_rebuild_through_kill_and_compact():
    s = _loaded_store(200)
    assert s.check_digests()
    # overwrite some keys (kills + inserts), then force compaction
    for i in range(0, 200, 3):
        vv = np.full(s.n_replicas, 7, np.int32)
        s.sync_key(f"key{i}", vv[None, :], np.asarray([0], np.int32),
                   np.asarray([8], np.int32), [f"w{i}"])
    assert s.check_digests()
    s.compact(force=True)
    assert s.check_digests()


def test_digest_is_representation_independent():
    """Same content, different interning order ⇒ identical digests."""
    a, b = PackedVersionStore(), PackedVersionStore()
    for r in ("r0", "r1", "r2"):
        a.intern_replica(r)
    for r in ("r2", "r0", "r1"):
        b.intern_replica(r)
    rng = np.random.default_rng(3)
    writes = []
    for i in range(50):
        col = ("r0", "r1", "r2")[int(rng.integers(0, 3))]
        m = int(rng.integers(0, 5))
        writes.append((f"key{i % 17}", col, m))
    for store, order in ((a, writes), (b, list(reversed(writes)))):
        for key, rid, m in order:
            cix = store.intern_replica(rid)
            vv = np.zeros(store.n_replicas, np.int32)
            vv[cix] = m
            store.sync_key(key, vv[None, :], np.asarray([cix], np.int32),
                           np.asarray([m + 1], np.int32), [f"{key}:{rid}:{m}"])
    assert len(a.sync_digest().diff(b.sync_digest())) == 0
    np.testing.assert_array_equal(a.sync_digest().leaves,
                                  b.sync_digest().leaves)


def test_digest_diff_locates_divergent_bucket():
    s = _loaded_store(64)
    t = s.clone()
    vv = np.zeros(t.n_replicas, np.int32)
    vv[1] = 50
    t.sync_key("key7", vv[None, :], np.asarray([1], np.int32),
               np.asarray([51], np.int32), ["div"])
    d = s.sync_digest().diff(t.sync_digest())
    assert list(d) == [key_bucket("key7", s.n_buckets)]


def test_digest_fold_and_cross_width_diff():
    s = _loaded_store(3000)            # wide (adaptive growth kicked in)
    assert s.n_buckets > 256
    # folding is exact: a store with the same content (whatever width its
    # own growth chose) projects to identical 256-wide leaves
    t = PackedVersionStore(n_buckets=256)
    t.apply_payload(s.payload())
    np.testing.assert_array_equal(t.sync_digest().fold(256).leaves,
                                  s.sync_digest().fold(256).leaves)
    assert len(s.sync_digest().diff(t.sync_digest())) == 0
    # a genuinely narrow peer (few keys, growth never triggers): the wide
    # store diffs against it and slices payloads at the narrow width
    small = PackedVersionStore(n_buckets=256)
    small.apply_payload(s.payload(s.keys[:5]))
    assert small.n_buckets == 256
    d = s.sync_digest().diff(small.sync_digest())
    assert len(d) > 0
    small.apply_payload(s.payload(key_ranges=d, ranges_width=256))
    assert len(s.sync_digest().diff(small.sync_digest())) == 0


def test_payload_key_ranges_equals_key_selection():
    s = _loaded_store(120, seed=9)
    buckets = sorted({int(key_bucket(k, s.n_buckets)) for k in s.keys[:10]})
    by_range = s.payload(key_ranges=buckets)
    want = [k for k in s.keys
            if key_bucket(k, s.n_buckets) in set(buckets) and s.key_slots(k)]
    by_keys = s.payload(sorted(want))
    from repro.store.replica import _as_object_payload
    assert _as_object_payload(by_range) == _as_object_payload(by_keys)


def test_digest_collision_probe():
    """Forced 64-bit collision: the delta round (correctly, per its
    probabilistic contract) ships nothing; the full-payload fallback
    converges; ``rebuild_digests`` repairs the poisoned state."""
    c = _drive(True, 21)
    c.network.partition({"a"}, {"b", "c", "d"})
    c.put(KEYS[2], "hidden-divergence", via="a", coordinator="a")
    c.network.heal()
    a = c.nodes["a"].backend.packed
    b = c.nodes["b"].backend.packed
    assert len(a.sync_digest().diff(b.sync_digest())) > 0
    # poison b's digest tree AND value root to collide with a's (a real
    # miss now requires both 64-bit structures to collide at once)
    b.digest = a.digest.copy()
    b._value_root = a.value_root()
    assert not b.check_digests()                 # detectable locally
    st = c.delta_antientropy("a", "b")
    assert st.payload_slots == 0                 # the miss, documented
    assert c.nodes["b"].versions(KEYS[2]) != c.nodes["a"].versions(KEYS[2])
    # safety net: the full round converges regardless of digest state
    changed = bulk_receive_antientropy(c.nodes["b"],
                                       c.nodes["a"].antientropy_payload())
    assert changed >= 1
    assert c.nodes["b"].versions(KEYS[2]) == c.nodes["a"].versions(KEYS[2])
    # repair, then delta rounds are trustworthy again
    b.rebuild_digests()
    assert b.check_digests()
    st2 = c.delta_antientropy("a", "b")
    assert st2.changed == 0 and b.check_digests()


# ---------------------------------------------------------------------------
# Shape-bucketed, jit-cached sync_mask.
# ---------------------------------------------------------------------------

def _random_grouped(N, K, R, seed=0):
    rng = np.random.default_rng(seed)
    vvs = rng.integers(0, 6, (N, K, R)).astype(np.int32)
    dids = rng.integers(-1, R, (N, K)).astype(np.int32)
    dns = np.where(
        dids >= 0,
        np.take_along_axis(vvs, np.clip(dids, 0, None)[..., None],
                           axis=-1)[..., 0] + rng.integers(1, 4, (N, K)),
        0).astype(np.int32)
    valid = rng.random((N, K)) < 0.8
    return vvs, dids, dns, valid


@pytest.mark.parametrize("shape", [(1, 1, 1), (5, 3, 4), (23, 4, 5),
                                   (9, 2, 8), (64, 5, 13)])
def test_bucketed_mask_matches_reference(shape):
    args = _random_grouped(*shape, seed=sum(shape))
    ref = B.sync_mask_np(*args)
    np.testing.assert_array_equal(B.sync_mask_bucketed(*args), ref)
    np.testing.assert_array_equal(dvv_sync_mask_bucketed(*args), ref)


def test_pad_rows_are_inert():
    """The bucket/padding invariant: zero-filled invalid pad rows/columns
    change nothing about the real region's survival mask."""
    args = _random_grouped(13, 3, 5, seed=4)
    ref = B.sync_mask_np(*args)
    for shape in [(16, 4, 8), (32, 8, 16), (128, 8, 128)]:
        padded = B.pad_sync_args(*args, shape)
        got = B.sync_mask_np(*padded)
        np.testing.assert_array_equal(got[:13, :3], ref, err_msg=str(shape))
        # pad rows themselves never survive (valid=False)
        assert not got[13:].any() and not got[:, 3:].any()


def test_bucket_cache_warm_across_shapes():
    m = B.BucketedSyncMask()
    m(*_random_grouped(5, 2, 3))       # -> bucket (8, 2, 8): miss
    m(*_random_grouped(7, 2, 5))       # same bucket: hit
    m(*_random_grouped(8, 2, 8))       # exact bucket shape: hit
    m(*_random_grouped(100, 2, 5))     # -> (128, 2, 8): miss
    info = m.cache_info()
    assert info["misses"] == 2 and info["hits"] == 2, info
    assert B.bucket_shape(5, 2, 3) in info["buckets"]


def test_bucket_shape_floors_and_pow2():
    assert B.bucket_shape(1, 1, 1) == (8, 2, 8)
    assert B.bucket_shape(9, 3, 9) == (16, 4, 16)
    assert B.bucket_shape(1024, 4, 128) == (1024, 4, 128)


# ---------------------------------------------------------------------------
# Satellite: the value-content digest gap (ROADMAP) is closed.
# ---------------------------------------------------------------------------

def test_value_content_gap_triggers_full_round_fallback():
    """Regression for the ROADMAP §6.1 gap: clock-equal/value-different
    versions — impossible under the protocol, reachable through arbitrary
    non-protocol ``bulk_sync``/``bulk_receive_antientropy`` dicts — are
    invisible to the clock+key digest tree.  The value root must route the
    delta round to the full-payload fallback, never silently report
    convergence."""
    from repro.core.dvv import DVV
    from repro.store import Version

    c = KVCluster(("a", "b"), DVV_MECHANISM, network=SimNetwork(seed=2))
    for i in range(12):
        c.put(KEYS[i % 3], f"v{i}", via="a", coordinator="a")
    c.deliver_replication()
    c.antientropy_round()
    # same clock, different values, one per side (a non-protocol injection)
    clock = DVV((("rogue-writer", 0, 1),))
    bulk_receive_antientropy(c.nodes["a"],
                             {"rogue": frozenset({Version(clock, "X")})})
    bulk_receive_antientropy(c.nodes["b"],
                             {"rogue": frozenset({Version(clock, "Y")})})
    a = c.nodes["a"].backend.packed
    b = c.nodes["b"].backend.packed
    assert len(a.sync_digest().diff(b.sync_digest())) == 0  # clocks collide
    assert a.value_root() != b.value_root()                 # content differs
    assert a.check_digests() and b.check_digests()          # roots are honest
    st = c.delta_antientropy("a", "b")
    assert st.fallback                                      # not a silent skip
    assert st.payload_slots > 0 and st.payload_bytes > 0
    assert st.buckets_divergent == 0                        # the gap, documented
    # the fallback cannot reconcile equal-clock values (resident copy wins);
    # the rounds keep flagging the divergence rather than masking it
    st2 = c.delta_antientropy("a", "b")
    assert st2.fallback
    assert c.nodes["b"].versions("rogue") != c.nodes["a"].versions("rogue")


def test_value_root_tracks_protocol_mutation():
    """Protocol stores never trip the value check: twin stores with equal
    content agree on the root through kills, compaction and growth."""
    s = _loaded_store(120, seed=5)
    t = PackedVersionStore(n_buckets=s.n_buckets)
    t.apply_payload(s.payload())
    assert s.value_root() == t.value_root()
    vv = np.full(s.n_replicas, 9, np.int32)
    for store in (s, t):
        store.sync_key("key3", vv[None, :], np.asarray([1], np.int32),
                       np.asarray([10], np.int32), ["overwrite"])
    s.compact(force=True)
    assert s.value_root() == t.value_root()
    assert s.check_digests() and t.check_digests()
    # same clocks, different value ⇒ roots split
    u = PackedVersionStore(n_buckets=s.n_buckets)
    p = s.payload()
    u.apply_payload(PackedPayload(
        p.replica_ids, p.keys, p.vv, p.dot_id, p.dot_n, p.key_ix,
        tuple("DIFFERENT" if i == 0 else v
              for i, v in enumerate(p.values)), wall=p.wall))
    assert len(s.sync_digest().diff(u.sync_digest())) == 0
    assert s.value_root() != u.value_root()


# ---------------------------------------------------------------------------
# Satellite: sparse object-backend deltas skip absent keys.
# ---------------------------------------------------------------------------

def test_object_backend_sparse_delta_skips_absent_keys(monkeypatch):
    src = KVCluster(("a", "b"), DVV_MECHANISM, packed=False,
                    network=SimNetwork(seed=8))
    for i, k in enumerate(KEYS):
        src.put(k, f"v{i}", via="a", coordinator="a")
    src.network.queue.clear()
    # dst knows only one key
    dst = src.nodes["b"]
    dst.apply_sync(KEYS[0], src.nodes["a"].versions(KEYS[0]))

    calls = []
    real = PackedVersionStore.sync_key_objects

    def counting(self, key, versions):
        calls.append(key)
        return real(self, key, versions)

    monkeypatch.setattr(PackedVersionStore, "sync_key_objects", counting)
    payload = src.nodes["a"].antientropy_payload()
    bulk_receive_antientropy(dst, payload)
    # staging encodes each incoming key once, plus the single present local
    # key — absent local keys are never staged
    assert len(calls) == len(payload) + 1, calls
    for k in KEYS:
        assert dst.versions(k) == src.nodes["a"].versions(k)


def test_compact_vectorized_remap_preserves_lists():
    s = _loaded_store(150, seed=2)
    # kill a scattered subset via dominating writes, then force compaction
    for i in range(0, 150, 2):
        vv = np.full(s.n_replicas, 9, np.int32)
        s.sync_key(f"key{i}", vv[None, :], np.asarray([1], np.int32),
                   np.asarray([10], np.int32), [f"w{i}"])
    before = {k: s.versions(k) for k in s.keys}
    s.compact(force=True)
    assert {k: s.versions(k) for k in s.keys} == before
    assert s.n_dead == 0
    for kix, slots in s._slots_by_key.items():
        for slot in slots:
            assert s.valid[slot] and s.key_ix[slot] == kix
    assert s.check_digests()


# ---------------------------------------------------------------------------
# Hypothesis fuzz of the delta-vs-full driver (slow phase; `make test-all`).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @pytest.mark.slow
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=100_000), st.booleans(),
           st.booleans())
    def test_delta_equals_full_fuzzed(seed, grow, use_kernel):
        c_delta = _drive(True, seed, grow_universe=grow,
                         use_kernel=use_kernel)
        c_full = _drive(False, seed, grow_universe=grow)
        _assert_byte_identical(c_delta, c_full, (seed, grow, use_kernel))
except ImportError:     # deterministic seeds above still run
    pass
