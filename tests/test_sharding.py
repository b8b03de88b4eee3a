"""Sharded packed stores on the vnode ring: placement, rebalance, planes.

Four fronts, mirroring DESIGN.md §10:

* **Ring properties** — determinism (placement is a pure function of the
  member set), O(shards) table size, and the consistent-hashing stability
  guarantee: a join/leave at N nodes remaps ~K/N keys, never O(K).  This
  is the regression test for replacing the per-key md5 full-sort (plus
  its unbounded ``_ring_cache``) with one bisect over vnode tokens.
* **Conformance** — the randomized churn schedules of ``test_churn`` run
  with the store split across 8 shards; packed and object backends must
  stay observationally equal (the object backend keeps one dict — shards
  must be physically invisible).
* **Rebalance** — after a join's shard-by-shard bootstrap, every shard's
  digest tree agrees across its holders and the incremental digests still
  verify against a rebuild.
* **Batched planes** — get_many/put_many admission stays atomic *across*
  shards: one unreachable shard fails the whole batch before any store
  (any shard, any node) is touched.
"""
import pytest

from repro.core import DVV_MECHANISM
from repro.store import (GossipDriver, KVCluster, PackedVersionStore,
                         SimNetwork, Unavailable, cluster_converged,
                         concat_payloads, key_hash64, shard_of_key,
                         split_payload)
from repro.store.sharding import (DEFAULT_PLACEMENT_SLICES, HashRing,
                                  moved_shards, owned_shards, shard_of_hash,
                                  shard_point)

from test_churn import _conformance, _random_ops

pytestmark = pytest.mark.shard

KEYS_10K = [f"key:{i}" for i in range(10_000)]


# ---------------------------------------------------------------------------
# Hashing + ring unit properties.
# ---------------------------------------------------------------------------

def test_key_hash64_stable_and_wide():
    # pinned: the wire/placement hash must never drift between versions
    assert key_hash64("k0") == int.from_bytes(
        __import__("hashlib").blake2b(b"k0", digest_size=8).digest(),
        "little")
    hs = {key_hash64(k) for k in KEYS_10K}
    assert len(hs) == len(KEYS_10K)          # no collisions at 10k keys


def test_shard_of_key_top_bits_and_validation():
    for shards in (1, 2, 8, 256):
        for k in ("a", "b", "zz"):
            s = shard_of_key(k, shards)
            assert 0 <= s < shards
            if shards > 1:
                assert s == shard_of_hash(key_hash64(k), shards)
                assert shard_point(s, shards) <= key_hash64(k)
    for bad in (0, 3, 12, -4):
        with pytest.raises(ValueError):
            shard_of_key("k", bad)
        with pytest.raises(ValueError):
            shard_of_hash(0, bad)


def test_shards_balance_keys():
    counts = [0] * 16
    for k in KEYS_10K:
        counts[shard_of_key(k, 16)] += 1
    assert min(counts) > 0.5 * (len(KEYS_10K) / 16)
    assert max(counts) < 1.5 * (len(KEYS_10K) / 16)


def test_ring_is_pure_function_of_membership():
    a = HashRing(["n2", "n0", "n1"])
    b = HashRing([])
    for n in ("n0", "n1", "n2"):
        b.add(n)
    assert a.placement_table(64, 2) == b.placement_table(64, 2)
    for k in ("x", "y", "z"):
        assert a.replicas_for_key(k, 2) == b.replicas_for_key(k, 2)


def test_ring_membership_errors():
    r = HashRing(["a", "b"])
    with pytest.raises(ValueError):
        r.add("a")
    with pytest.raises(KeyError):
        r.remove("c")
    assert "a" in r and len(r) == 2
    assert r.n_tokens == 2 * r.vnodes


def test_ring_replicas_distinct_and_capped():
    r = HashRing(["a", "b", "c"])
    for k in KEYS_10K[:200]:
        reps = r.replicas_for_key(k, 2)
        assert len(reps) == len(set(reps)) == 2
    assert len(r.replicas_for_key("k", 99)) == 3   # capped at member count


@pytest.mark.parametrize("n_nodes", [4, 8])
def test_placement_stability_on_join_and_leave(n_nodes):
    """The consistent-hashing guarantee the md5 full-sort never gave:
    membership change at N nodes remaps ~K/N keys (generous slack for
    vnode variance), not an arbitrary fraction of the key space."""
    nodes = [f"n{i}" for i in range(n_nodes)]
    ring = HashRing(nodes)
    K, R = 10_000, 2
    before = {k: ring.replicas_for_key(k, R) for k in KEYS_10K}

    ring.add("joiner")
    moved_join = sum(before[k] != ring.replicas_for_key(k, R)
                     for k in KEYS_10K)
    # a joiner takes ~R·K/(N+1) key-slots; allow 2.5x for vnode variance
    assert 0 < moved_join < 2.5 * R * K / (n_nodes + 1)

    ring.remove("joiner")                     # ring returns to `before`
    assert all(before[k] == ring.replicas_for_key(k, R) for k in KEYS_10K)

    ring.remove(nodes[0])
    moved_leave = sum(before[k] != ring.replicas_for_key(k, R)
                      for k in KEYS_10K)
    # only keys that had nodes[0] in their replica set may move
    affected = sum(nodes[0] in reps for reps in before.values())
    assert 0 < moved_leave <= affected
    assert affected < 2.5 * R * K / n_nodes


def test_moved_shards_is_exact_rebalance_set():
    ring = HashRing([f"n{i}" for i in range(5)])
    before = ring.placement_table(256, 3)
    ring.add("n5")
    after = ring.placement_table(256, 3)
    moved = moved_shards(before, after)
    assert 0 < len(moved) < 256               # some move, never all
    for s in moved:
        assert before[s] != after[s]
    for s in set(range(256)) - set(moved):
        assert before[s] == after[s]
    assert owned_shards(after, "n5") >= frozenset(
        s for s in moved if "n5" in after[s])


# ---------------------------------------------------------------------------
# Cluster placement: bounded table, no per-key cache.
# ---------------------------------------------------------------------------

def _cluster(shards=8, n=4, replication=2, seed=0, packed=True):
    return KVCluster([f"n{i}" for i in range(n)], DVV_MECHANISM,
                     replication=replication, packed=packed,
                     network=SimNetwork(seed=seed), seed=seed, shards=shards)


def test_cluster_placement_is_bounded():
    c = _cluster(shards=8)
    assert not hasattr(c, "_ring_cache")      # the unbounded dict is gone
    assert len(c._placement) == 8             # table is O(shards)...
    for k in KEYS_10K:                        # ...however many keys place
        reps = c.replicas_for(k)
        assert len(reps) == 2
        assert tuple(reps) == c._placement[shard_of_key(k, 8)]
    assert len(c._placement) == 8

    c1 = _cluster(shards=1)                   # unsharded: fixed slice count
    assert len(c1._placement) == DEFAULT_PLACEMENT_SLICES


def test_cluster_rejects_bad_shards():
    with pytest.raises(ValueError):
        _cluster(shards=6)


# ---------------------------------------------------------------------------
# Conformance: the churn schedules with sharding on.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 23])
def test_sharded_churn_conformance(seed):
    _conformance(seed, _random_ops(seed), ("shard8", seed), shards=8)


def test_sharded_store_routes_by_shard():
    c = _cluster(shards=8, replication=4)
    for i in range(64):
        c.put(f"k{i}", f"v{i}")
    c.deliver_replication()
    n = c.nodes["n0"]
    assert len(n.shard_stores) == 8
    per_shard = [len(st.keys) for st in n.shard_stores]
    assert sum(per_shard) == 64
    assert sum(1 for x in per_shard if x) > 1  # keys actually spread
    for i in range(64):
        st = n.store_for(f"k{i}")
        assert st is n.shard_stores[n.shard_of(f"k{i}")]
        assert f"k{i}" in st.keys


# ---------------------------------------------------------------------------
# Rebalance: shard-local digests agree after join/leave.
# ---------------------------------------------------------------------------

def test_shard_digests_agree_after_join_rebalance():
    c = _cluster(shards=8, n=3, replication=3, seed=5)
    for i in range(300):
        c.put(f"k{i}", f"v{i}")
    c.deliver_replication()
    d = GossipDriver(c, period=5.0, seed=5)
    d.run_for(120.0)
    assert cluster_converged(c)

    stats = c.add_node("n3")                  # warm shard-by-shard pull
    assert stats and sum(s.changed for s in stats) > 0
    d.run_for(240.0)
    assert cluster_converged(c)
    ref = c.nodes["n0"]
    for other in ("n1", "n2", "n3"):
        for a, b in zip(ref.shard_stores, c.nodes[other].shard_stores):
            assert len(a.sync_digest().diff(b.sync_digest())) == 0
            assert a.value_root() == b.value_root()
    for node in c.nodes.values():
        for st in node.shard_stores:
            assert st.check_digests()         # incremental == rebuilt


def test_remove_handoff_moves_only_owned_shards():
    c = _cluster(shards=16, n=4, replication=2, seed=11)
    for i in range(400):
        c.put(f"k{i}", f"v{i}")
    c.deliver_replication()
    d = GossipDriver(c, period=5.0, seed=11)
    d.run_for(180.0)
    assert cluster_converged(c)
    stats = c.remove_node("n1")
    # every handoff round was shard-filtered: any shard that ran carries
    # its shard id, and converged shards cost only root-probe bytes
    assert stats                              # some survivor got a handoff
    for st in stats:
        assert st.digest_bytes > 0
        assert all(p.shard >= 0 for p in st.per_shard)
    d.run_for(240.0)
    assert cluster_converged(c)


# ---------------------------------------------------------------------------
# Batched planes: admission is atomic across shards.
# ---------------------------------------------------------------------------

def test_get_many_admission_atomic_across_shards(monkeypatch):
    import repro.store.cluster as cluster_mod
    c = _cluster(shards=8, n=3, replication=1, seed=3)
    keys = [f"p{i}" for i in range(24)]
    for k in keys:
        c.put(k, f"v-{k}")
    c.deliver_replication()
    owners = {k: c.replicas_for(k)[0] for k in keys}
    assert {"n0"} < set(owners.values())      # n0 owns some, not all
    merges = []
    real = cluster_mod.quorum_merge_many
    monkeypatch.setattr(
        cluster_mod, "quorum_merge_many",
        lambda *a, **kw: merges.append(1) or real(*a, **kw))
    c.network.partition({"n0"}, {"n1", "n2"})
    with pytest.raises(Unavailable):
        c.get_many(keys, via="n0", quorum=1, repair=True)
    assert merges == []                       # no shard's store was merged
    assert c.network.pending() == 0           # no repair pushes either
    mine = [k for k in keys if owners[k] == "n0"]
    got = c.get_many(mine, via="n0", quorum=1)
    assert all(got[k].values == (f"v-{k}",) for k in mine)


def test_put_many_admission_atomic_across_shards(monkeypatch):
    from repro.store.replica import ReplicaNode
    c = _cluster(shards=8, n=3, replication=1, seed=3)
    keys = [f"p{i}" for i in range(24)]
    owners = {k: c.replicas_for(k)[0] for k in keys}
    assert {"n0"} < set(owners.values())
    writes = []
    real = ReplicaNode.commit_updates
    monkeypatch.setattr(
        ReplicaNode, "commit_updates",
        lambda self, *a, **kw: writes.append(1) or real(self, *a, **kw))
    c.network.partition({"n0"}, {"n1", "n2"})
    with pytest.raises(Unavailable):
        c.put_many({k: (f"w-{k}", None) for k in keys}, via="n0")
    assert writes == []                       # nothing written anywhere
    mine = {k: (f"w-{k}", None) for k in keys if owners[k] == "n0"}
    acks = c.put_many(mine, via="n0")
    assert set(acks) == set(mine)
    assert writes                             # the admitted batch did run


# ---------------------------------------------------------------------------
# Payload plumbing: split/concat round-trips.
# ---------------------------------------------------------------------------

def _filled_store(n_keys=60, node="w"):
    import numpy as np
    st = PackedVersionStore()
    empty = np.zeros(0, np.int32)
    for i in range(n_keys):
        st.update_key(f"k{i}", empty, node, f"v{i}")
    return st


def test_split_payload_partitions_by_shard():
    st = _filled_store()
    full = st.payload()
    parts = split_payload(full, 8)
    got = [k for p in parts.values() for k in p.keys]
    assert sorted(got) == sorted(full.keys)   # partition, no dup/loss
    for s, p in parts.items():
        assert all(shard_of_key(k, 8) == s for k in p.keys)
    assert split_payload(full, 1) == {0: full}


def test_split_then_concat_roundtrips_through_stores():
    st = _filled_store()
    parts = split_payload(st.payload(), 4)
    # apply each part to its own shard store, as the sharded backend does
    stores = [PackedVersionStore() for _ in range(4)]
    for s, p in parts.items():
        assert stores[s].apply_payload(p) == len(p.keys)
    re = concat_payloads([stores[s].payload() for s in sorted(parts)])
    flat = PackedVersionStore()
    flat.apply_payload(re)
    for k in st.keys:
        assert flat.versions(k) == st.versions(k)
