"""The batched quorum-read plane (one-sweep ``get_many``) + read-repair.

Covers the PR's acceptance surface:

* conformance — batched ``get_many`` is byte-identical to looped ``get``
  (values, contexts, resolution order, siblings) on both backends, across
  randomized partition/heal/divergence schedules, quorum sizes and
  proxies, with and without the shape-bucketed kernel mask;
* admission — reachability/quorum resolve for ALL keys up front; a failing
  key raises ``Unavailable`` before any store is merged;
* read-repair — a diverged quorum converges after ONE batched read (one
  consolidated ``("store", payload)`` push per stale member; digests agree
  after delivery) and a converged quorum generates ZERO repair traffic;
* the merged-read staleness signal (``MergedRead.stale``) and the
  ``track_stale`` fast path;
* ``dvv_read_sweep`` — the fused survival+ceiling kernel sweep equals the
  numpy reference (``sync_mask_np`` + ``grouped_ceiling_np``);
* a hypothesis fuzz phase over randomized schedules (slow/property lane).
"""
import random

import numpy as np
import pytest

from repro.core import DVV_MECHANISM
from repro.core import batched as B
from repro.store import (
    KVClient, KVCluster, SimNetwork, Unavailable, quorum_merge_many,
)
from repro.store.packed import PackedPayload, quorum_merge_key

pytestmark = pytest.mark.read

KEYS = tuple(f"k{i}" for i in range(8))
NODES = ("a", "b", "c", "d")


def _cluster(seed=0, packed=None, nodes=NODES, **kw):
    return KVCluster(nodes, DVV_MECHANISM, network=SimNetwork(seed=seed),
                     packed=packed, **kw)


def _drive(seed: int, packed, ops: int = 80) -> KVCluster:
    """Randomized put/partition/heal/deliver schedule ending healed (so a
    full-quorum read is admissible for every key)."""
    rng = random.Random(seed)
    c = _cluster(seed=seed, packed=packed)
    for i in range(ops):
        p = rng.random()
        key, node = rng.choice(KEYS), rng.choice(NODES)
        if p < 0.5:
            try:
                c.put(key, f"v{i}", via=node, coordinator=node)
            except Unavailable:
                pass
        elif p < 0.65:
            c.deliver_replication()
        elif p < 0.85:
            halves = set(rng.sample(NODES, 2))
            c.network.partition(halves, set(NODES) - halves)
        else:
            c.network.heal()
    c.network.heal()
    return c


def _assert_batched_equals_looped(c: KVCluster, keys, via, quorum):
    looped = {k: c.get(k, via=via, quorum=quorum) for k in keys}
    batched = c.get_many(keys, via=via, quorum=quorum)
    assert list(batched) == list(dict.fromkeys(keys))
    for k in keys:
        assert batched[k] == looped[k], (k, via, quorum)


# ---------------------------------------------------------------------------
# Conformance: batched == looped, byte-identical.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("seed", [0, 7, 42])
def test_get_many_equals_looped_get(seed, packed):
    c = _drive(seed, packed)
    for via in NODES:
        for quorum in (1, 2, len(NODES)):
            _assert_batched_equals_looped(c, list(KEYS), via, quorum)


def test_get_many_kernel_mask_equals_reference():
    """use_kernel=True routes the stacked sweep through the shape-bucketed
    Pallas mask; results must not change.  A low-sibling cluster keeps the
    interpret-mode kernel's K² work cheap on the fast lane; the slow
    lane (`make test-read` / nightly) sweeps deep sibling sets below."""
    c, keys = _diverged(packed=True, n_keys=8)
    ref = c.get_many(keys, via="a", quorum=3)
    ker = c.get_many(keys, via="a", quorum=3, use_kernel=True)
    assert ref == ker
    assert any(r.siblings > 1 for r in ref.values())   # a real merge ran


@pytest.mark.slow
def test_get_many_kernel_mask_equals_reference_deep_siblings():
    c = _drive(3, packed=True)
    for quorum in (1, 2, len(NODES)):
        ref = c.get_many(list(KEYS), via="b", quorum=quorum)
        ker = c.get_many(list(KEYS), via="b", quorum=quorum,
                         use_kernel=True)
        assert ref == ker


def test_get_many_groups_by_quorum_set(monkeypatch):
    """Different keys contact different quorum sets through one proxy; the
    grouped merge must keep them apart (and still match looped get)."""
    import repro.store.cluster as cluster_mod
    c = _drive(11, packed=True)
    calls = []
    real = cluster_mod.quorum_merge_many

    def spy(stores_by_key, keys, **kw):
        calls.append(list(keys))
        return real(stores_by_key, keys, **kw)

    monkeypatch.setattr(cluster_mod, "quorum_merge_many", spy)
    _assert_batched_equals_looped(c, list(KEYS), "b", 2)
    # one grouped call for the whole batch, not one per key
    assert len(calls) == 1 and sorted(calls[0]) == sorted(KEYS)


def test_quorum_merge_key_is_one_key_view_of_many():
    c = _drive(5, packed=True)
    stores = [n.backend.packed for n in c.nodes.values()]
    for k in KEYS:
        values, walls, ckeys, entries = quorum_merge_key(stores, k)
        m = quorum_merge_many({k: stores}, [k])[k]
        assert (values, walls, ckeys, entries) == \
            (m.values, m.walls, m.clock_keys, m.entries)


def test_get_many_empty_and_absent_keys():
    c = _cluster(seed=2)
    assert c.get_many([]) == {}
    got = c.get_many(["nope", "nada"], quorum=2)
    for k in ("nope", "nada"):
        assert got[k].values == () and got[k].siblings == 0
        assert got[k].context.is_empty


# ---------------------------------------------------------------------------
# Admission: all keys resolved up front, no partial merges.
# ---------------------------------------------------------------------------

def test_get_many_admission_is_atomic(monkeypatch):
    """If ANY key cannot assemble its read quorum, ``Unavailable`` is
    raised before any store is touched — no partial merge, no repair."""
    import repro.store.cluster as cluster_mod
    c = KVCluster(("x", "y", "z"), DVV_MECHANISM, replication=1,
                  network=SimNetwork(seed=3))
    keys = [f"p{i}" for i in range(12)]
    for k in keys:
        c.put(k, f"v-{k}")
    c.deliver_replication()
    owners = {k: c.replicas_for(k)[0] for k in keys}
    assert {"x"} < set(owners.values())   # some keys at x, some elsewhere
    merges = []
    real = cluster_mod.quorum_merge_many
    monkeypatch.setattr(
        cluster_mod, "quorum_merge_many",
        lambda *a, **kw: merges.append(1) or real(*a, **kw))
    c.network.partition({"x"}, {"y", "z"})
    with pytest.raises(Unavailable):
        c.get_many(keys, via="x", quorum=1, repair=True)
    assert merges == []                   # raised before any merge
    assert c.network.pending() == 0       # and before any repair push
    # x-owned keys alone are admissible
    mine = [k for k in keys if owners[k] == "x"]
    got = c.get_many(mine, via="x", quorum=1)
    assert all(got[k].values == (f"v-{k}",) for k in mine)


def test_get_many_down_proxy():
    c = _cluster(seed=1)
    c.network.fail_node("a")
    with pytest.raises(Unavailable):
        c.get_many(list(KEYS), via="a")


# ---------------------------------------------------------------------------
# Read-repair: diverged quorums heal on the read path.
# ---------------------------------------------------------------------------

def _diverged(packed, seed=9, n_keys=30):
    """All replicas hold all keys; a partition plus dropped replication
    leaves the quorum diverged on a prefix of the keys."""
    nodes = ("a", "b", "c")
    c = _cluster(seed=seed, packed=packed, nodes=nodes)
    cl = KVClient(c, "t", via="a")
    keys = [f"k{i}" for i in range(n_keys)]
    cl.put_many({k: (f"base-{k}", None) for k in keys})
    c.deliver_replication()
    c.network.partition({"a"}, {"b", "c"})
    for k in keys[: n_keys // 2]:
        cl.put(k, f"fork-{k}", coordinator="a")
    c.network.heal()
    c.network.queue.clear()               # drop replication: reads must heal
    return c, keys


@pytest.mark.parametrize("packed", [True, False])
def test_read_repair_converges_in_one_batched_read(packed):
    c, keys = _diverged(packed)
    before = c.network.bytes_sent
    c.get_many(keys, via="a", quorum=3, repair=True)
    assert c.network.pending() > 0
    assert c.network.bytes_sent > before  # repair is priced on the wire
    c.deliver_replication()
    for n in c.nodes:
        for k in keys:
            assert c.nodes[n].versions(k) == c.nodes["a"].versions(k), (n, k)
    if packed:
        roots = {n.backend.packed.sync_digest().root
                 for n in c.nodes.values()}
        assert len(roots) == 1            # digests agree after repair
    # …and a converged quorum generates zero repair traffic
    b1 = c.network.bytes_sent
    c.get_many(keys, via="a", quorum=3, repair=True)
    assert c.network.bytes_sent == b1 and c.network.pending() == 0


def test_read_repair_one_consolidated_push_per_member():
    c, keys = _diverged(packed=True)
    c.get_many(keys, via="a", quorum=3, repair=True)
    # b and c each miss the fork writes: exactly one payload per member,
    # carrying ALL of its stale keys
    msgs = list(c.network.queue)
    assert sorted(m.dst for m in msgs) == ["b", "c"]
    for m in msgs:
        kind, payload = m.payload
        assert kind == "store" and isinstance(payload, PackedPayload)
        assert sorted(payload.keys) == sorted(keys[: len(keys) // 2])
        assert m.src == "a"               # the proxy coordinates repair


def test_read_repair_off_by_default_never_mutates():
    c, keys = _diverged(packed=True)
    before = c.network.bytes_sent
    c.get_many(keys, via="a", quorum=3)
    cl = KVClient(c, "s", via="a")
    cl.get_many(keys, quorum=3)           # session default is off too
    assert c.network.pending() == 0 and c.network.bytes_sent == before
    # sibling divergence is still visible (nothing healed behind our back)
    assert c.nodes["b"].versions(keys[0]) != c.nodes["a"].versions(keys[0])


def test_read_repair_client_session_default():
    c, keys = _diverged(packed=True)
    cl = KVClient(c, "healer", via="a", read_repair=True)
    cl.get_many(keys, quorum=3)
    c.deliver_replication()
    for n in c.nodes:
        for k in keys:
            assert c.nodes[n].versions(k) == c.nodes["a"].versions(k)
    # per-call override wins over the session default
    c2, keys2 = _diverged(packed=True)
    cl2 = KVClient(c2, "reader", via="a", read_repair=True)
    cl2.get_many(keys2, quorum=3, repair=False)
    assert c2.network.pending() == 0


def test_read_repair_stale_proxy_heals_locally():
    """When the proxy itself is a stale quorum member (the common case —
    local-read preference puts it first), repair applies the payload
    locally: no self-addressed message, no phantom wire bytes, and the
    proxy is healed immediately (not at the next delivery)."""
    c, keys = _diverged(packed=True)          # b and c missed a's forks
    b0 = c.network.bytes_sent
    c.get_many(keys, via="b", quorum=3, repair=True)
    msgs = list(c.network.queue)
    assert sorted(m.dst for m in msgs) == ["c"]    # only c gets a message
    sent = c.network.bytes_sent - b0
    from repro.store.network import payload_nbytes
    assert sent == sum(payload_nbytes(m.payload) for m in msgs)
    # b (the proxy) already holds the merged state, pre-delivery
    for k in keys:
        assert c.nodes["b"].versions(k) == c.nodes["a"].versions(k), k
    c.deliver_replication()
    for k in keys:
        assert c.nodes["c"].versions(k) == c.nodes["a"].versions(k), k
    b1 = c.network.bytes_sent
    c.get_many(keys, via="b", quorum=3, repair=True)
    assert c.network.bytes_sent == b1 and c.network.pending() == 0


def test_stale_detection_is_value_aware():
    """The §6.1 gap state — equal clocks, different values (impossible
    under the protocol, reachable via non-protocol bulk feeds) — must be
    FLAGGED stale, never read as converged.  Like the delta round's
    full-payload fallback, sync cannot reconcile it (the resident copy
    wins), so repaired reads keep flagging rather than masking it."""
    from repro.core.dvv import DVV
    from repro.store import Version
    from repro.store.bulk import bulk_receive_antientropy

    c = _cluster(seed=2, packed=True, nodes=("a", "b"))
    c.put("k", "v", coordinator="a")
    c.deliver_replication()
    clock = DVV((("rogue-writer", 0, 1),))
    bulk_receive_antientropy(c.nodes["a"],
                             {"rogue": frozenset({Version(clock, "X")})})
    bulk_receive_antientropy(c.nodes["b"],
                             {"rogue": frozenset({Version(clock, "Y")})})
    stores = [c.nodes["a"].backend.packed, c.nodes["b"].backend.packed]
    m = quorum_merge_many({"rogue": stores}, ["rogue"])["rogue"]
    assert m.stale == (1,)        # b's value diverges under an equal clock
    c.get_many(["rogue"], via="a", quorum=2, repair=True)
    assert c.network.pending() == 1         # flagged, not silently skipped
    c.deliver_replication()
    # …and, as documented, sync keeps the resident copy: the divergence
    # stays visible (and stays flagged) instead of being masked
    assert c.nodes["b"].versions("rogue") != c.nodes["a"].versions("rogue")


def test_merged_read_stale_signal():
    """``stale`` flags exactly the members whose row set differs from the
    survivors: behind members AND members holding dominated rows."""
    c, keys = _diverged(packed=True, n_keys=4)
    stores = {n: c.nodes[n].backend.packed for n in c.nodes}
    quorum = [stores["a"], stores["b"], stores["c"]]
    merged = quorum_merge_many({k: quorum for k in keys}, keys)
    for k in keys[:2]:                    # forked keys: b, c are stale
        assert merged[k].stale == (1, 2), k
    for k in keys[2:]:                    # converged keys: nobody is
        assert merged[k].stale == (), k
    # track_stale=False skips the bookkeeping but not the merge
    fast = quorum_merge_many({k: quorum for k in keys}, keys,
                             track_stale=False)
    for k in keys:
        assert fast[k].stale == ()
        assert fast[k].values == merged[k].values
        assert fast[k].entries == merged[k].entries


# ---------------------------------------------------------------------------
# Stacked launches: every quorum group of a get_many in shared kernel calls.
# ---------------------------------------------------------------------------

NODES5 = ("a", "b", "c", "d", "e")


def _sharded_diverged(n_keys: int, seed: int = 4) -> KVCluster:
    """Five nodes, replication 3, eight shards (so eight placement slices
    and per-(node, shard) stores): every key is written, then rewritten
    without context by coordinators inside and outside its replica set
    (siblings, and union universes of 3 to 5 replicas), and half the
    replication is dropped (stale members).  Same seed, same cluster."""
    rng = random.Random(seed)
    c = _cluster(seed=seed, nodes=NODES5, replication=3, shards=8)
    keys = [f"s{i}" for i in range(n_keys)]
    c.put_many({k: (f"base-{k}", None) for k in keys}, via="a")
    c.deliver_replication()
    for i, k in enumerate(keys):
        for j in range(rng.randrange(3)):
            c.put(k, f"w{j}-{k}", via="a",
                  coordinator=rng.choice(NODES5[: 2 + i % 4]))
    c.deliver_replication(max_messages=c.network.pending() // 2)
    c.network.queue.clear()
    return c


@pytest.mark.parametrize("n_keys", [31, 32, 33, 65])
def test_get_many_kernel_stacked_equals_reference(n_keys):
    """A get_many on the kernel stacks every quorum group's tensor into
    launches of up to 32 keys; it must read, flag staleness and push
    repairs exactly as the numpy plane and the looped get do."""
    from repro.kernels.dvv_ops import dvv_read_sweep_bucketed

    ker, ref = _sharded_diverged(n_keys), _sharded_diverged(n_keys)
    keys = [f"s{i}" for i in range(n_keys)]
    quorum_sets = {ker.replicas_for(k) for k in keys}
    assert len(quorum_sets) >= 3
    looped = {k: ref.get(k, via="a", quorum=3) for k in keys}

    stores_by_key = {k: [ker.nodes[r].store_for(k)
                         for r in ker._reachable_replicas("a", k)[:3]]
                     for k in keys}
    want = quorum_merge_many(stores_by_key, keys)
    got = quorum_merge_many(stores_by_key, keys,
                            sweep_fn=dvv_read_sweep_bucketed)
    assert len({len(m.replica_ids) for m in want.values()}) >= 2
    assert any(m.stale for m in want.values())
    for k in keys:
        w, g = want[k], got[k]
        assert (g.replica_ids, g.values, g.walls, g.clock_keys, g.entries,
                g.stale) == (w.replica_ids, w.values, w.walls,
                             w.clock_keys, w.entries, w.stale), k
        for a in ("vv", "dot_id", "dot_n"):
            assert np.array_equal(getattr(g, a), getattr(w, a)), (k, a)

    got_read = ker.get_many(keys, via="a", quorum=3, repair=True,
                            use_kernel=True)
    ref_read = ref.get_many(keys, via="a", quorum=3, repair=True)
    assert got_read == ref_read == looped
    assert ker.network.pending() > 0
    assert list(ker.network.queue) == list(ref.network.queue)
    for n in NODES5:
        for k in keys:
            assert ker.nodes[n].versions(k) == ref.nodes[n].versions(k)


def _hot_cart(seed: int = 5) -> KVCluster:
    """A cart written blind 45 times by coordinators across its replica
    set and beyond, with a third of the replication dropped: 40-odd
    siblings on each replica, and replicas that disagree, so a quorum read
    merges well over 32 versions.  Same seed, same cluster."""
    rng = random.Random(seed)
    c = _cluster(seed=seed, nodes=NODES5, replication=3, shards=8)
    c.put_many({k: (f"base-{k}", None) for k in ("cart", "x1", "x2")},
               via="a")
    c.deliver_replication()
    for i in range(45):
        c.put("cart", f"add-{i}", via="a", coordinator=rng.choice(NODES5))
    c.deliver_replication(max_messages=2 * c.network.pending() // 3)
    c.network.queue.clear()
    return c


def test_hot_cart_past_32_siblings_kernel_equals_numpy(monkeypatch):
    """A key with 40 and more siblings reads and writes through the
    kernels (survival at K buckets of 64 and 128) exactly as through the
    numpy plane: values, sibling sets, context bytes, acknowledged clocks
    and every replica's versions."""
    import repro.kernels.dvv_ops as pkg

    ks = {"read": [], "write": []}
    for kind, attr in (("read", "dvv_read_sweep_bucketed"),
                       ("write", "dvv_sync_mask_bucketed")):
        inner = getattr(pkg, attr)

        def record(vvs, *rest, _inner=inner, _seen=ks[kind]):
            _seen.append(vvs.shape[1])
            return _inner(vvs, *rest)

        monkeypatch.setattr(pkg, attr, record)

    ker, ref = _hot_cart(), _hot_cart()
    keys = ["cart", "x1", "x2"]
    for quorum in (2, 3):
        got = ker.get_many(keys, via="b", quorum=quorum, use_kernel=True)
        want = ref.get_many(keys, via="b", quorum=quorum)
        assert got == want
        assert got["cart"].siblings >= 40
        assert (got["cart"].context.to_bytes()
                == want["cart"].context.to_bytes())
    assert max(ks["read"]) > 64                  # the K = 128 bucket ran
    # one blind add (every sibling survives it), then one with the read's
    # context (it supersedes them all), each coordinated on the kernel
    for ctx in (None, got["cart"].context):
        items = {"cart": ("add-more", ctx), "x1": ("x1-new", None)}
        acks_k = ker.put_many(items, via="b", quorum=2, use_kernel=True)
        acks_r = ref.put_many(items, via="b", quorum=2)
        assert acks_k == acks_r
    assert max(ks["write"]) > 32                 # the K = 64 bucket ran
    ker.deliver_replication()
    ref.deliver_replication()
    for n in NODES5:
        for k in keys:
            assert ker.nodes[n].versions(k) == ref.nodes[n].versions(k)
    final = ker.get_many(keys, via="c", quorum=3, use_kernel=True)
    assert final == ref.get_many(keys, via="c", quorum=3)


# ---------------------------------------------------------------------------
# dvv_read_sweep: fused survival + ceiling equals the numpy reference.
# ---------------------------------------------------------------------------

def test_dvv_read_sweep_matches_reference():
    from repro.kernels.dvv_ops import dvv_read_sweep

    rng = np.random.default_rng(0)
    N, K, R = 9, 4, 5
    vvs = rng.integers(0, 4, (N, K, R)).astype(np.int32)
    dot_ids = rng.integers(-1, R, (N, K)).astype(np.int32)
    has = dot_ids != B.NO_DOT
    dot_ns = np.where(
        has, np.take_along_axis(
            vvs, np.clip(dot_ids, 0, None)[..., None], axis=-1)[..., 0] + 1,
        0).astype(np.int32)
    valid = rng.random((N, K)) < 0.8
    mask, ceil = dvv_read_sweep(vvs, dot_ids, dot_ns, valid)
    mask, ceil = np.asarray(mask), np.asarray(ceil)
    want_mask = B.sync_mask_np(vvs, dot_ids, dot_ns, valid)
    assert np.array_equal(mask, want_mask)
    for n in range(N):
        s = np.flatnonzero(want_mask[n])
        want = B.grouped_ceiling_np(
            vvs[n][s], dot_ids[n][s], dot_ns[n][s],
            np.zeros(len(s), np.int64), 1)[0]
        assert np.array_equal(ceil[n], want), n


def test_grouped_ceiling_matches_per_key_reference():
    from repro.store.packed import ceiling_from_rows

    rng = np.random.default_rng(1)
    M, R, N = 40, 6, 7
    vvs = rng.integers(0, 5, (M, R)).astype(np.int32)
    dot_ids = rng.integers(-1, R, M).astype(np.int32)
    dot_ns = rng.integers(1, 9, M).astype(np.int32)
    dot_ns[dot_ids == B.NO_DOT] = 0
    groups = rng.integers(0, N, M)
    got = B.grouped_ceiling_np(vvs, dot_ids, dot_ns, groups, N)
    for g in range(N):
        s = np.flatnonzero(groups == g)
        assert np.array_equal(
            got[g], ceiling_from_rows(vvs[s], dot_ids[s], dot_ns[s])), g
    # empty input: all-zero ceilings, right shape
    assert B.grouped_ceiling_np(np.zeros((0, R), np.int32),
                                np.zeros(0, np.int32), np.zeros(0, np.int32),
                                np.zeros(0, np.int64), 3).shape == (3, R)


# ---------------------------------------------------------------------------
# Hypothesis fuzz (slow/property lane; see pytest.ini markers).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @pytest.mark.slow
    @pytest.mark.property
    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=100_000), st.booleans(),
           st.sampled_from([1, 2, 4]))
    def test_get_many_conformance_fuzzed(seed, packed, quorum):
        c = _drive(seed, packed)
        _assert_batched_equals_looped(
            c, list(KEYS), random.Random(seed).choice(NODES), quorum)
        # repair leaves the read results themselves untouched…
        before = c.get_many(list(KEYS), via="a", quorum=quorum)
        repaired = c.get_many(list(KEYS), via="a", quorum=quorum,
                              repair=True)
        assert before == repaired
        c.deliver_replication()
        # …and a repaired+delivered quorum is read-quiescent
        again = c.get_many(list(KEYS), via="a", quorum=quorum, repair=True)
        assert c.network.pending() == 0
        assert again == repaired
except ImportError:     # deterministic seeds above still run
    pass
