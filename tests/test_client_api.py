"""Client API: opaque CausalContext tokens, KVClient sessions, batching.

Covers the PR's acceptance surface:

* token round-trips — encode→bytes→decode→PUT equals object-context PUT on
  randomized schedules, on both the packed and object backends;
* the §5.4 compaction claim — ``to_bytes()`` is O(R), independent of the
  sibling count;
* zero object-clock decodes on packed GET (monkeypatched codec);
* deterministic ``GetResult.value`` resolution by (wall_time, clock, value);
* ``KVClient`` sessions: counters, ``get_many``/``put_many`` conformance
  with looped single-key operations, quorum/Unavailable error paths;
* gossip scheduling: seeded round-robin ``fanout=`` rounds converge and are
  deterministic; the per-round ``max_ranges`` budget defaults on;
* the bucket→slot index: payload slicing stays exact through kills,
  compaction and digest-tree growth.
"""
import random

import numpy as np
import pytest

from repro.core import ALL_MECHANISMS, DVV_MECHANISM
from repro.store import (
    CausalContext, KVClient, KVCluster, SimNetwork, Unavailable,
)
from repro.store.packed import PackedVersionStore

KEYS = tuple(f"k{i}" for i in range(6))
NODES = ("a", "b", "c", "d")


def _cluster(seed=0, packed=None, mech="dvv", nodes=NODES, **kw):
    return KVCluster(nodes, ALL_MECHANISMS[mech],
                     network=SimNetwork(seed=seed), packed=packed, **kw)


# ---------------------------------------------------------------------------
# Token round-trips (randomized schedules, both backends).
# ---------------------------------------------------------------------------

def _drive_tokens(seed: int, packed: bool, roundtrip: bool,
                  ops: int = 100) -> KVCluster:
    """Randomized PUT/GET/partition schedule; ``roundtrip=True`` sends every
    context through bytes (encode→decode) before the PUT."""
    rng = random.Random(seed)
    c = _cluster(seed=seed, packed=packed)
    contexts = {}
    for i in range(ops):
        key, node = rng.choice(KEYS), rng.choice(NODES)
        p = rng.random()
        if p < 0.3:
            try:
                ctx = c.get(key, via=node).context
                assert isinstance(ctx, CausalContext)
                contexts[(node, key)] = ctx
            except Unavailable:
                pass
        elif p < 0.75:
            ctx = contexts.get((node, key)) if rng.random() < 0.7 else None
            if roundtrip and ctx is not None:
                ctx = CausalContext.from_bytes(ctx.to_bytes())
            try:
                c.put(key, f"v{i}", context=ctx, via=node, coordinator=node)
            except Unavailable:
                pass
        elif p < 0.85:
            c.deliver_replication()
        elif p < 0.95:
            halves = set(rng.sample(NODES, 2))
            c.network.partition(halves, set(NODES) - halves)
        else:
            c.network.heal()
    c.network.heal()
    c.deliver_replication()
    c.antientropy_round()
    return c


@pytest.mark.property
@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("seed", [0, 7, 42])
def test_token_bytes_roundtrip_equals_object_context(seed, packed):
    direct = _drive_tokens(seed, packed, roundtrip=False)
    viabytes = _drive_tokens(seed, packed, roundtrip=True)
    for n in NODES:
        for k in KEYS:
            assert direct.nodes[n].versions(k) == \
                viabytes.nodes[n].versions(k), (seed, packed, n, k)
    # and packed equals object under byte-roundtripped tokens
    other = _drive_tokens(seed, not packed, roundtrip=True)
    for n in NODES:
        for k in KEYS:
            assert other.nodes[n].versions(k) == \
                viabytes.nodes[n].versions(k), (seed, packed, n, k)
            ra = other.get(k, via=n)
            rb = viabytes.get(k, via=n)
            assert ra.values == rb.values
            assert ra.value == rb.value        # deterministic resolution
            assert ra.context.entries == rb.context.entries


def test_token_is_o_of_replicas_not_siblings():
    """§5.4: five concurrent siblings through one coordinator still compact
    to a ceiling over the replica universe — byte size doesn't grow with
    the sibling count."""
    c = _cluster(nodes=("a", "b"))
    c.put("k", "v0", coordinator="b")
    one_sibling = c.get("k", via="b").context.to_bytes()
    for i in range(1, 5):
        c.put("k", f"v{i}", coordinator="b")   # blind writes: all concurrent
    got = c.get("k", via="b")
    assert got.siblings == 5
    assert len(got.context.entries) <= 2                   # ≤ R entries
    assert len(got.context.to_bytes()) == len(one_sibling)  # O(R), not O(sib)


def test_token_clock_set_view_and_legacy_shim():
    """Tokens iterate as clock sets (ceiling DVV); raw frozenset contexts
    still work through the deprecation shim and produce identical state."""
    c1 = _cluster(seed=3, nodes=("a", "b"))
    c2 = _cluster(seed=3, nodes=("a", "b"))
    for c in (c1, c2):
        c.put("k", "v", coordinator="b")
        c.put("k", "w", coordinator="b")
    tok = c1.get("k", via="b").context
    clocks = frozenset(tok)                    # legacy clock-set view
    assert len(clocks) == 1                    # one compacted ceiling clock
    c1.put("k", "merged", context=tok, coordinator="b")
    with pytest.deprecated_call():
        c2.put("k", "merged", context=clocks, coordinator="b")
    assert c1.nodes["b"].versions("k") == c2.nodes["b"].versions("k")
    assert c1.get("k", via="b").values == ("merged",)


def test_token_residue_non_dvv_mechanisms():
    """Non-DVV clocks ride in the residue and round-trip through bytes."""
    c = _cluster(seed=1, mech="oracle", nodes=("a", "b"))
    c.put("k", "v", coordinator="b")
    c.put("k", "w", coordinator="b")
    tok = c.get("k", via="b").context
    assert tok.residue and not tok.entries
    tok2 = CausalContext.from_bytes(tok.to_bytes())
    assert tok2 == tok
    c.put("k", "merged", context=tok2, coordinator="b")
    assert c.get("k", via="b").values == ("merged",)


def test_coerce_rejects_garbage():
    with pytest.raises(TypeError):
        CausalContext.coerce(42)
    with pytest.raises(ValueError):
        CausalContext.from_bytes(b"not-a-token")


def test_from_bytes_rejects_truncated_corrupt_and_empty():
    """Satellite edge cases: a malformed token must fail with a clean
    ``ValueError`` — never an IndexError/struct.error, never a context
    carrying a *prefix* of the encoded entries."""
    tok = CausalContext(entries=(("node-a", 7), ("node-b", 3),
                                 ("node-c", 12)))
    wire = tok.to_bytes()
    assert CausalContext.from_bytes(wire) == tok
    # empty and sub-magic inputs
    for data in (b"", b"D", b"DCX", b"XXX1"):
        with pytest.raises(ValueError):
            CausalContext.from_bytes(data)
    # truncation at EVERY byte boundary fails cleanly — no partial decode
    for cut in range(len(wire)):
        with pytest.raises(ValueError):
            CausalContext.from_bytes(wire[:cut])
    # trailing garbage is corruption, not silently ignored
    with pytest.raises(ValueError):
        CausalContext.from_bytes(wire + b"\x00")
    # corrupt residue flag / unpicklable residue blob
    with pytest.raises(ValueError):
        CausalContext.from_bytes(wire[:4] + b"\x07" + wire[5:])
    residueless_header = wire[:4] + b"\x01" + wire[5:]
    with pytest.raises(ValueError):
        CausalContext.from_bytes(residueless_header + b"\x80garbage")
    # an entry id that is not UTF-8
    bad = bytearray(wire)
    bad[9:11] = b"\xff\xfe"                   # inside "node-a"
    with pytest.raises(ValueError):
        CausalContext.from_bytes(bytes(bad))
    # trailing garbage after a residue blob (pickle STOPs early) is
    # corruption too, and residue truncation fails cleanly
    res_tok = CausalContext(entries=(("node-a", 1),), residue=("stamp",))
    res_wire = res_tok.to_bytes()
    assert CausalContext.from_bytes(res_wire) == res_tok
    with pytest.raises(ValueError):
        CausalContext.from_bytes(res_wire + b"\x00")
    with pytest.raises(ValueError):
        CausalContext.from_bytes(res_wire[:-1])


def test_from_bytes_rejects_pickle_gadgets(tmp_path):
    """Tokens travel through untrusted clients: a crafted residue blob
    whose pickle would execute a callable must be *rejected*, not run."""
    import os
    import pickle
    import struct

    from repro.store.context import _MAGIC

    marker = tmp_path / "pwned"

    class Evil:
        def __reduce__(self):
            return (os.system, (f"touch {marker}",))

    evil_wire = _MAGIC + struct.pack("<BH", 1, 0) + pickle.dumps((Evil(),))
    with pytest.raises(ValueError):
        CausalContext.from_bytes(evil_wire)
    assert not marker.exists()               # the gadget never executed
    # protocol-4 dotted STACK_GLOBAL through a repro module's own imports
    # (repro.store.wal does `import os`) must be rejected too — a
    # namespace-prefix allowance would resolve `os.system` through it
    def short_unicode(s):
        b = s.encode()
        return b"\x8c" + bytes([len(b)]) + b

    dotted = (b"\x80\x04"                                 # PROTO 4
              + short_unicode("repro.store.wal")
              + short_unicode("os.system")
              + b"\x93"                                   # STACK_GLOBAL
              + short_unicode(f"touch {marker}")
              + b"\x85R.")                                # TUPLE1 REDUCE STOP
    with pytest.raises(ValueError):
        CausalContext.from_bytes(_MAGIC + struct.pack("<BH", 1, 0) + dotted)
    assert not marker.exists()


# ---------------------------------------------------------------------------
# Acceptance: packed GET performs zero object-clock decodes.
# ---------------------------------------------------------------------------

def test_packed_get_zero_object_decodes(monkeypatch):
    import repro.core.batched as batched

    c = _cluster(seed=5)
    for i in range(12):
        c.put(KEYS[i % 3], f"v{i}", via=NODES[i % 4],
              coordinator=NODES[i % 4])
    c.deliver_replication()
    calls = {"decode": 0, "encode": 0}
    real_dec, real_enc = batched.decode, batched.encode

    def count_dec(*a, **kw):
        calls["decode"] += 1
        return real_dec(*a, **kw)

    def count_enc(*a, **kw):
        calls["encode"] += 1
        return real_enc(*a, **kw)

    monkeypatch.setattr(batched, "decode", count_dec)
    monkeypatch.setattr(batched, "encode", count_enc)
    for k in KEYS[:3]:
        got = c.get(k, via="a", quorum=3)
        assert got.values
        assert got.value is not None
        assert got.context.entries
    assert calls == {"decode": 0, "encode": 0}


def test_packed_store_context_of_matches_clock_ceiling():
    c = _cluster(seed=6, nodes=("a", "b"))
    c.put("k", "v", coordinator="a")
    c.put("k", "w", coordinator="b")
    c.antientropy_round()
    store = c.nodes["a"].backend.packed
    tok = store.context_of("k")
    want = CausalContext.from_clocks(
        v.clock for v in c.nodes["a"].versions("k"))
    assert tok.entries == want.entries
    assert store.context_of("absent-key").is_empty


# ---------------------------------------------------------------------------
# Deterministic GetResult.value resolution.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("packed", [True, False])
def test_value_resolution_latest_wall_time_wins(packed):
    c = _cluster(seed=2, packed=packed, nodes=("a", "b"))
    c.network.partition({"a"}, {"b"})
    c.put("k", "older", coordinator="a", via="a")     # wall 1.0
    c.put("k", "newer", coordinator="b", via="b")     # wall 2.0
    c.network.heal()
    c.antientropy_round()
    got = c.get("k", via="a")
    assert set(got.values) == {"newer", "older"}      # both siblings kept
    assert got.siblings == 2
    assert got.value == "newer"                       # resolved by wall time
    assert len(got.resolution) == 2


@pytest.mark.property
def test_value_resolution_agrees_across_backends():
    for seed in (0, 11, 42):
        cp = _drive_tokens(seed, packed=True, roundtrip=False)
        co = _drive_tokens(seed, packed=False, roundtrip=False)
        for n in NODES:
            for k in KEYS:
                rp, ro = cp.get(k, via=n), co.get(k, via=n)
                assert rp.value == ro.value, (seed, n, k)
                assert rp.resolution == ro.resolution, (seed, n, k)


# ---------------------------------------------------------------------------
# KVClient sessions: batching conformance + error paths.
# ---------------------------------------------------------------------------

def test_kvclient_session_counter_and_roundtrip():
    c = _cluster(seed=4)
    client = KVClient(c, "alice", via="a")
    client.put("cart", "apple")
    assert client.counter == 1
    got = client.get("cart")
    client.put("cart", "apple+banana", context=got.context)
    assert client.counter == 2
    assert client.get("cart").values == ("apple+banana",)


@pytest.mark.parametrize("packed", [True, False])
def test_put_many_equals_looped_puts(packed):
    """The batched path is observationally equal to K single puts — same
    coordinators, same wall-times, same minted clocks, same replica state."""
    keys = [f"key{i}" for i in range(40)]
    looped = _cluster(seed=9, packed=packed)
    batched_ = _cluster(seed=9, packed=packed)
    cl_l = KVClient(looped, "c1", via="a")
    cl_b = KVClient(batched_, "c1", via="a")
    acks_l = {k: cl_l.put(k, f"v-{k}") for k in keys}
    acks_b = cl_b.put_many({k: (f"v-{k}", None) for k in keys})
    for k in keys:
        assert acks_l[k].clock == acks_b[k].clock, k
        assert acks_l[k].coordinator == acks_b[k].coordinator, k
        assert set(acks_l[k].replicated_to) == set(acks_b[k].replicated_to)
    looped.deliver_replication()
    batched_.deliver_replication()
    for n in NODES:
        for k in keys:
            assert looped.nodes[n].versions(k) == \
                batched_.nodes[n].versions(k), (n, k)
    # second round WITH contexts: read-modify-write via get_many/put_many
    ctxs_l = {k: cl_l.get(k, quorum=3) for k in keys}
    ctxs_b = cl_b.get_many(keys, quorum=3)
    for k in keys:
        cl_l.put(k, f"w-{k}", context=ctxs_l[k].context)
    cl_b.put_many({k: (f"w-{k}", ctxs_b[k].context) for k in keys})
    looped.deliver_replication()
    batched_.deliver_replication()
    for n in NODES:
        for k in keys:
            assert looped.nodes[n].versions(k) == \
                batched_.nodes[n].versions(k), (n, k)
            assert looped.get(k, via=n).values == (f"w-{k}",)


def test_put_many_duplicate_keys_rejected():
    c = _cluster(seed=1)
    store = c.nodes["a"].backend.packed
    with pytest.raises(ValueError):
        store.update_keys([("k", (), "v1", 1.0), ("k", (), "v2", 2.0)], "a")


def test_kvclient_unavailable_paths():
    net = SimNetwork(seed=12)
    c = KVCluster(NODES, DVV_MECHANISM, network=net)
    client = KVClient(c, "c2", via="a")
    # down proxy
    net.fail_node("a")
    with pytest.raises(Unavailable):
        client.get("k")
    with pytest.raises(Unavailable):
        client.put_many({"k": ("v", None)})
    net.recover_node("a")
    # read quorum unreachable
    net.partition({"a"}, set(NODES) - {"a"})
    with pytest.raises(Unavailable):
        client.get("k", quorum=4)
    # write quorum unreachable: durable at coordinator, then raises
    with pytest.raises(Unavailable):
        client.put_many({f"key{i}": (f"v{i}", None) for i in range(5)},
                        quorum=4)
    assert any(c.nodes["a"].versions(f"key{i}") for i in range(5))
    net.heal()


def test_put_many_admission_is_atomic():
    """If ANY key of a batch has no reachable coordinator, nothing at all
    is written (single-replica keys during a partition)."""
    c2 = KVCluster(("x", "y", "z"), DVV_MECHANISM, replication=1,
                   network=SimNetwork(seed=3))
    cl2 = KVClient(c2, "c3", via="x")
    keys = [f"p{i}" for i in range(12)]
    owners = {k: c2.replicas_for(k)[0] for k in keys}
    assert {"x"} < set(owners.values())   # some keys owned by x, some not
    c2.network.partition({"x"}, {"y", "z"})
    with pytest.raises(Unavailable):
        cl2.put_many({k: (f"v-{k}", None) for k in keys})
    for k in keys:                        # even x-owned keys: not written
        assert not c2.nodes[owners[k]].versions(k), k


# ---------------------------------------------------------------------------
# Gossip scheduling: seeded round-robin fanout + per-round budgets.
# ---------------------------------------------------------------------------

def _diverged(seed=21, nodes=tuple(f"n{i}" for i in range(6))):
    rng = random.Random(seed)
    c = KVCluster(nodes, DVV_MECHANISM, network=SimNetwork(seed=seed))
    for i in range(60):
        n = rng.choice(nodes)
        c.put(rng.choice(KEYS), f"v{i}", via=n, coordinator=n)
    c.network.queue.clear()      # drop replication: gossip must do the work
    return c


def test_fanout_rounds_converge_and_cycle_all_peers():
    c = _diverged()
    n = len(c.nodes)
    pushes = []
    for _ in range(3 * n):       # round-robin cycles every peer within n-1
        stats = c.delta_antientropy_round(fanout=1)
        assert len(stats) == n   # one push per node per round
        pushes.append(len(stats))
        if all(s.buckets_divergent == 0 for s in stats):
            break
    ref = c.nodes["n0"]
    for other in c.nodes.values():
        for k in KEYS:
            assert other.versions(k) == ref.versions(k), (other.node_id, k)


def test_fanout_schedule_is_deterministic():
    a, b = _diverged(seed=33), _diverged(seed=33)
    for _ in range(4):
        sa = a.delta_antientropy_round(fanout=2)
        sb = b.delta_antientropy_round(fanout=2)
        assert sa == sb
    for k in KEYS:
        assert a.nodes["n1"].versions(k) == b.nodes["n1"].versions(k)


def test_fanout_defaults_max_ranges_budget():
    c = _diverged(seed=5)
    c.delta_range_budget = 2
    stats = c.delta_antientropy_round(fanout=1)
    assert all(s.buckets_sent <= 2 for s in stats)
    # explicit max_ranges still wins
    stats = c.delta_antientropy_round(fanout=1, max_ranges=1)
    assert all(s.buckets_sent <= 1 for s in stats)
    # no fanout ⇒ all-pairs, uncapped (legacy behaviour)
    stats = c.delta_antientropy_round()
    assert len(stats) == len(c.nodes) * (len(c.nodes) - 1)


# ---------------------------------------------------------------------------
# Bucket→slot index: payload slicing stays exact through mutation.
# ---------------------------------------------------------------------------

def test_bucket_index_tracks_kills_compaction_and_growth():
    rng = np.random.default_rng(0)
    s = PackedVersionStore(n_buckets=256)
    for r in ("r0", "r1", "r2"):
        s.intern_replica(r)
    # enough keys to trigger digest-tree growth (and index rebuild)
    for i in range(1500):
        col = int(rng.integers(0, 3))
        vv = np.zeros(s.n_replicas, np.int32)
        vv[col] = int(rng.integers(0, 4))
        s.sync_key(f"key{i}", vv[None, :], np.asarray([col], np.int32),
                   np.asarray([vv[col] + 1], np.int32), [f"v{i}"])
    assert s.n_buckets > 256
    assert s.check_bucket_index()
    # overwrite a scattered subset (kills + inserts), then force compaction
    for i in range(0, 1500, 7):
        vv = np.full(s.n_replicas, 9, np.int32)
        s.sync_key(f"key{i}", vv[None, :], np.asarray([1], np.int32),
                   np.asarray([10], np.int32), [f"w{i}"])
    s.compact(force=True)
    assert s.check_bucket_index()
    # sliced payloads from the index equal explicit key selection
    from repro.store.packed import key_bucket
    from repro.store.replica import _as_object_payload
    buckets = sorted({int(key_bucket(k, s.n_buckets)) for k in s.keys[:40]})
    by_range = s.payload(key_ranges=buckets)
    want = [k for k in s.keys
            if key_bucket(k, s.n_buckets) in set(buckets) and s.key_slots(k)]
    assert _as_object_payload(by_range) == \
        _as_object_payload(s.payload(sorted(want)))
    # empty ranges produce an empty payload
    empty = [b for b in range(s.n_buckets) if not s._bucket_slots.get(b)]
    assert len(s.payload(key_ranges=empty[:5])) == 0


# ---------------------------------------------------------------------------
# Hypothesis fuzz (property lane; see pytest.ini markers).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @pytest.mark.property
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=100_000), st.booleans())
    def test_token_roundtrip_fuzzed(seed, packed):
        direct = _drive_tokens(seed, packed, roundtrip=False)
        viabytes = _drive_tokens(seed, packed, roundtrip=True)
        for n in NODES:
            for k in KEYS:
                assert direct.nodes[n].versions(k) == \
                    viabytes.nodes[n].versions(k), (seed, packed, n, k)
except ImportError:     # deterministic seeds above still run
    pass
