"""A replication pump applied once per destination store equals the same
messages applied one at a time.

``KVCluster.deliver_replication`` takes a whole pump from the fabric
(``SimNetwork.take``) and has each packed node join its messages' parts
per shard store (``join_payloads``) and apply each store once.  The
reference below is the fabric's message-at-a-time loop: one scan, one
removal and one ``receive_antientropy`` per message.  Twin clusters run
the same seeded schedules (hot keys, sibling sets past 32, duplicated and
reordered sends, a destination down mid-run, ``until`` and
``max_messages`` cuts) and must agree after every pump on each store's
version sets, digest and value roots and index checks, and on the
fabric's clock and counts.  Slot order may differ, so raw arrays are not
compared; nor is the digest tree's width, which grows on the live count a
store passes through.
"""
import random

import numpy as np
import pytest

from repro import trace
from repro.core import DVV_MECHANISM
from repro.store import KVCluster, PackedPayload, PackedVersionStore, \
    SimNetwork, Unavailable
from repro.store.packed import NO_DOT, join_payloads

NODES = ("a", "b", "c", "d", "e")
HOT = ("h0",)
KEYS = HOT + tuple(f"k{i}" for i in range(22))


def per_message_pump(c: KVCluster, until=None, max_messages=None) -> int:
    """The reference delivery: the fabric's message-at-a-time loop."""
    net = c.network
    count = 0
    while max_messages is None or count < max_messages:
        ready = [m for m in net.queue
                 if (until is None or m.deliver_at <= until)
                 and net.reachable(m.src, m.dst)]
        if not ready:
            break
        msg = min(ready, key=lambda m: (m.deliver_at, m.src, m.dst))
        net.queue = [m for m in net.queue if m is not msg]
        net.now = max(net.now, msg.deliver_at)
        net.delivered += 1
        count += 1
        c.nodes[msg.dst].receive_antientropy(msg.payload[1])
        if c.geo is not None:
            c.geo.note_receive(msg.dst, msg.payload)
    return count


def joined_pump(c: KVCluster, until=None, max_messages=None) -> int:
    return c.deliver_replication(max_messages=max_messages, until=until)


def _assert_same(c1: KVCluster, c2: KVCluster, keys, tag) -> None:
    n1, n2 = c1.network, c2.network
    assert (n1.now, n1.delivered, n1.pending()) == \
        (n2.now, n2.delivered, n2.pending()), tag
    for n in c1.nodes:
        for k in keys:
            assert c1.nodes[n].versions(k) == c2.nodes[n].versions(k), \
                (tag, n, k)
        if not c1.nodes[n].is_packed:
            continue
        for s1, s2 in zip(c1.nodes[n].shard_stores, c2.nodes[n].shard_stores):
            assert s1.digest_root() == s2.digest_root(), (tag, n)
            assert s1.value_root() == s2.value_root(), (tag, n)
            for st in (s1, s2):
                assert st.check_digests(), (tag, n)
                assert st.check_bucket_index(), (tag, n)


def _schedule(c: KVCluster, seed: int, pump, check, *, ops: int = 200):
    """One seeded schedule; ``check(tag)`` runs after every pump.  Returns
    what the run went through: the widest sibling set, and whether a pump
    left messages queued for a down destination."""
    rng = random.Random(seed)
    net = c.network
    contexts = {}
    widest, held_back = 0, False
    for i in range(ops):
        node = rng.choice(NODES)
        p = rng.random()
        try:
            if p < 0.35:                    # blind write: one more sibling
                key = rng.choice(HOT) if rng.random() < 0.8 \
                    else rng.choice(KEYS)
                c.put(key, f"b{i}", via=node, coordinator=node)
            elif p < 0.45:
                key = rng.choice(KEYS)
                contexts[key] = c.get(key, via=node).context
            elif p < 0.60:
                key = rng.choice(KEYS)
                c.put(key, f"w{i}", context=contexts.get(key), via=node)
            elif p < 0.70:
                ks = rng.sample(KEYS, 6) + [rng.choice(HOT)]
                c.put_many({k: (f"m{i}-{k}", contexts.get(k)) for k in ks},
                           via=node)
            elif p < 0.90:
                until = None if rng.random() < 0.5 \
                    else net.now + rng.random() * 2.0
                cut = None if rng.random() < 0.6 else rng.randrange(0, 12)
                pump(c, until=until, max_messages=cut)
                held_back |= bool(net.down) and any(
                    m.dst in net.down for m in net.queue)
                check(i)
            elif p < 0.95:
                net.fail_node(rng.choice(NODES))
            else:
                for n in list(net.down):
                    net.recover_node(n)
        except Unavailable:
            pass
        net.advance(rng.random())
        widest = max(widest, max(len(c.nodes[n].versions(k))
                                 for n in NODES for k in HOT))
    for n in list(net.down):
        net.recover_node(n)
    pump(c)
    check("end")
    return widest, held_back


def _cluster(seed, shards, faults, packed=True):
    net = SimNetwork(seed=seed)
    if faults:
        net.set_duplication(0.3)
        net.set_reorder(0.3, spread=3.0)
    return KVCluster(NODES, DVV_MECHANISM, replication=3, shards=shards,
                     network=net, seed=seed, packed=packed)


@pytest.fixture
def counters():
    trace.reset()
    trace.enable()
    yield lambda: trace.snapshot()["counters"]
    trace.disable()
    trace.reset()


@pytest.mark.parametrize("seed,shards,faults", [
    (0, 1, False), (1, 4, False), (2, 4, True), (3, 8, True), (4, 1, True)])
def test_joined_pump_equals_per_message_delivery(seed, shards, faults,
                                                 counters):
    c1 = _cluster(seed, shards, faults)
    c2 = _cluster(seed, shards, faults)
    runs = []

    def twin_pump(c, until=None, max_messages=None):
        # drive both twins through the same pump; ``c`` is c1
        runs.append((joined_pump(c1, until, max_messages),
                     per_message_pump(c2, until, max_messages)))

    def check(tag):
        assert runs[-1][0] == runs[-1][1], tag
        _assert_same(c1, c2, KEYS, tag)

    # the twins' own ops run in lockstep: replay the schedule on c2 by
    # driving c1 and mirroring every call onto c2
    widest, held_back = _schedule(_Mirror(c1, c2), seed, twin_pump, check)
    got = counters()
    # the schedule exercised what the join must get right
    assert widest > 32
    assert held_back
    assert got[trace.REPLICA_APPLY_PARTS] > got[trace.REPLICA_APPLY_STORES]
    if faults:
        assert c1.network.duplicated and c1.network.reordered


class _Mirror:
    """Forwards every client call to both twins and returns the first
    twin's result; ``network``/``nodes``/``geo`` are the first twin's."""

    def __init__(self, c1: KVCluster, c2: KVCluster):
        self._c1, self._c2 = c1, c2
        self.network = _MirrorNet(c1.network, c2.network)
        self.nodes = c1.nodes
        self.geo = c1.geo

    def _both(self, name, *a, **kw):
        err = None
        try:
            out = getattr(self._c1, name)(*a, **kw)
        except Unavailable as e:
            err = e
        try:
            getattr(self._c2, name)(*a, **kw)
        except Unavailable:
            assert err is not None, name
        else:
            assert err is None, name
        if err is not None:
            raise err
        return out

    def put(self, *a, **kw):
        return self._both("put", *a, **kw)

    def put_many(self, *a, **kw):
        return self._both("put_many", *a, **kw)

    def get(self, *a, **kw):
        return self._both("get", *a, **kw)


class _MirrorNet:
    def __init__(self, n1: SimNetwork, n2: SimNetwork):
        self._n1, self._n2 = n1, n2

    def __getattr__(self, name):
        return getattr(self._n1, name)

    def fail_node(self, n):
        self._n1.fail_node(n)
        self._n2.fail_node(n)

    def recover_node(self, n):
        self._n1.recover_node(n)
        self._n2.recover_node(n)

    def advance(self, dt):
        self._n1.advance(dt)
        self._n2.advance(dt)


def test_object_backend_pump_applies_message_by_message(counters):
    c1 = _cluster(5, 1, True, packed=False)
    c2 = _cluster(5, 1, True, packed=False)

    def twin_pump(c, until=None, max_messages=None):
        assert joined_pump(c1, until, max_messages) == \
            per_message_pump(c2, until, max_messages)

    _schedule(_Mirror(c1, c2), 5, twin_pump,
              lambda tag: _assert_same(c1, c2, KEYS, tag), ops=80)
    assert trace.REPLICA_APPLY_STORES not in counters()


DCS = {"east": ("e0", "e1", "e2"), "west": ("w0", "w1", "w2")}


def test_geo_shadows_match_through_the_per_message_path(counters):
    """Stable shadows must see every intermediate live set, so a geo
    cluster's stores apply message by message: same shadows, same sets."""
    def geo(seed):
        return KVCluster(DCS["east"] + DCS["west"], DVV_MECHANISM,
                         network=SimNetwork(seed=seed), seed=seed, shards=2,
                         datacenters=DCS)

    g1, g2 = geo(8), geo(8)
    rng = random.Random(8)
    keys = [f"g{i}" for i in range(4)]
    for i in range(60):
        key, node = rng.choice(keys), rng.choice(DCS["east"])
        for g in (g1, g2):
            ctx = g.get(key, via=node).context if i % 3 else None
            g.put(key, f"v{i}", ctx, via=node)
        if i % 7 == 6:
            joined_pump(g1)
            per_message_pump(g2)
            for g in (g1, g2):
                g.geo.wan_round()
    joined_pump(g1)
    per_message_pump(g2)
    _assert_same(g1, g2, keys, "geo")
    assert g1.geo.shadow == g2.geo.shadow
    assert any(ks for ks in g1.geo.shadow.values())
    assert trace.REPLICA_APPLY_STORES not in counters()


# ---------------------------------------------------------------------------
# The joiner.
# ---------------------------------------------------------------------------

def _payload(replicas, rows):
    """``rows``: (key, {replica: counter}, dot or None, value, wall)."""
    keys = tuple(dict.fromkeys(r[0] for r in rows))
    vv = np.zeros((len(rows), len(replicas)), np.int32)
    did = np.full(len(rows), NO_DOT, np.int32)
    dn = np.zeros(len(rows), np.int32)
    for i, (_, counters_, dot, _, _) in enumerate(rows):
        for r, n in counters_.items():
            vv[i, replicas.index(r)] = n
        if dot is not None:
            did[i], dn[i] = replicas.index(dot[0]), dot[1]
    return PackedPayload(
        tuple(replicas), keys, vv, did, dn,
        np.asarray([keys.index(r[0]) for r in rows], np.int32),
        tuple(r[3] for r in rows), np.asarray([r[4] for r in rows]))


def _state(st: PackedVersionStore, keys):
    return ({k: st.versions(k) for k in keys}, st.digest_root(),
            st.value_root())


def test_join_of_overlapping_payloads_applies_as_each_in_turn():
    p1 = _payload(["a", "b"], [
        ("x", {"a": 1}, ("b", 1), "x-b1", 2.0),
        ("y", {}, ("a", 2), "y-a2", 3.0),
        ("x", {}, ("a", 3), "x-a3", 4.0)])
    # another universe and order; ``x`` again, and an exact duplicate of
    # p1's first row under other values: the first copy must be kept
    p2 = _payload(["c", "b", "a"], [
        ("z", {"c": 1}, None, "z-c1", 1.0),
        ("x", {"a": 1}, ("b", 1), "x-b1-again", 9.0),
        ("x", {"a": 3, "b": 1}, ("c", 2), "x-c2", 5.0)])
    p3 = _payload(["b"], [("y", {}, ("b", 4), "y-b4", 6.0),
                          ("x", {}, ("b", 2), "x-b2", 7.0)])
    joined = join_payloads([p1, p2, p3])
    assert joined.keys == ("x", "y", "z")
    assert joined.replica_ids == ("a", "b", "c")
    assert len(joined) == len(p1) + len(p2) + len(p3) - 1
    assert "x-b1-again" not in joined.values and "x-b1" in joined.values

    seed = _payload(["a", "b"], [("x", {"a": 1}, None, "x-a1", 0.5),
                                 ("y", {}, ("b", 3), "y-b3", 0.5)])
    in_turn, once = PackedVersionStore(), PackedVersionStore()
    for st in (in_turn, once):
        st.apply_payload(seed)
    for p in (p1, p2, p3):
        in_turn.apply_payload(p)
    once.apply_payload(joined)
    assert _state(in_turn, "xyz") == _state(once, "xyz")
    assert {v.value for v in once.versions("x")} == {"x-b2", "x-c2"}
    assert once.check_digests() and once.check_bucket_index()

    # of two equal clocks the first copy is kept, as an apply in turn does
    q1 = _payload(["b"], [("q", {}, ("b", 1), "first", 1.0)])
    q2 = _payload(["b"], [("q", {}, ("b", 1), "second", 2.0)])
    assert join_payloads([q1, q2]).values == ("first",)
    in_turn, once = PackedVersionStore(), PackedVersionStore()
    in_turn.apply_payload(q1)
    in_turn.apply_payload(q2)
    once.apply_payload(join_payloads([q1, q2]))
    assert _state(in_turn, "q") == _state(once, "q")
    assert [v.value for v in once.versions("q")] == ["first"]


def test_join_of_one_payload_is_that_payload():
    p = _payload(["a"], [("x", {}, ("a", 1), "v", 1.0)])
    assert join_payloads([p]) is p
