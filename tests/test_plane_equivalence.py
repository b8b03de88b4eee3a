"""The kernel plane equals the numpy plane at every entry point that takes
``use_kernel``.

Twin clusters are built from one seed and driven through the same calls,
one with ``use_kernel=True`` (every clock sweep on the DVV Pallas kernels:
compiled on a TPU, interpreted on the CPU) and one with ``use_kernel=False``
(the numpy reference plane).  The twins must return equal results — values,
contexts, acks, anti-entropy stats — and end in equal packed state: digest
and value roots per shard store, every key's versions on every replica, and
the replication still in flight.

Each entry point runs on 1 and on 8 shards, and with two sibling widths:
at most four siblings a key, or one hot key written blind 64 times across
its replicas, which takes both kernels past K = 32 (the K = 64 bucket the
``cart.shared`` benchmark cell runs).
"""
from __future__ import annotations

import random

import pytest

import repro.kernels.dvv_ops as dvv_ops
from repro.core import DVV_MECHANISM
from repro.store import KVCluster, OpScheduler, SimNetwork
from repro.store.bulk import bulk_receive_antientropy, bulk_sync

SEED = 13
NODES = tuple(f"n{i}" for i in range(5))
DCS = {"east": ("e0", "e1", "e2"), "west": ("w0", "w1", "w2")}
KEYS = tuple(f"k{i:02d}" for i in range(24))
HOT = "cart"
HOT_SIBLINGS = 64

SHARDS = pytest.mark.parametrize("shards", [1, 8])
WIDTH = pytest.mark.parametrize("hot", [False, True], ids=["few", "hot"])


def keys_of(hot: bool):
    return KEYS + (HOT,) if hot else KEYS


def scenario(shards: int, hot: bool, *, datacenters=None,
             **kw) -> KVCluster:
    """A loaded cluster whose replicas disagree: every key written once and
    replicated, then concurrent blind writes through random coordinators
    (three on each of eight keys, or ``HOT_SIBLINGS`` on the hot key), of
    which half the replication arrives and the rest is lost."""
    nodes = (NODES if datacenters is None
             else tuple(n for ns in datacenters.values() for n in ns))
    if datacenters is None:
        kw.update(replication=3, read_quorum=2, write_quorum=2)
    c = KVCluster(nodes, DVV_MECHANISM, shards=shards, seed=SEED,
                  network=SimNetwork(seed=SEED, jitter=0.0),
                  datacenters=datacenters, **kw)
    c.put_many({k: (f"base-{k}", None) for k in keys_of(hot)}, via=nodes[0])
    c.deliver_replication()
    rng = random.Random(SEED)
    writes = [(k, j) for k in KEYS[:8] for j in range(3)]
    if hot:
        writes += [(HOT, j) for j in range(HOT_SIBLINGS)]
    for key, j in writes:
        node = rng.choice(nodes)
        c.put(key, f"{key}-{j}", via=node, coordinator=node)
    c.deliver_replication(max_messages=c.network.pending() // 2)
    c.network.queue.clear()
    return c


def twins(shards: int, hot: bool, **kw):
    """(kernel twin, numpy twin) of one scenario."""
    return scenario(shards, hot, **kw), scenario(shards, hot, **kw)


def assert_same_state(ker: KVCluster, ref: KVCluster, hot: bool) -> None:
    assert list(ker.nodes) == list(ref.nodes)
    for nid in ker.nodes:
        a, b = ker.nodes[nid], ref.nodes[nid]
        assert ([(st.digest_root(), st.value_root())
                 for st in a.backend.stores]
                == [(st.digest_root(), st.value_root())
                    for st in b.backend.stores]), nid
        for k in keys_of(hot):
            assert a.versions(k) == b.versions(k), (nid, k)
    assert list(ker.network.queue) == list(ref.network.queue)


@pytest.fixture
def kernel_ks(monkeypatch):
    """The K of every tensor the kernel front ends are handed.  The store
    looks both bucket caches up on the package at call time."""
    seen = []
    for attr in ("dvv_sync_mask_bucketed", "dvv_read_sweep_bucketed"):
        inner = getattr(dvv_ops, attr)

        def record(vvs, *rest, _inner=inner):
            seen.append(vvs.shape[1])
            return _inner(vvs, *rest)

        monkeypatch.setattr(dvv_ops, attr, record)
    return seen


def twin_calls(kernel_ks, hot: bool, fn):
    """``fn(use_kernel)`` on the kernel plane, then on the numpy plane.
    The kernel run must reach the kernels, past K = 32 on the hot key;
    the numpy run must not reach them at all."""
    got = fn(True)
    assert kernel_ks, "the kernel plane never launched"
    if hot:
        assert max(kernel_ks) > 32, kernel_ks
    n = len(kernel_ks)
    want = fn(False)
    assert len(kernel_ks) == n, "the numpy plane launched a kernel"
    return got, want


# ---------------------------------------------------------------------------
# The served path: KVClient sessions on an OpScheduler.
# ---------------------------------------------------------------------------

def serve(c: KVCluster, hot: bool, use_kernel: bool):
    """Sessions read, then write back with the read's context, across
    several flushes; returns every op's result and the scheduler's stats."""
    sch = OpScheduler(c, via="n0", max_batch=64, max_delay=2.0,
                      read_repair=True, use_kernel=use_kernel)
    sessions = [sch.session(f"s{i}", read_quorum=2, write_quorum=2)
                for i in range(6)]
    keys = keys_of(hot)
    out = []
    for r in range(3):
        picks = [(s, [keys[(3 * i + r) % len(keys)],
                      keys[(5 * i + 2 * r + 1) % len(keys)]]
                  + ([HOT] if hot and i % 2 == r % 2 else []))
                 for i, s in enumerate(sessions)]
        gets = [(s, ks, s.submit_get(ks)) for s, ks in picks]
        sch.flush()
        reads = [(s, op.result()) for s, _, op in gets]
        puts = [s.submit_put({k: (f"{s.client_id}-{r}", res[k].context)
                              for k in res})
                for s, res in reads]
        sch.flush()
        c.deliver_replication(max_messages=c.network.pending() // 2)
        out += [res for _, res in reads] + [op.result() for op in puts]
    return out, sch.stats()


@SHARDS
@WIDTH
def test_scheduler_sessions(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    got, want = twin_calls(kernel_ks, hot,
                           lambda uk: serve(ker if uk else ref, hot, uk))
    assert got == want
    assert_same_state(ker, ref, hot)


# ---------------------------------------------------------------------------
# KVCluster's batched read and write planes.
# ---------------------------------------------------------------------------

@SHARDS
@WIDTH
def test_get_many(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    keys = list(keys_of(hot))

    def read(uk):
        c = ker if uk else ref
        return [c.get_many(keys, via=via, quorum=q, repair=True,
                           use_kernel=uk)
                for via, q in (("n1", 2), ("n3", 3))]

    got, want = twin_calls(kernel_ks, hot, read)
    assert got == want
    for a, b in zip(got, want):
        for k in keys:
            assert a[k].context.to_bytes() == b[k].context.to_bytes()
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_put_many(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    keys = list(keys_of(hot))
    reads = ref.get_many(keys, via="n2", quorum=2)
    assert reads == ker.get_many(keys, via="n2", quorum=2)

    def write(uk):
        c = ker if uk else ref
        # a blind write (every sibling survives it), then writes with the
        # read's context (they supersede what the read saw)
        blind = c.put_many({k: (f"blind-{k}", None) for k in keys[::3]},
                           via="n2", quorum=2, use_kernel=uk)
        ctx = c.put_many({k: (f"rmw-{k}", reads[k].context) for k in keys},
                         via="n4", quorum=2, client_id="w",
                         client_counter=1, use_kernel=uk)
        return blind, ctx

    got, want = twin_calls(kernel_ks, hot, write)
    assert got == want
    assert_same_state(ker, ref, hot)


# ---------------------------------------------------------------------------
# Anti-entropy: delta pushes, rounds and gossip ticks.
# ---------------------------------------------------------------------------

@SHARDS
@WIDTH
def test_delta_antientropy(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)

    def push(uk):
        c = ker if uk else ref
        return [c.delta_antientropy(a, b, use_kernel=uk)
                for a, b in (("n0", "n1"), ("n2", "n1"), ("n1", "n4"))]

    got, want = twin_calls(kernel_ks, hot, push)
    assert got == want
    assert any(st.changed for st in got)
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_delta_antientropy_round(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    got, want = twin_calls(
        kernel_ks, hot,
        lambda uk: (ker if uk else ref).delta_antientropy_round(
            use_kernel=uk))
    assert got == want
    assert any(st.changed for st in got)
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_gossip_tick(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)

    def tick(uk):
        c = ker if uk else ref
        return [c.gossip_tick(n, fanout=2, use_kernel=uk) for n in NODES]

    got, want = twin_calls(kernel_ks, hot, tick)
    assert got == want
    assert any(st.changed for pairs in got for _, st in pairs)
    assert_same_state(ker, ref, hot)


# ---------------------------------------------------------------------------
# Membership and recovery: warm restart, join, bootstrap.
# ---------------------------------------------------------------------------

def crashed_twin(tmp_path, name: str, shards: int, hot: bool):
    """The scenario on a logged cluster; one of the hot key's replicas
    crashes and the cluster keeps writing without it (read-modify-writes,
    and blind adds to the hot key), then the node comes back.  Returns the
    cluster and the node."""
    c = scenario(shards, hot, wal_dir=str(tmp_path / name))
    victim = c.replicas_for(HOT)[1]
    live = [n for n in NODES if n != victim]
    c.network.fail_node(victim)
    c.wal[victim].detach()
    for i, k in enumerate(KEYS[::2]):
        via = live[i % 2]
        c.put(k, f"outage-{k}", via=via, coordinator=via,
              context=c.get(k, via=via).context)
    for j in range(8 if hot else 0):
        via = live[j % 2]
        c.put(HOT, f"outage-{HOT}-{j}", via=via, coordinator=via)
    c.deliver_replication()
    c.network.recover_node(victim)
    return c, victim


@SHARDS
@WIDTH
def test_restart_node(kernel_ks, tmp_path, shards, hot):
    ker, victim = crashed_twin(tmp_path, "kernel", shards, hot)
    ref, _ = crashed_twin(tmp_path, "numpy", shards, hot)
    got, want = twin_calls(
        kernel_ks, hot,
        lambda uk: (ker if uk else ref).restart_node(victim, use_kernel=uk))
    assert got == want
    assert any(st.changed for st in got)
    assert ker.last_replay == ref.last_replay
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_add_node(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    got, want = twin_calls(
        kernel_ks, hot,
        lambda uk: (ker if uk else ref).add_node("n5", use_kernel=uk))
    assert got == want
    assert any(st.changed for st in got)
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_bootstrap_node(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)
    got, want = twin_calls(
        kernel_ks, hot,
        lambda uk: (ker if uk else ref).bootstrap_node("n4", use_kernel=uk))
    assert got == want
    assert any(st.changed for st in got)
    assert_same_state(ker, ref, hot)


# ---------------------------------------------------------------------------
# Bulk entry points: a whole payload into a node, and object-level sync.
# ---------------------------------------------------------------------------

@SHARDS
@WIDTH
def test_bulk_receive_antientropy(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot)

    def receive(uk):
        c = ker if uk else ref
        return [bulk_receive_antientropy(
            c.nodes[dst], c.nodes[src].antientropy_payload(), use_kernel=uk)
            for src, dst in (("n0", "n3"), ("n1", "n3"), ("n3", "n4"))]

    got, want = twin_calls(kernel_ks, hot, receive)
    assert got == want and any(got)
    assert_same_state(ker, ref, hot)


@SHARDS
@WIDTH
def test_bulk_sync(kernel_ks, shards, hot):
    c = scenario(shards, hot)
    keys = keys_of(hot)

    def versions_at(node):
        return {k: v for k in keys if (v := c.nodes[node].versions(k))}

    local, incoming = versions_at("n0"), versions_at("n1")
    got, want = twin_calls(
        kernel_ks, hot,
        lambda uk: bulk_sync(local, incoming, use_kernel=uk))
    assert got == want
    assert set(got) == set(local) | set(incoming)
    if hot:
        assert len(got[HOT]) > 32


# ---------------------------------------------------------------------------
# The geo tier: one WAN shipping tick between datacenters.
# ---------------------------------------------------------------------------

@SHARDS
@WIDTH
def test_wan_tick(kernel_ks, shards, hot):
    ker, ref = twins(shards, hot, datacenters=DCS)

    def ship(uk):
        c = ker if uk else ref
        return [c.geo.wan_tick(a, b, use_kernel=uk)
                for a, b in (("east", "west"), ("west", "east"))]

    got, want = twin_calls(kernel_ks, hot, ship)
    assert got == want
    assert any(st.changed for stats, _ in got for st in stats)
    assert_same_state(ker, ref, hot)
