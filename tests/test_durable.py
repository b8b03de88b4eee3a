"""Durable segment log + crash-point-fuzzed warm restart (DESIGN.md §14).

Three layers, matching the recovery stack:

* **Codec + filesystem units** — the record framing is torn-tail-proof for
  *every* byte prefix and every single-byte corruption (exhaustive at this
  layer: this is where per-byte crash coverage lives, cheaply).  ``CrashFS``
  semantics: torn appends persist a prefix, interrupted atomic writes
  persist nothing.
* **Segment-log units** — sealing, snapshot compaction (manifest flip +
  orphan GC), on-disk torn-tail truncation, checksum verification.
* **Cluster warm restart** — ``restart_node`` rebuilds a crashed replica
  from disk and converges with ONE pull+push delta pass per peer; the
  membership controller re-admits an evicted node through the same path;
  and the crash-point fuzzer kills the writer mid-write and requires
  digest equality with an uncrashed run afterwards.

The cluster fuzz does not re-enumerate every byte: within one write extent
all interior kill offsets land in the same recovery class (append → one
torn record dropped; atomic → old content kept), and the codec layer
already proves per-byte tearing exhaustively.  Each extent is therefore
probed at its boundaries and midpoint — the tier-1 lane samples extents,
the ``slow`` lane sweeps all of them for both backends × shards ∈ {1, 4}.
"""
import os
import pickle
import random
import shutil
import tempfile

import pytest

from repro.core import DVV_MECHANISM
from repro.ckpt.atomic import atomic_write_bytes
from repro.store import (CrashFS, CrashPoint, GossipDriver, KVCluster,
                         LocalFS, MembershipController, SegmentLog,
                         cluster_converged)
from repro.store.wal import (REC_COMPACT, REC_EPOCH, REC_KILL, REC_UPDATE,
                             decode_records, encode_record)

pytestmark = pytest.mark.durable

KEYS = ["alpha", "beta", "gamma", "delta", "epsilon"]


@pytest.fixture
def tmp():
    d = tempfile.mkdtemp(prefix="durable-")
    yield d
    shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# Record codec: exhaustive per-byte torn-tail + corruption coverage.
# ---------------------------------------------------------------------------

def _sample_records():
    return [
        (REC_UPDATE, pickle.dumps(("alpha", 1), 4)),
        (REC_KILL, pickle.dumps("beta", 4)),
        (REC_EPOCH, pickle.dumps((3, ("a", "b")), 4)),
        (REC_UPDATE, b"x" * 100),
        (REC_COMPACT, b""),                    # zero-length body
    ]


def test_record_roundtrip():
    recs = _sample_records()
    buf = b"".join(encode_record(k, b) for k, b in recs)
    out, good = decode_records(buf)
    assert out == recs and good == len(buf)
    assert decode_records(b"") == ([], 0)


def test_torn_tail_every_prefix():
    """Cutting the stream at EVERY byte offset yields exactly the complete
    record prefix, with ``good_bytes`` at the preceding record boundary —
    the per-byte guarantee the cluster fuzz builds on."""
    recs = _sample_records()
    frames = [encode_record(k, b) for k, b in recs]
    buf = b"".join(frames)
    boundaries = [0]
    for f in frames:
        boundaries.append(boundaries[-1] + len(f))
    for cut in range(len(buf) + 1):
        n_complete = sum(1 for b in boundaries[1:] if b <= cut)
        out, good = decode_records(buf[:cut])
        assert out == recs[:n_complete]
        assert good == boundaries[n_complete]


def test_every_single_byte_corruption_stops_replay():
    """Flipping ANY one byte makes replay stop at (or before) the record
    containing it — never decode garbage past a corruption."""
    recs = _sample_records()
    frames = [encode_record(k, b) for k, b in recs]
    buf = bytearray(b"".join(frames))
    owner = []                                 # byte offset -> record index
    for i, f in enumerate(frames):
        owner.extend([i] * len(f))
    for pos in range(len(buf)):
        corrupt = bytearray(buf)
        corrupt[pos] ^= 0x5A
        out, good = decode_records(bytes(corrupt))
        assert len(out) <= owner[pos]
        assert out == recs[:len(out)]


# ---------------------------------------------------------------------------
# Filesystem layer: atomic helper + CrashFS semantics.
# ---------------------------------------------------------------------------

def test_atomic_write_replaces_and_leaves_no_temps(tmp):
    path = os.path.join(tmp, "blob")
    atomic_write_bytes(path, b"first")
    atomic_write_bytes(path, b"second")
    with open(path, "rb") as f:
        assert f.read() == b"second"
    assert os.listdir(tmp) == ["blob"]         # no stray temp files


def test_crashfs_append_keeps_affordable_prefix(tmp):
    fs = CrashFS(budget=10)
    path = os.path.join(tmp, "log")
    fs.append(path, b"0123456")
    with pytest.raises(CrashPoint):
        fs.append(path, b"abcdefg")            # only 3 bytes left
    with open(path, "rb") as f:
        assert f.read() == b"0123456abc"       # torn: prefix persisted
    assert fs.crashed
    for op in (lambda: fs.append(path, b"x"),
               lambda: fs.read(path),
               lambda: fs.write_atomic(path, b"x"),
               lambda: fs.remove(path)):
        with pytest.raises(CrashPoint):        # crashed fs stays crashed
            op()


def test_crashfs_atomic_write_is_all_or_nothing(tmp):
    fs = CrashFS(budget=8)
    path = os.path.join(tmp, "manifest")
    fs.write_atomic(path, b"old-data")         # exactly spends the budget
    with pytest.raises(CrashPoint):
        fs.write_atomic(path, b"new-data!")
    with open(path, "rb") as f:
        assert f.read() == b"old-data"         # target untouched


def test_crashfs_recording_mode_tracks_extents(tmp):
    fs = CrashFS(None)
    fs.append(os.path.join(tmp, "a"), b"12345")
    fs.write_atomic(os.path.join(tmp, "b"), b"678")
    assert [(op, s, e) for op, _, s, e in fs.extents] == \
        [("append", 0, 5), ("atomic", 5, 8)]
    assert fs.written == 8 and not fs.crashed


# ---------------------------------------------------------------------------
# SegmentLog: seal, snapshot compaction, torn-tail truncation on disk.
# ---------------------------------------------------------------------------

def _fill(log, n, size=40):
    for i in range(n):
        log.append_record(REC_UPDATE, f"rec-{i:04d}-".encode() + b"p" * size)


def test_seal_rolls_segments_and_checksums_them(tmp):
    log = SegmentLog(tmp, "n1", 0, seal_bytes=120)
    _fill(log, 7)
    assert len(log.segments) >= 2              # sealed at least twice
    for seg in log.segments:
        assert seg["records"] > 0 and len(seg["checksum"]) == 16
    snap, records, stats = SegmentLog(tmp, "n1", 0, seal_bytes=120).load()
    assert snap is None and len(records) == 7 and stats.torn_bytes == 0
    assert [b for _, b in records] == \
        [f"rec-{i:04d}-".encode() + b"p" * 40 for i in range(7)]


def test_snapshot_compacts_and_gcs_old_files(tmp):
    log = SegmentLog(tmp, "n1", 0, snapshot_every=5, seal_bytes=120)
    state = {"snapshot": b""}
    log.snapshot_source = lambda: state["snapshot"]
    for i in range(12):
        state["snapshot"] = f"state-after-{i}".encode()
        log.append_record(REC_UPDATE, f"rec-{i}".encode())
    assert log.snapshot_rec is not None
    files = set(os.listdir(log.dir))
    # exactly one snapshot blob survives; orphaned segments are GC'd
    assert sum(f.startswith("snap-") for f in files) == 1
    referenced = {log.snapshot_rec.file, log.active, SegmentLog.MANIFEST} \
        | {s["file"] for s in log.segments}
    assert files == referenced
    snap, records, _ = SegmentLog(tmp, "n1", 0, snapshot_every=5,
                                  seal_bytes=120).load()
    # the snapshot subsumes the prefix; the tail replays the rest
    assert snap == state["snapshot"] or (
        pickle.loads(records[-1][1]) if records[-1][0] == REC_COMPACT
        else True)
    replayed = [b for k, b in records if k == REC_UPDATE]
    assert snap.decode().startswith("state-after-")
    subsumed = int(snap.decode().rsplit("-", 1)[1])
    assert replayed == [f"rec-{i}".encode() for i in range(subsumed + 1, 12)]


def test_load_truncates_torn_tail_on_disk(tmp):
    log = SegmentLog(tmp, "n1", 0)
    _fill(log, 3)
    active = os.path.join(log.dir, log.active)
    with open(active, "ab") as f:              # simulate a torn append
        f.write(encode_record(REC_UPDATE, b"torn")[:-2])
    reopened = SegmentLog(tmp, "n1", 0)
    snap, records, stats = reopened.load()
    assert len(records) == 3 and stats.torn_bytes > 0
    # the truncation is durable: a second reopen sees a clean tail
    _, records2, stats2 = SegmentLog(tmp, "n1", 0).load()
    assert len(records2) == 3 and stats2.torn_bytes == 0


def test_load_rejects_corrupted_sealed_segment(tmp):
    log = SegmentLog(tmp, "n1", 0, seal_bytes=120)
    _fill(log, 6)
    seg_file = os.path.join(log.dir, log.segments[0]["file"])
    data = bytearray(open(seg_file, "rb").read())
    data[len(data) // 2] ^= 0xFF
    with open(seg_file, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(IOError, match="bad checksum"):
        SegmentLog(tmp, "n1", 0, seal_bytes=120).load()


# ---------------------------------------------------------------------------
# Cluster warm restart.
# ---------------------------------------------------------------------------

def _wal_cluster(tmp, packed, shards, fs=None, **kw):
    kw.setdefault("replication", 3)
    kw.setdefault("write_quorum", 2)
    return KVCluster(("a", "b", "c"), DVV_MECHANISM, packed=packed,
                     shards=shards, seed=7, wal_dir=tmp,
                     wal_snapshot_every=4, wal_seal_bytes=600,
                     wal_fs={"b": fs} if fs else None, **kw)


def _check_stores(c):
    for n in c.nodes.values():
        if n.is_packed:
            for st in n.shard_stores:
                st.check_digests()
                st.check_bucket_index()


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("shards", [1, 4])
def test_warm_restart_converges_with_divergence(tmp, packed, shards):
    """Crash b, let the survivors diverge, warm-restart b: the log replay
    plus ONE pull+push delta pass per peer restores digest equality."""
    c = _wal_cluster(tmp, packed, shards)
    for i in range(10):
        via = ("a", "b", "c")[i % 3]
        c.put(KEYS[i % len(KEYS)], f"v{i}", via=via, coordinator=via)
        c.deliver_replication()
    c.network.fail_node("b")
    c.wal["b"].detach()
    for i in range(5):                         # b misses these
        c.put(KEYS[i % len(KEYS)], f"miss{i}", via="a", coordinator="a")
        c.deliver_replication()
    c.network.recover_node("b")
    stats = c.restart_node("b")
    c.deliver_replication()
    assert stats                                # delta passes actually ran
    _check_stores(c)
    assert cluster_converged(c)


RECOVERY_NODES = tuple(f"n{i}" for i in range(5))
RECOVERY_KEYS = 240


def _outage(wal_dir, seed, frac):
    """A 5-node, 4-shard cluster takes a keyed load; ``n2`` crashes and the
    survivors rewrite ``frac`` of the keyspace (read-modify-writes) without
    it; then ``n2`` is reachable again."""
    c = KVCluster(RECOVERY_NODES, DVV_MECHANISM, packed=True, shards=4,
                  replication=3, write_quorum=2, seed=seed, wal_dir=wal_dir)
    rng = random.Random(seed * 31 + 5)
    for i in range(RECOVERY_KEYS):
        via = RECOVERY_NODES[rng.randrange(len(RECOVERY_NODES))]
        c.put(f"k{i:04d}", f"value-{i:04d}-" + "x" * 48, via=via,
              coordinator=via)
        if i % 8 == 7:
            c.deliver_replication()
    c.deliver_replication()
    for _ in range(3):
        c.delta_antientropy_round()
    c.network.fail_node("n2")
    c.wal["n2"].detach()
    for i in rng.sample(range(RECOVERY_KEYS), int(RECOVERY_KEYS * frac)):
        k = f"k{i:04d}"
        c.put(k, f"revised-{i:04d}-" + "y" * 48, via="n0", coordinator="n0",
              context=c.get(k, via="n0").context)
    c.deliver_replication()
    c.network.recover_node("n2")
    return c


def _wire(stats):
    return sum(s.payload_bytes + s.digest_bytes for s in stats)


@pytest.mark.parametrize("frac", [0.01, 0.05, 0.10])
def test_warm_restart_resyncs_5x_fewer_bytes_than_cold_bootstrap(tmp, frac):
    """The log makes recovery O(divergence), not O(store): at up to 10%
    of the keyspace rewritten during the outage, the warm restart (log
    replay, one pull+push delta pass per peer) moves at least 5x fewer
    resync bytes (payload + digest phases) than re-admitting the node
    empty and bootstrapping it, on twin clusters from one seed."""
    warm_c = _outage(f"{tmp}/warm", 0, frac)
    warm = _wire(warm_c.restart_node("n2"))
    cold_c = _outage(f"{tmp}/cold", 0, frac)
    cold_c.remove_node("n2", handoff=False)
    cold = _wire(cold_c.add_node("n2"))
    assert 0 < 5 * warm <= cold, (warm, cold)
    for c in (warm_c, cold_c):
        c.deliver_replication()
        c.delta_antientropy_round()
        assert cluster_converged(c)


@pytest.mark.parametrize("packed", [True, False])
def test_restart_pushes_unreplicated_coordinated_write(tmp, packed):
    """The WAL can be the ONLY surviving copy: b coordinates a write whose
    replication messages die with the crash.  Recovery must PUSH it back
    out — a pull-only resync would lose an acknowledged write."""
    c = _wal_cluster(tmp, packed, 1, write_quorum=1)
    c.put("alpha", "everywhere", via="a", coordinator="a")
    c.deliver_replication()
    c.put("alpha", "only-in-wal", via="b", coordinator="b",
          context=c.get("alpha", via="b").context)
    c.network.fail_node("b")                   # replication never delivered
    assert all("only-in-wal" not in {v.value for v in c.nodes[n]
               .versions("alpha")} for n in "ac")
    c.network.recover_node("b")
    c.restart_node("b")
    c.deliver_replication()
    assert cluster_converged(c)
    for n in "abc":
        assert {v.value for v in c.nodes[n].versions("alpha")} == \
            {"only-in-wal"}


def test_restart_bumps_incarnation_and_epoch(tmp):
    c = _wal_cluster(tmp, True, 1)
    inc, epoch = c.incarnation["b"], c.wal["b"].last_epoch
    c.restart_node("b")
    assert c.incarnation["b"] == inc + 1
    assert c.wal["b"].last_epoch > epoch


def test_restart_requires_wal(tmp):
    c = KVCluster(("a", "b"), DVV_MECHANISM, packed=True, seed=1)
    with pytest.raises(ValueError, match="durable log"):
        c.restart_node("b")


@pytest.mark.parametrize("packed", [True, False])
def test_controller_readmits_evicted_node_via_warm_restart(tmp, packed):
    """The closed loop: crash → accrual eviction → recovery → re-admission
    through ``restart_node`` (log replay + delta), NOT the cold full-payload
    bootstrap."""
    c = _wal_cluster(tmp, packed, 2, replication=2, write_quorum=1)
    driver = GossipDriver(c, period=5.0, seed=3)
    mem = MembershipController(c, period=5.0, seed=3)
    bootstraps = []
    real = c.bootstrap_node
    c.bootstrap_node = lambda *a, **k: (bootstraps.append(a),
                                        real(*a, **k))[1]
    for i in range(8):
        c.put(KEYS[i % len(KEYS)], f"v{i}", via="a", coordinator="a")
    driver.run_for(30.0)
    c.network.fail_node("b")
    driver.run_for(300.0)
    assert "b" not in c.nodes and mem.evictions == 1
    c.network.recover_node("b")
    driver.run_for(300.0)
    c.deliver_replication()
    assert "b" in c.nodes and mem.readmissions == 1
    assert not bootstraps                      # warm path, no cold bootstrap
    _check_stores(c)
    assert cluster_converged(c)
    for i in range(8):
        assert {v.value for v in c.nodes["b"].versions(KEYS[i % len(KEYS)])} \
            == {v.value for v in c.nodes["a"].versions(KEYS[i % len(KEYS)])}


# ---------------------------------------------------------------------------
# Crash-point fuzz: kill the writer mid-write, restart, demand equality.
# ---------------------------------------------------------------------------

def _fuzz_schedule(c):
    for i in range(10):
        via = ("a", "b", "c")[i % 3]
        c.put(KEYS[i % len(KEYS)], f"v{i}", via=via, coordinator=via)
        c.deliver_replication()


def _record_extents(packed, shards):
    """Recording pass: run the schedule uncrashed, return b's write extents
    relative to the post-boot baseline."""
    tmp = tempfile.mkdtemp(prefix="durable-rec-")
    try:
        fs = CrashFS(None)
        c = _wal_cluster(tmp, packed, shards, fs=fs)
        base = fs.written
        _fuzz_schedule(c)
        return [(s - base, e - base) for _, _, s, e in fs.extents
                if e > base], fs.written - base
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _fuzz_once(packed, shards, offset):
    """Boot with a byte budget, run until the crash (if it fires), then
    warm-restart b and demand digest equality with the survivors."""
    tmp = tempfile.mkdtemp(prefix="durable-fuzz-")
    try:
        fs = CrashFS(None)
        c = _wal_cluster(tmp, packed, shards, fs=fs)
        fs.budget = fs.written + offset        # arm AFTER the boot epoch
        try:
            _fuzz_schedule(c)
        except CrashPoint:
            pass
        c.network.fail_node("b")
        c.wal["b"].detach()
        for i in range(4):                     # divergence while b is down
            c.put(KEYS[i % len(KEYS)], f"miss{i}", via="a", coordinator="a")
            c.deliver_replication()
        c.network.recover_node("b")
        c.wal["b"].set_fs(LocalFS())           # fresh process, same bytes
        c.restart_node("b")
        c.deliver_replication()
        _check_stores(c)
        assert cluster_converged(c), \
            f"diverged: packed={packed} shards={shards} offset={offset}"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _boundary_offsets(extents, total, *, stride=1):
    """The distinct crash classes: extent start (nothing written), first
    byte (minimal tear), midpoint, last-but-one (maximal tear), plus the
    uncrashed run.  ``stride`` subsamples extents for the tier-1 lane."""
    offs = set()
    for s, e in extents[::stride]:
        offs.update(x for x in (s, s + 1, (s + e) // 2, e - 1) if s <= x < e)
    offs.add(total + 1)                        # budget never reached
    return sorted(offs)


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("shards", [1, 4])
def test_crash_fuzz_sampled_extents(packed, shards):
    extents, total = _record_extents(packed, shards)
    for off in _boundary_offsets(extents, total, stride=4):
        _fuzz_once(packed, shards, off)


@pytest.mark.slow
@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("shards", [1, 4])
def test_crash_fuzz_every_extent(packed, shards):
    """The nightly sweep: every write extent of the recorded schedule, all
    four crash classes each."""
    extents, total = _record_extents(packed, shards)
    for off in _boundary_offsets(extents, total):
        _fuzz_once(packed, shards, off)
