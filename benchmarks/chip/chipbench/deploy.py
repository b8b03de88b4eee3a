"""Deployments: one JSON file per configuration, built into a served cluster.

A configuration file states the cluster (nodes, ``n_val``, R, W, shards,
proxy, clock mechanism), the coalescing scheduler's flush policy, the
simulated links, the record count and shape, and the guarantees the
deployment gives.  The record generator is a copy of ``chip_smoke.py``'s
YCSB generator, kept here so that a change to the program cannot change
the yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

#: Records per ``put_many`` call while loading.
LOAD_CHUNK = 65536


def read_config(path: Path) -> Dict[str, Any]:
    cfg = json.loads(Path(path).read_text())
    for key in ("deployment", "scheduler", "network", "records", "fields",
                "field_bytes"):
        if key not in cfg:
            raise ValueError(f"{path}: configuration has no {key!r}")
    return cfg


def record_bytes(cfg: Dict[str, Any]) -> int:
    return int(cfg["fields"]) * int(cfg["field_bytes"])


def key_name(i: int) -> str:
    return f"k{i}"


def key_index(key: str) -> int:
    return int(key[1:])


def make_records(n: int, nbytes: int, seed: int) -> List[str]:
    """``n`` YCSB records of ``nbytes`` random lowercase letters each."""
    rng = np.random.default_rng(seed)
    out: List[str] = []
    for s in range(0, n, LOAD_CHUNK):
        raw = rng.integers(ord("a"), ord("z") + 1, dtype=np.uint8,
                           size=(min(LOAD_CHUNK, n - s), nbytes))
        out.extend(row.tobytes().decode("ascii") for row in raw)
    return out


def mechanism_of(name: str):
    from repro.core import DVV_MECHANISM, VV_SERVER_MECHANISM
    table = {"dvv": DVV_MECHANISM, "vv_server": VV_SERVER_MECHANISM}
    if name not in table:
        raise ValueError(f"unknown clock mechanism {name!r}")
    return table[name]


def build_cluster(cfg: Dict[str, Any], seed: int, *,
                  mechanism: Optional[str] = None):
    """A fresh in-memory cluster as the configuration states it."""
    from repro.store import KVCluster, SimNetwork

    d = cfg["deployment"]
    if d.get("wal_dir") is not None:
        raise ValueError("only the in-memory backend is benchmarked here")
    net = cfg["network"]
    network = SimNetwork(seed=seed,
                         base_latency=float(net["base_latency_ticks"]),
                         jitter=float(net["jitter_ticks"]))
    return KVCluster(
        tuple(f"n{i}" for i in range(int(d["nodes"]))),
        mechanism_of(mechanism or d["mechanism"]),
        replication=int(d["n_val"]), read_quorum=int(d["r"]),
        write_quorum=int(d["w"]), shards=int(d["shards"]),
        vnodes=int(d["vnodes"]), seed=seed, network=network)


def dot_of(clock) -> Optional[Tuple[str, int]]:
    """The event a write minted: the one component of its DVV with a dot;
    ``None`` for a clock of another mechanism."""
    for r, _, n in getattr(clock, "components", ()):
        if n:
            return r, n
    return None


def load(cluster, records: List[str], via: str
         ) -> List[Optional[Tuple[str, int]]]:
    """Write every record once, with no context, on the host's numpy plane,
    and deliver all replication.  Returns the dot each key's load write
    minted, indexed like ``records`` (equal dots share one tuple)."""
    dots: List[Optional[Tuple[str, int]]] = []
    shared: Dict[Tuple[str, int], Tuple[str, int]] = {}
    for s in range(0, len(records), LOAD_CHUNK):
        chunk = records[s:s + LOAD_CHUNK]
        acks = cluster.put_many(
            {key_name(s + i): (v, None) for i, v in enumerate(chunk)},
            via=via, use_kernel=False)
        for i in range(len(chunk)):
            dot = dot_of(acks[key_name(s + i)].clock)
            dots.append(shared.setdefault(dot, dot))
        cluster.deliver_replication()
    return dots
