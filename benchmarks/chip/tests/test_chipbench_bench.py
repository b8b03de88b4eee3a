"""The benchmark's files: the contract of BENCHMARK.json, a cell added from
new files alone, and the measured path's refusal of anything but a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from _chipbench_tiny import BENCH, ROOT, Bench, tiny_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_contract():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmarks/chip"]
    assert 1 <= spec["run_seconds"] <= 51
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    bench = Bench()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert w["config"] in configs and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        reported = [m["name"] for m in bench.end_to_end(w["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        assert bench.per_layer(w["name"])


def test_a_cell_is_added_from_new_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((BENCH / "traffic" / "ycsb-a.json").read_text())
    mix["clients"] = 4
    (tmp_path / "benchmarks" / "chip" / "traffic" / "ycsb-a-4.json"
     ).write_text(json.dumps(mix))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "ycsb-a4.riak5", "config": "ycsb-riak5",
                              "traffic": "ycsb-a-4", "chips": 1,
                              "why": "four clients"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "ycsb-a.riak5" in m.get("workloads", []):
            m["workloads"].append("ycsb-a4.riak5")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = tiny_run("ycsb-a4.riak5", root=tmp_path,
                 traffic_overrides={"clients": 4})
    assert r["correct"]
    assert set(r["metrics"]) == {"ops_per_s", "op_p95_ms", "setup_s"}


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ycsb-a.riak5", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_a_result(stdout):
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_measured_path_refuses_a_cpu_backend():
    p = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)


@pytest.mark.parametrize("workload", ["ycsb-a.riak5", "ycsb-a.repair"])
def test_traced_run_reports_per_layer_metrics(workload, tmp_path):
    """On the CPU no device plane exists: the counters' metrics are read,
    the device's are left out rather than reported as 0."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    r = tiny_run(workload, root=tmp_path, trace=True)
    assert r["correct"]
    names = set(r["metrics"])
    assert not any("roofline" in n or "idle" in n for n in names)
    want = ({"ops_per_flush", "plane_ms_per_op", "kernel_calls_per_op"}
            if workload == "ycsb-a.riak5" else {"slots_per_repaired_key"})
    assert want <= names
    assert not (tmp_path / ".bench_trace").exists()
