"""Whole runs of the harness on the CPU at a small size: the last line's
keys, the faults it must catch, a mix added as data, and the refusals."""
import functools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import faults
from bench.cell import run_cell
from bench.spec import Spec

ROOT = Path(__file__).resolve().parents[2]
RECORDS = 2000
SECONDS = 0.5
SEED = 3_000_000_021
CELLS = ("ycsb-c-multiget", "dbbench-overwrite", "ycsb-e", "ycsb-a")
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run(workload, trace=False, plant=None, spec=None, seed=SEED):
    return run_cell(workload, seed, SECONDS, trace,
                    t_start=time.perf_counter(), on_tpu=False, spec=spec,
                    records=RECORDS, plant=plant)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_line(workload):
    out = run(workload)
    line = out["line"]
    assert list(line) == LINE_KEYS + ["checks"]
    assert line["correct"] is False            # never on the CPU
    assert out["answers_ok"], out["info"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in Spec().metrics("end_to_end", workload)}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    json.dumps(line)


@pytest.mark.parametrize("workload,metrics", [
    ("ycsb-e", {"runs_per_scan"}),
    ("dbbench-overwrite", {"backlog_memtables", "drain_share_pct",
                           "stall_ms_per_s"}),
])
def test_traced_run_line_on_cpu(workload, metrics):
    out = run(workload, trace=True)
    line = out["line"]
    assert list(line) == LINE_KEYS + ["checks"]
    per_layer = {m["name"]: m for m in Spec().metrics("per_layer", workload)}
    assert set(line["metrics"]) <= set(per_layer)
    assert metrics <= set(line["metrics"])
    # no device number comes from a CPU run
    assert not any(per_layer[n]["source"] == "device_trace"
                   for n in line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line


FAULTS = [
    ("ycsb-c-multiget", "lossy_probe"),
    ("ycsb-c-multiget", "half_batch"),
    ("ycsb-c-multiget", "alter_answer"),
    ("ycsb-c-multiget", "extra_answer"),
    ("dbbench-overwrite", "lose_writes"),
    ("dbbench-overwrite", "half_batch"),
    ("dbbench-overwrite", "alter_written_value"),
    ("ycsb-e", "lose_writes"),
    ("ycsb-e", "alter_written_value"),
    ("ycsb-e", "alter_answer"),
    ("ycsb-e", "half_batch"),
    ("ycsb-a", "lose_writes"),
    ("ycsb-a", "alter_answer"),
    ("ycsb-a", "alter_written_value"),
]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_is_caught(workload, fault):
    plant = faults.PLANTS[fault]
    if fault != "half_batch":
        plant = functools.partial(plant, every=7)
    out = run(workload, plant=plant)
    assert not out["answers_ok"], out["checks"]
    assert out["line"]["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_mix_added_as_data(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "bench" / "traffic" / "ycsb-b.json").write_text(json.dumps({
        "source": "YCSB workloads/workloadb: 95% reads, 5% updates",
        "ops": {"read": 0.95, "update": 0.05}, "read_call": "get",
        "keys": "zipfian", "warmup_calls": 500}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "ycsb-b", "config": "ycsb-1kb",
                               "traffic": "ycsb-b", "chips": 1,
                               "why": "reads beside a few updates"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("read_p99_ms", "runs_per_point_read"):
            m["workloads"].append("ycsb-b")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = Spec(root=tmp_path, bench=tmp_path / "bench")
    out = run("ycsb-b", spec=spec)
    assert out["answers_ok"]
    assert set(out["line"]["metrics"]) == {"setup_s", "ops_per_s",
                                           "read_p99_ms"}
    traced = run("ycsb-b", trace=True, spec=spec)
    assert "runs_per_point_read" in traced["line"]["metrics"]


def cli(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb-e", "--seed",
         str(SEED), "--seconds", "0.5", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_refuses_off_the_tpu():
    p = cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = cli(tmp_path, "--cpu-rehearsal", "--records", "1000")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_cpu_rehearsal_prints_the_line_last():
    p = cli(ROOT, "--cpu-rehearsal", "--records", str(RECORDS))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert list(line) == LINE_KEYS + ["checks"] and not line["correct"]
    info = json.loads(lines[-2])
    assert info["tails"]["scan"]["samples"] > 0
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [t.split(":")[0] for t in tail] == \
        [f"check {name}" for name in line["checks"]]
