"""A configuration, a traffic mix, a metric and a driver added as new
files, with new BENCHMARK.json entries only, are found by name and run
(tiny, CPU): no file the benchmark already has is edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


@pytest.fixture
def copy(tmp_path):
    dst = tmp_path / "checkout"
    shutil.copytree(BENCH, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    before = {"BENCHMARK.json": (dst / "BENCHMARK.json").read_bytes()}
    for d, _, files in os.walk(dst / "benchmark"):
        for q in files:
            path = os.path.join(d, q)
            before[os.path.relpath(path, dst)] = open(path, "rb").read()
    return dst, before


def _add(dst):
    b = dst / "benchmark"
    cfg = json.loads((b / "configs" / "edge-2048-int16.json").read_text())
    cfg.update(name="edge-512-int16", channels=512)
    (b / "configs" / "edge-512-int16.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "workloads" / "stream-1x10s.json").read_text())
    mix.update(pool=2)
    (b / "workloads" / "stream-1x10s-pool2.json").write_text(json.dumps(mix))
    (b / "metrics" / "rounds_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['units'])\n")
    # a driver of its own: each unit is two archive jobs
    (b / "drivers" / "batch_pairs.py").write_text(
        "import harness\n\n"
        "Batch = harness.load_module('drivers', 'batch').Driver\n\n\n"
        "class Driver(Batch):\n"
        "    def samples_per_unit(self):\n"
        "        return 2 * super().samples_per_unit()\n\n"
        "    def unit(self):\n"
        "        super().unit()\n"
        "        super().unit()\n")
    arc = json.loads((b / "configs" / "archive-10k-int16.json").read_text())
    arc.update(name="archive-pairs-int16", driver="batch_pairs",
               channels=2000)
    (b / "configs" / "archive-pairs-int16.json").write_text(json.dumps(arc))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "edge-512-int16", "source": "https://example.org/x",
        "file": "benchmark/configs/edge-512-int16.json", "reduced": [],
        "why": "drop-in self-test"})
    bench["configs"].append({
        "name": "archive-pairs-int16", "source": "https://example.org/y",
        "file": "benchmark/configs/archive-pairs-int16.json", "reduced": [],
        "why": "drop-in self-test of a driver"})
    bench["workloads"].append({
        "name": "edge-512-10s", "config": "edge-512-int16",
        "traffic": "stream-1x10s-pool2", "chips": 1, "why": "drop-in self-test"})
    bench["workloads"].append({
        "name": "batch-pairs", "config": "archive-pairs-int16",
        "traffic": "archive-jobs", "chips": 1, "why": "drop-in self-test"})
    bench["per_layer"].append({
        "name": "rounds_in_window", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "round", "moves": "ch_samples_per_s",
        "workloads": ["edge-512-10s", "batch-pairs"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_found_and_run(copy):
    dst, before = copy
    _add(dst)
    for path, raw in before.items():
        if path != "BENCHMARK.json":
            assert open(os.path.join(dst, path), "rb").read() == raw
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for cell, trace, want in (
            ("edge-512-10s", 0, ["ch_samples_per_s", "setup_s"]),
            ("edge-512-10s", 1, ["rounds_in_window"]),
            ("batch-pairs", 1, ["rounds_in_window"])):
        out = subprocess.run(
            [sys.executable, "benchmark/rehearse.py", "--workload", cell,
             "--seed", "77", "--seconds", "1.0", "--trace", str(trace),
             "--channels", "16"],
            cwd=dst, env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["correct"] and set(want) <= set(res["metrics_found"]), res


def test_only_benchmark_files_is_refused(copy):
    """A directory with BENCHMARK.json and benchmark/ alone has no
    system to run: no result, non-zero exit."""
    dst, _ = copy
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "batch-10k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=dst, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
