"""The command measures the chip only: no TPU, too few chips, a device the
peaks table does not know, or a checkout without the program, and it prints
no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, loader
from bench.tests.conftest import ROOT


def _run(cwd, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_device():
    p = _run(ROOT, "--workload", "solve.homo-n64.full", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_too_few_chips_is_refused():
    with pytest.raises(harness.Refused, match="asks for 4 chips"):
        harness.check_devices(4, require_tpu=False)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.Refused, match="not in"):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_checkout_without_the_program_prints_nothing(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, "--workload", "solve.homo-n64.full", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    with pytest.raises(harness.Refused, match="src/repro"):
        harness.execute("solve.homo-n64.full", 1, 1.0, False,
                        require_tpu=False, base=str(tmp_path / "bench"),
                        root=str(tmp_path))


def test_benchmark_json_keeps_to_its_form():
    bench = loader.benchmark(ROOT)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                              "device_trace")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    json.dumps(bench)
