"""The benchmark refuses a CPU, an unknown chip, and a checkout that
holds nothing but the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import common as C

ROOT = Path(__file__).resolve().parents[2]


def _dev(platform, kind):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_refuses_cpu():
    with pytest.raises(C.BenchError, match="needs a TPU"):
        C.check_devices([_dev("cpu", "cpu")], 1)


def test_refuses_unknown_chip_and_too_few():
    with pytest.raises(C.BenchError, match="no peaks"):
        C.check_devices([_dev("tpu", "TPU v9 imaginary")], 1)
    with pytest.raises(C.BenchError, match="needs 4 chips"):
        C.check_devices([_dev("tpu", "TPU v5 lite")], 4)
    with pytest.raises(C.BenchError, match="no peaks"):
        C.load_peaks("TPU v4")


def test_accepts_known_chip():
    d = C.check_devices([_dev("tpu", "TPU v5 lite")], 1)
    assert d == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert C.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-20b.alpaca",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_cpu_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_checkout_of_only_the_benchmark(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_file_names_every_reader_and_file():
    bench = C.load_benchmark()
    for m in bench["per_layer"]:
        assert (C.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        cfg = C.load_json(ROOT / c["file"])
        assert set(c["reduced"]) <= set(cfg["published"])
    for w in bench["workloads"]:
        C.find_cell(bench, w["name"])
