"""What every cell shares: the benchmark's files, the device check, seeds,
tails and rates, and the result line.

Nothing here imports JAX at module level, so the CPU tests can load it
without touching a backend.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
PEAKS_FILE = BENCH_DIR / "peaks.json"
# A failed or refused request never gets a token: its latency sorts above
# every real one. JSON has no infinity, so a tail that lands on such a
# request is printed as this many milliseconds.
MISSING_MS = 1e9
# JAX monitoring events of a program compiled, or fetched from the
# persistent cache: neither may happen inside a measured window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchError(RuntimeError):
    """A run that cannot produce a result (wrong device, bad files)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of a cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries a cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """The per-layer metric reader ``bench/metrics/<name>.py``: a module
    with ``read(run) -> float | None``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The chip's peaks, keyed by JAX's ``device_kind``. An unknown chip
    is an error, never a default."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device_kind {device_kind!r} in "
                         f"{path.name} (have {sorted(table)})")
    return table[device_kind]


def check_devices(devices, chips: int) -> dict:
    """Refuse anything but enough TPU chips; return the result's
    ``device`` entry (without the memory peak)."""
    if not devices:
        raise BenchError("JAX found no device")
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {dev.platform!r} "
                         f"({dev.device_kind})")
    if len(devices) < chips:
        raise BenchError(f"cell needs {chips} chips, JAX found {len(devices)}")
    load_peaks(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def split_seed(seed: int) -> tuple[int, int]:
    """A seed of any size as two non-negative int32 halves, for
    ``jax.random`` (which takes 32 bits) inside a jitted program."""
    if seed < 0:
        raise BenchError(f"seed must be >= 0, got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all ``values`` (``inf`` sorts last)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def finite_ms(x: float) -> float:
    return MISSING_MS if math.isinf(x) else x


def emit(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Print the compared numbers beside their limits as the last lines
    of stderr, then the result as the last line of stdout, with the
    checks under their own key, last."""
    for name, value, limit in checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["check"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(out), flush=True)
