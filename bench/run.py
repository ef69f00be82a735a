"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration file and its traffic file (``bench/traffic/<mix>.json``)
hold everything that is particular to it. Requests are served open
loop through ``repro.serve_engine``. Set-up (imports, weights, compiles,
warm-up) is timed as ``setup_s``; then the window runs for ``--seconds``.
With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of part of the window is reduced to
its per-layer metrics (one reader each, ``bench/metrics/<metric>.py``).

Every run then checks what the timed path produced against the plain
reference (``bench/harness/reference.py``), prints each compared number
beside its limit as the last lines of stderr, and prints the result as
the last line of stdout. It exits non-zero with no result when JAX finds
no TPU, fewer chips than the cell asks for, a chip missing from
``bench/peaks.json``, or no ``repro`` package beside ``bench/``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import common as C  # noqa: E402

# how much of a serving window the traced run records, at its end
TRACE_SERVE_S = 5.0


class Ctx:
    """What a per-layer reader sees: the run, its trace and the chip."""

    def __init__(self, run, trace, peaks):
        self.run, self.trace, self.peaks = run, trace, peaks
        self.dims = run.dims


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this many requests per second instead of the "
                         "mix's rate (for finding a cell's knee)")
    args = ap.parse_args(argv)

    try:
        bench = C.load_benchmark()
        cell, config, mix = C.find_cell(bench, args.workload)
        try:
            from repro.launch.compile_cache import use_compile_cache
        except ImportError as e:
            raise C.BenchError(f"cannot import repro from {BENCH.parent / 'src'}: {e}")
        import jax

        devices = jax.devices()
        device = C.check_devices(devices, cell["chips"])
        peaks = C.load_peaks(device["kind"])
    except C.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] {args.workload} seed {args.seed} on {device['kind']}; "
          f"compile cache {cache}", file=sys.stderr)

    if args.rate is not None:
        mix = dict(mix, rate_rps=args.rate)
    from harness.serve import ServeRun

    run = ServeRun(config, mix, args.seed, args.seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        run.setup(record=bool(args.trace))
        setup_s = time.perf_counter() - T0
        run.window(trace_dir, min(TRACE_SERVE_S, args.seconds))
        print(f"[bench] setup {setup_s:.3f}s; compiles in window "
              f"{run.window_compiles}", file=sys.stderr)
        o = run.outcomes()
        result = {"correct": False, "attempted": o["attempted"],
                  "failed": o["failed"]}
        late = sorted(run.submit_late)
        tt = sorted(o["ttft_s"])
        print(f"[bench] {o['attempted']} requests, {o['failed']} failed; "
              f"ttft p50 {tt[len(tt) // 2]:.4f}s; drain {run.drain_s:.3f}s; "
              f"generator lateness p50 {late[len(late) // 2]:.4f}s "
              f"max {late[-1]:.4f}s", file=sys.stderr)
        device["memory_peak_bytes"] = int(
            (devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0))

        if args.trace:
            from harness.trace import Trace, read_xplane

            tr = Trace(read_xplane(trace_dir))
            ctx = Ctx(run, tr, peaks)
            metrics = {}
            for m in C.cell_metrics(bench, args.workload, True):
                v = C.load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s
            result["breakdown"] = tr.breakdown()
        else:
            e2e = run.end_to_end()
            e2e["setup_s"] = setup_s
            print(f"[bench] end to end {json.dumps(e2e)}", file=sys.stderr)
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in C.cell_metrics(bench, args.workload, False)}
        result["metrics"] = metrics
        result["device"] = device

        t_check = time.perf_counter()
        run.release()
        numbers = run.check()
        print(f"[bench] check {time.perf_counter() - t_check:.3f}s",
              file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    limits = config["limits"]
    checks = [(k, v, limits[k]) for k, v in numbers.items()]
    result["correct"] = (result["failed"] == 0
                         and all(v <= lim for _, v, lim in checks))
    C.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
