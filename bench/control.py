"""Readings for a cell's correctness limits: the program's compared
numbers and its control's, on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 15
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --fault state_unchanged

Each seed gets its own weights and a short window at the cell's own
load, through the same engine programs (compiled once). Then the
reference reads the program's numbers, and the control (the reference
with float8 matmul operands in the program's place) reads its own: at
each compared position, the gap of the token the control puts first.
With ``--fault`` the program runs with that fault of
``harness/faults.py`` planted in its decode program. One JSON line per
seed on stdout, with whether each side reads as correct by the
configuration's limits.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import common as C  # noqa: E402
from harness.faults import FAULTS  # noqa: E402

# one precision below the served model's bfloat16 matmul operands
CONTROL_OPERANDS = "float8_e4m3fn"


def within(numbers: dict, limits: dict) -> bool:
    return all(v <= limits[k] for k, v in numbers.items())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    args = ap.parse_args()
    bench = C.load_benchmark()
    cell, config, mix = C.find_cell(bench, args.workload)
    import jax

    C.check_devices(jax.devices(), cell["chips"])
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from harness.serve import ServeRun

    limits = config["limits"]
    donor = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        run = ServeRun(config, mix, seed, args.seconds)
        run.setup(share=donor)
        e = run.engine
        donor = types.SimpleNamespace(
            cfg=e.cfg, _decode_jit=e._decode_jit, _chunk_jit=e._chunk_jit,
            _decode_c=e._decode_c, _chunk_c=e._chunk_c, _compile_s=e._compile_s)
        if args.fault:
            FAULTS[args.fault](e)
        del e  # the engine has to go with the run, before the reference
        run.window()
        o = run.outcomes()
        run.release()
        prog = run.check()
        out = {"seed": seed, "fault": args.fault, "failed": o["failed"],
               "attempted": o["attempted"], "program": prog,
               "program_correct": o["failed"] == 0 and within(prog, limits)}
        if not args.fault:
            ctrl = run.check(CONTROL_OPERANDS)
            out.update(control=ctrl, control_correct=within(ctrl, limits))
        out["wall_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
