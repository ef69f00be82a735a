"""Each cell end to end on the CPU at small widths, through the harness's
own functions (the command itself refuses a CPU), and the same runs with
the timed path broken underneath (``harness/faults.py``): ``correct``
has to come out false, and so does the control.
"""
import copy

import pytest

from harness import common as C
from harness.faults import FAULTS
from harness.serve import ServeRun

CELL = "internlm2-20b.alpaca"
SMALL = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
             vocab_size=512)


def _serve_cell(name):
    bench = C.load_benchmark()
    _, config, mix = C.find_cell(bench, name)
    config = copy.deepcopy(config)
    config.update(SMALL)
    eng = config["engine"]
    eng.update(num_pages=eng["num_slots"] * 16 + 1, max_len=256)
    mix = copy.deepcopy(mix)
    mix.update(rate_rps=3.0)
    mix["prompt_len"].update(max=120)
    mix["output_len"].update(max=40)
    return config, mix


def _correct(numbers, config):
    return all(v <= config["limits"][k] for k, v in numbers.items())


def _serve(name, seed=2**31 + 101, fault=None, trace_dir=None):
    config, mix = _serve_cell(name)
    run = ServeRun(config, mix, seed, 3.0)
    run.setup(record=trace_dir is not None)
    if fault is not None:
        fault(run.engine)
    run.window(trace_dir, 1.5)
    o = run.outcomes()
    e2e = run.end_to_end()
    run.release()
    return run, o, e2e, run.check(), config


@pytest.mark.parametrize("cell", [w["name"] for w in C.load_benchmark()["workloads"]])
def test_serve_cell_rehearsal(cell, tmp_path):
    run, o, e2e, numbers, config = _serve(cell, trace_dir=str(tmp_path))
    assert o["failed"] == 0 and o["attempted"] == 9
    # the traced stretch noted each program call's live work
    slots = config["engine"]["num_slots"]
    assert run.decode_calls and all(0 < len(c) <= slots for c in run.decode_calls)
    assert run.chunk_calls and all(0 < r <= 32 and off % 32 == 0
                                   for r, off in run.chunk_calls)
    assert run.window_compiles == 0
    assert e2e["out_tok_s"] > 0 and 0 < e2e["ttft_p90_ms"] <= e2e["ttft_p95_ms"] < C.MISSING_MS
    assert e2e["itl_p95_ms"] > 0
    assert _correct(numbers, config), numbers


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_serve_fault_is_caught(fault):
    _, _, _, numbers, config = _serve(CELL, fault=FAULTS[fault])
    assert not _correct(numbers, config), numbers


def test_serve_control_reads_above_the_program():
    config, mix = _serve_cell(CELL)
    run = ServeRun(config, mix, 77, 3.0)
    run.setup()
    run.window()
    run.release()
    prog, ctrl = run.check(), run.check("float8_e4m3fn")
    assert ctrl["gap_mean"] > prog["gap_mean"]
    assert ctrl["gap_max"] > prog["gap_max"]
    assert _correct(prog, config) and not _correct(ctrl, config), (prog, ctrl)
