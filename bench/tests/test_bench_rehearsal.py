"""Each cell end to end on the CPU at its architecture's small widths,
through the harness's own functions (the command itself refuses a CPU),
and the same runs with the timed path broken underneath
(``harness/faults.py``): ``correct`` has to come out false, and so does
the control. A new architecture joins with new files only.
"""
import copy
import json
import shutil
from pathlib import Path

import pytest

from harness import common as C
from harness.faults import FAULTS
from harness.serve import ServeRun

CELL = "internlm2-20b.alpaca"


def _serve_cell(name, bench=None):
    """The cell's configuration at its architecture's small widths
    (``small`` of ``bench/archs/<arch>.py``), and its mix cut short."""
    bench = bench or C.load_benchmark()
    _, config, mix = C.find_cell(bench, name)
    config = copy.deepcopy(config)
    config.update(C.load_arch(config).small)
    eng = config["engine"]
    eng.update(num_pages=eng["num_slots"] * 16 + 1, max_len=256)
    mix = copy.deepcopy(mix)
    mix.update(rate_rps=3.0)
    mix["prompt_len"].update(max=120)
    mix["output_len"].update(max=40)
    return config, mix


def _correct(numbers, config):
    return all(v <= config["limits"][k] for k, v in numbers.items())


def _serve(name, seed=2**31 + 101, fault=None, trace_dir=None, bench=None):
    config, mix = _serve_cell(name, bench)
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


# the second architecture of the new-files test: a renamed copy of the
# dense module, or a module whose layer 0 has other leaves than the rest
TWINS = {
    "copy": (C.BENCH_DIR / "archs" / "dense_gqa.py", {}),
    "lead_dense_moe": (Path(__file__).parent / "data" / "twin_moe.py",
                       {"first_k_dense_replace": 1, "n_routed_experts": 8,
                        "num_experts_per_tok": 2, "moe_intermediate_size": 2048}),
}


@pytest.mark.parametrize("twin", sorted(TWINS))
def test_new_architecture_is_new_files_only(twin, tmp_path, monkeypatch):
    """A second architecture joins the benchmark with new files alone: a
    module under ``bench/archs/``, a configuration naming it, and
    additions to ``BENCHMARK.json``. Its cell then rehearses from that
    checkout, and no file that was there has changed."""
    source, keys = TWINS[twin]
    shutil.copy(C.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(C.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    had = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
           if p.is_file()}
    archs = tmp_path / "bench" / "archs"
    shutil.copy(source, archs / "twin_arch.py")
    config = C.load_json(tmp_path / "bench/configs/internlm2-20b.json")
    config.update(name="twin-20b", arch="twin_arch", **keys)
    (tmp_path / "bench/configs/twin-20b.json").write_text(json.dumps(config))
    bench = C.load_benchmark(tmp_path)
    bench["configs"].append(dict(bench["configs"][0], name="twin-20b",
                                 file="bench/configs/twin-20b.json"))
    cell = "twin-20b.alpaca"
    bench["workloads"].append(dict(bench["workloads"][0], name=cell,
                                   config="twin-20b"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setattr(C, "ROOT", tmp_path)
    monkeypatch.setattr(C, "BENCH_DIR", tmp_path / "bench")
    bench = C.load_benchmark(tmp_path)
    _, config, _ = C.find_cell(bench, cell)
    assert Path(C.load_arch(config).__file__) == archs / "twin_arch.py"
    run, o, _, numbers, config = _serve(cell, trace_dir=str(tmp_path / "t"),
                                        bench=bench)
    assert Path(run.arch.__file__) == archs / "twin_arch.py"
    assert run.dims.d_model == run.arch.small["hidden_size"]
    assert o["failed"] == 0 and run.decode_calls and run.chunk_calls
    assert _correct(numbers, config), numbers
    assert not _correct(run.check("float8_e4m3fn"), config)
    assert {m["name"] for m in C.cell_metrics(bench, cell, True)} == {
        m["name"] for m in bench["per_layer"]}
    assert all(p.read_bytes() == b for p, b in had.items())
    if twin == "lead_dense_moe":
        # layer 0 is made and applied as a kind of its own
        _, layer_w = run.arch.reference_weights(run.dims, 4)
        shapes = [{n: a.shape for n, a in layer_w(1, 2, i, run.arch.layer_kind(
            run.dims, i)).items()} for i in (0, 1)]
        assert shapes[0]["w_gate"] == (128, 256)
        assert shapes[1]["w_gate"] == (4, 128, 64) and "router" in shapes[1]
