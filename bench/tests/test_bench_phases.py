"""The readers of the engine's spans and of its device operations' scopes
(``harness.phases``), on hand-made records and on a small recorded trace of
internlm2-20b served at 2 layers on a TPU v5e (three engine ticks with the
``serve.*`` spans and each op's scope)."""
import json
from pathlib import Path

import pytest

from harness.trace import Trace

DATA = Path(__file__).parent / "data" / "trace_internlm2_chat.json"
PHASED = Path(__file__).parent / "data" / "trace_internlm2_phases.json"


def _rec(ops, modules, spans):
    return {"device": {"XLA Ops": ops, "XLA Modules": modules},
            "n_devices": 1, "spans": spans}


@pytest.fixture
def tiny():
    # the hand-made record of test_bench_trace.py: a decode program 10..50
    # and a chunk program 60..80, with no serve.* span and no scope
    ops = [["%while.1", 12, 28, {}], ["%qmatmul.3", 15, 10, {"kernel": 1}],
           ["%kv_decode.4", 26, 4, {"kernel": 1}], ["%fusion.2", 42, 6, {}],
           ["%fusion.9", 60, 20, {}]]
    mods = [["jit_decode_fn", 10, 40], ["jit_chunk_fn", 60, 20]]
    spans = [["bench.window", 0, 100], ["bench.step", 5, 50],
             ["bench.wait", 55, 3], ["bench.step", 58, 30]]
    return Trace(_rec(ops, mods, spans))


# -- the engine's spans and the scopes of its device operations ------------

KW = "jit(decode_fn)/while/body/closed_call/kv_write/scatter"
KR = "jit(decode_fn)/while/body/closed_call/kv_read/gather"
KR_KERNEL = "jit(decode_fn)/while/body/closed_call/kv_read/pallas_call"
CW = "jit(chunk_fn)/while/body/closed_call/kv_write/scatter"
READERS = ("decode_dev_ms", "prefill_dev_ms", "qmm_roofline.decode",
           "qmm_roofline.prefill", "kv_roofline.decode", "mfu.decode",
           "mfu.prefill", "idle_share.serve")
NEW_READERS = ("tick_host_ms", "readback_ms.decode", "kv_write_ms.decode",
               "kv_read_ms.decode")


@pytest.fixture
def phased():
    # one tick that decodes (program 20..100 inside its fetch 20..102) and
    # one whose prefill chunk ends a prompt (program 112..150, fetch
    # 112..152); each op's scope in its marks
    from harness.phases import Phases

    ops = [["%while.1", 22, 68, {"scope": "jit(decode_fn)/while"}],
           ["%fusion.5", 25, 10, {"scope": KW}],
           ["%fusion.6", 36, 12, {"scope": KR}],
           ["%kv_decode.7", 48, 4, {"kernel": 1, "scope": KR_KERNEL}],
           ["%qmatmul.8", 55, 30, {"kernel": 1}],
           ["%copy.9", 92, 6, {}],
           ["%fusion.10", 112, 8, {"scope": CW}],
           ["%fusion.11", 120, 30, {}]]
    mods = [["jit_decode_fn", 20, 80], ["jit_chunk_fn", 112, 38]]
    spans = [["bench.window", 0, 200], ["bench.step", 5, 100],
             ["serve.tick", 6, 98], ["serve.expire", 6, 1], ["serve.admit", 7, 1],
             ["serve.decode.stage", 8, 10], ["serve.decode.run", 18, 2],
             ["serve.decode.fetch", 20, 82], ["serve.decode.sample", 102, 2],
             ["bench.step", 108, 52], ["serve.tick", 108, 50],
             ["serve.expire", 108, 1], ["serve.admit", 109, 1],
             ["serve.prefill.stage", 110, 1], ["serve.prefill.run", 111, 1],
             ["serve.prefill.fetch", 112, 40], ["serve.prefill.sample", 152, 1]]
    return Phases(_rec(ops, mods, spans))


def _read(name, ctx):
    from harness import common as C

    return C.load_reader(name)(ctx)


def test_new_readers_by_hand(phased, monkeypatch):
    from harness import phases

    monkeypatch.setattr(phases, "of_run", lambda ctx: phased)
    got = {n: _read(n, None) for n in NEW_READERS}
    ms = 1e-6   # one ns in ms
    # ticks 98 - fetch 82 and 50 - fetch 40: host 16 and 10
    assert got["tick_host_ms"] == pytest.approx(13 * ms)
    # the decode program ends at 100, its fetch at 102
    assert got["readback_ms.decode"] == pytest.approx(2 * ms)
    # one decode call: fusion.5 in kv_write; fusion.6 in kv_read (the
    # kv_decode kernel in the same scope is not counted)
    assert got["kv_write_ms.decode"] == pytest.approx(10 * ms)
    assert got["kv_read_ms.decode"] == pytest.approx(12 * ms)


def test_split_adds_up(phased):
    ns = 1e-9
    split = phased.split("decode_fn")
    assert split == pytest.approx({"qmm": 30 * ns, "kv_decode": 4 * ns,
                                   "kv_write": 10 * ns, "kv_read": 12 * ns,
                                   "rest": 18 * ns})
    # the loop's own 12 and the copy's 6 are the rest; the program's 80
    # less the 6 between its ops
    assert sum(split.values()) == pytest.approx(74 * ns)
    assert phased.split("chunk_fn")["kv_write"] == pytest.approx(8 * ns)


def test_readback_needs_an_execution_end(phased):
    from harness.trace import Ev

    # a fetch that holds no end of a decode execution reads None
    phased.spans.append(Ev("serve.decode.fetch", 160e-9, 170e-9, {}))
    assert phased.readback_s("decode_fn", "serve.decode.fetch") is None


def test_idle_by_phase(phased):
    ns = 1e-9
    idle = phased.idle_by_phase()
    assert idle == pytest.approx({
        "bench.step": 4 * ns, "serve.tick": 5 * ns,
        "serve.expire": 2 * ns, "serve.admit": 2 * ns,
        "serve.decode.stage": 10 * ns, "serve.decode.run": 2 * ns,
        "serve.decode.fetch": 8 * ns, "serve.decode.sample": 2 * ns,
        "serve.prefill.stage": 1 * ns, "serve.prefill.run": 1 * ns,
        "serve.prefill.fetch": 2 * ns, "serve.prefill.sample": 1 * ns})
    assert sum(idle.values()) == pytest.approx(phased.idle_within("bench.step")[0])
    # the breakdown's gaps now name the engine's phases
    names = [n for n, _ in phased.breakdown()["idle_gaps"]]
    assert names == ["outside", "serve.decode.stage", "bench.step",
                     "serve.decode.fetch"]


def test_readers_of_a_program_without_spans(tiny, monkeypatch):
    """The same readers on a record with no ``serve.*`` span and no scope,
    as a program without them leaves: None, never a number or an error."""
    from harness import phases

    monkeypatch.setattr(phases, "of_run", lambda ctx: phases.Phases(tiny.rec))
    assert {n: _read(n, None) for n in NEW_READERS} == dict.fromkeys(NEW_READERS)


HLO = """HloModule jit_decode_fn, entry_computation_layout={()->f32[]}

%fused_computation.3 (param_0: s8[4,16]) -> s8[4,16] {
  %param_0 = s8[4,16]{1,0} parameter(0)
  ROOT %scatter.2 = s8[4,16]{1,0} scatter(%param_0), metadata={op_name="jit(decode_fn)/kv_write/scatter" stack_frame_id=3}
}

%fused_computation.4 (param_0.1: s8[4,16]) -> (s8[4,16], s8[4,16]) {
  %param_0.1 = s8[4,16]{1,0} parameter(0)
  ROOT %tuple.1 = (s8[4,16]{1,0}, s8[4,16]{1,0}) tuple(%param_0.1, %param_0.1)
}

ENTRY %main.9 (Arg_0.1: s8[4,16]) -> s8[4,16] {
  %Arg_0.1 = s8[4,16]{1,0} parameter(0)
  %fusion.7 = s8[4,16]{1,0} fusion(s8[4,16]{1,0} %Arg_0.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(decode_fn)/add" stack_frame_id=1}
  %fusion.8 = (s8[4,16]{1,0}, s8[4,16]{1,0}) fusion(%fusion.7), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(decode_fn)/kv_read/gather"}
  %qmatmul.3 = f32[4,16]{1,0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_fn)/pallas_call"}
  ROOT %copy.5 = s8[4,16]{1,0} copy(%fusion.7)
}
"""


def test_hlo_scopes_attribute_a_fusion_by_its_root():
    from harness.phases import hlo_scopes

    module, scopes = hlo_scopes(HLO)
    assert module == "jit_decode_fn"
    # the fusion's root is the kv_write scatter, whatever its own metadata
    assert scopes["%fusion.7"] == "jit(decode_fn)/kv_write/scatter"
    # a root with no metadata (a tuple): the fusion's own
    assert scopes["%fusion.8"] == "jit(decode_fn)/kv_read/gather"
    assert scopes["%qmatmul.3"] == "jit(decode_fn)/pallas_call"
    assert scopes["%copy.5"] == ""


def test_with_phases_marks_each_op_by_program(tiny):
    from harness.phases import Phases, with_phases

    spans = [["serve.tick", 6, 48]]
    scopes = {"jit_decode_fn": {"%fusion.2": KW},
              "jit_chunk_fn": {"%fusion.2": "x", "%fusion.9": CW}}
    rec = with_phases(tiny.rec, spans, scopes)
    marks = {r[0]: r[3] for r in rec["device"]["XLA Ops"]}
    assert marks["%fusion.2"] == {"scope": KW}         # in the decode program
    assert marks["%fusion.9"] == {"scope": CW}         # in the chunk program
    assert marks["%qmatmul.3"] == {"kernel": 1}
    assert rec["spans"][-1] == ["serve.tick", 6, 48]
    assert "scope" not in tiny.rec["device"]["XLA Ops"][3][3]   # a copy
    assert Phases(rec).has_scopes("decode_fn")
    assert Phases(rec).has_scopes("chunk_fn")
    assert not Phases(tiny.rec).has_scopes("decode_fn")


def _recorded_ctx(rec):
    """What a reader sees of a run, with the calls noted for the recorded
    trace's three ticks (20 live rows at 40 tokens; 20-row chunks)."""
    import types

    from harness import common as C
    from harness.weights import Dims

    bench = C.load_benchmark()
    _, config, _ = C.find_cell(bench, bench["workloads"][0]["name"])
    run = types.SimpleNamespace(decode_calls=[[40] * 20] * 3,
                                chunk_calls=[(20, 0)] * 3,
                                q=config["quant"], dims=Dims.from_config(config))
    return types.SimpleNamespace(run=run, trace=Trace(rec), dims=run.dims,
                                 peaks=C.load_peaks("TPU v5 lite"))


def test_existing_readers_unchanged_by_engine_spans():
    """The eight readers and the breakdown's device ops read the recorded
    trace identically with the engine's spans and op scopes added."""
    from harness.phases import with_phases

    rec = json.loads(DATA.read_text())
    spans = []
    for name, start, dur in rec["spans"]:
        if name == "bench.step":
            spans += [["serve.tick", start + 10, dur - 20],
                      ["serve.decode.stage", start + 20, 1000],
                      ["serve.decode.fetch", start + 2000, dur - 3000]]
    tr = Trace(rec)
    scopes = {m: {o.name: f"{m}/while/body/kv_read/x" for o in tr.ops}
              for m in ("jit_decode_fn", "jit_chunk_fn")}
    phased = with_phases(rec, spans, scopes)
    assert len(phased["spans"]) > len(rec["spans"])
    before, after = _recorded_ctx(rec), _recorded_ctx(phased)
    for name in READERS:
        a, b = _read(name, before), _read(name, after)
        assert a is not None and a == b, name
    assert (before.trace.breakdown()["device_ops"]
            == after.trace.breakdown()["device_ops"])


def test_recorded_phases(monkeypatch):
    """The four readers on a recorded trace with the engine's spans and
    scopes: finite, and the decode program's parts add up to its time."""
    import math

    from harness import phases

    ph = phases.Phases(json.loads(PHASED.read_text()))
    monkeypatch.setattr(phases, "of_run", lambda ctx: ph)
    got = {n: _read(n, None) for n in NEW_READERS}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in got.values()), got
    assert got["kv_read_ms.decode"] > got["kv_write_ms.decode"] > 0
    assert len(ph.ticks()) == 3
    for fn in ("decode_fn", "chunk_fn"):
        runs = ph.module_runs(fn)
        assert sum(ph.split(fn).values()) == pytest.approx(
            sum(r.dur for r in runs) / len(runs), rel=1e-3)
    # nearly all the device's idle time inside engine steps lies in a
    # named phase, not in the tick alone
    idle = ph.idle_by_phase()
    bare = idle.get("serve.tick", 0) + idle.get("bench.step", 0)
    assert bare < 0.1 * sum(idle.values())
