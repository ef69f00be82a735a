"""The trace reducer, on hand-made intervals and on a small recorded
trace of internlm2-20b served at 4 layers (three engine ticks on a TPU
v5e)."""
import json
from pathlib import Path

import pytest

from harness.trace import Trace, gaps, self_times, union_length

DATA = Path(__file__).parent / "data" / "trace_internlm2_chat.json"


def _rec(ops, modules, spans):
    return {"device": {"XLA Ops": ops, "XLA Modules": modules},
            "n_devices": 1, "spans": spans}


@pytest.fixture
def tiny():
    # window 0..100 ns; decode program 10..50 holds a while loop 12..40
    # with a qmatmul kernel 15..25 and a kv_decode kernel 26..30 nested
    # in it; a chunk program 60..80 with one fusion; host step spans
    ops = [["%while.1", 12, 28, {}], ["%qmatmul.3", 15, 10, {"kernel": 1}],
           ["%kv_decode.4", 26, 4, {"kernel": 1}], ["%fusion.2", 42, 6, {}],
           ["%fusion.9", 60, 20, {}]]
    mods = [["jit_decode_fn", 10, 40], ["jit_chunk_fn", 60, 20]]
    spans = [["bench.window", 0, 100], ["bench.step", 5, 50],
             ["bench.wait", 55, 3], ["bench.step", 58, 30]]
    return Trace(_rec(ops, mods, spans))


def test_interval_arithmetic():
    assert union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert gaps([], 0, 1) == [(0, 1)]


def test_busy_and_idle(tiny):
    ns = 1e-9
    assert tiny.window_s == pytest.approx(100 * ns)
    # busy: 12..40, 42..48, 60..80
    assert tiny.busy_s() == pytest.approx(54 * ns)
    idle, total = tiny.idle_within("bench.step")
    # step 5..55 is idle 5..12, 40..42, 48..55; step 58..88 is idle 58..60, 80..88
    assert total == pytest.approx(80 * ns)
    assert idle == pytest.approx((7 + 2 + 7 + 2 + 8) * ns)


def test_programs_and_kernels(tiny):
    dec = tiny.module_runs("decode_fn")
    assert [m.dur for m in dec] == [pytest.approx(40e-9)]
    kern = {tiny.kernel_of(o): o.dur for o in tiny.ops_within(dec)
            if tiny.kernel_of(o)}
    assert kern == {"qmatmul": pytest.approx(10e-9), "kv_decode": pytest.approx(4e-9)}
    assert tiny.kernel_time("decode_fn", ("qmatmul", "qgemv")) == pytest.approx(10e-9)
    assert tiny.kernel_time("chunk_fn", ("qmatmul",)) == 0
    own = {o.name: t for o, t in self_times(tiny.ops)}
    assert own["%while.1"] == pytest.approx(14e-9)
    assert tiny.program_of(tiny.ops[3]) == "jit_decode_fn"


def test_breakdown_names_gaps_by_span(tiny):
    b = tiny.breakdown()
    assert b["device_ops"][0] == ["jit_chunk_fn/fusion.9", pytest.approx(20e-9)]
    # device gaps: 0..12, 40..42, 48..60, 80..100; each named by the
    # innermost harness span open at its middle
    names = [n for n, _ in b["idle_gaps"]]
    lens = [t for _, t in b["idle_gaps"]]
    assert names == ["outside", "bench.step", "bench.step", "bench.step"]
    assert lens == pytest.approx([20e-9, 12e-9, 12e-9, 2e-9])


def test_recorded_trace():
    tr = Trace(json.loads(DATA.read_text()))
    dec, chunk = tr.module_runs("decode_fn"), tr.module_runs("chunk_fn")
    assert len(dec) == 3 and len(chunk) == 3
    for runs in (dec, chunk):
        ops = tr.ops_within(runs)
        # own times of the ops inside a program add up to its device time,
        # less the few microseconds between its operations
        own = sum(t for _, t in self_times(ops))
        assert own <= sum(r.dur for r in runs)
        assert own == pytest.approx(sum(r.dur for r in runs), rel=1e-4)
    kernels = {tr.kernel_of(o) for o in tr.ops_within(dec)} - {""}
    assert kernels == {"qmatmul", "kv_decode"}
    assert 0 < tr.busy_s() <= tr.window_s
    idle, total = tr.idle_within("bench.step")
    assert 0 <= idle < total
    b = tr.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(t > 0 for _, t in b["device_ops"])


@pytest.mark.parametrize("reader,prog", [("qmm_roofline.decode", "decode_fn"),
                                         ("qmm_roofline.prefill", "chunk_fn")])
def test_qmm_readers_count_the_grouped_tier(reader, prog):
    """A stacked-expert matmul runs ``qmatmul_grouped`` (its HLO name in a
    compiled v5e program); its time counts beside the other tiers'."""
    from types import SimpleNamespace

    from harness import common as C

    start = 10 if prog == "decode_fn" else 60
    ops = [["%qmatmul.3", start + 5, 10, {"kernel": 1}],
           ["%qmatmul_grouped.5", start + 20, 6, {"kernel": 1}]]
    mods = [["jit_decode_fn", 10, 40], ["jit_chunk_fn", 60, 30]]
    tr = Trace(_rec(ops, mods, [["bench.window", 0, 100]]))
    arch = SimpleNamespace(decode_qmm_s=lambda *a: 8e-9,
                           chunk_qmm_s=lambda *a: 8e-9)
    run = SimpleNamespace(decode_calls=[[5]], chunk_calls=[(5, 0)],
                          q={"w_bits": 4})
    ctx = SimpleNamespace(run=run, trace=tr, arch=arch, dims=None, peaks={})
    read = C.load_reader(reader)
    assert "qmatmul_grouped" in read.__globals__["KERNELS"]
    # 8 ns of need over 10 + 6 ns of qmm kernels
    assert read(ctx) == pytest.approx(50.0)
