"""Tail and rate arithmetic: every request counted, failures as missing,
whole-window division."""
import math

import pytest

from harness import common as C
from harness.serve import end_to_end, request_outcomes


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert C.percentile(vals, 95) == 95
    assert C.percentile(vals, 50) == 50
    assert C.percentile([3.0], 95) == 3.0
    assert C.percentile([1, 2, math.inf], 95) == math.inf


def test_outcomes_count_failures_and_tokens_in_window():
    arrival = [0.0, 1.0, 2.0]
    max_new = [3, 2, 2]
    toks = [[0.5, 0.6, 0.8], [1.5, 10.5], [2.5]]   # the third never finished
    o = request_outcomes(arrival, max_new, toks, [True, True, False], 10.0)
    assert o["attempted"] == 3 and o["failed"] == 1
    assert o["ttft_s"][:2] == pytest.approx([0.5, 0.5])
    assert o["ttft_s"][2] == math.inf
    assert o["itl_s"] == pytest.approx([0.1, 0.2, 9.0])
    # tokens seen after the window closed do not count toward the rate
    assert o["tokens_in_window"] == 5
    e = end_to_end(o, 10.0)
    assert e["out_tok_s"] == pytest.approx(0.5)
    assert e["ttft_p95_ms"] == C.MISSING_MS     # the failed request is the tail
    assert e["ttft_p90_ms"] == C.MISSING_MS
    assert e["itl_p95_ms"] == pytest.approx(9000.0)


def test_short_output_counts_as_failed():
    o = request_outcomes([0.0], [4], [[0.1, 0.2]], [True], 1.0)
    assert o["failed"] == 1


def test_emit_puts_checks_last(capsys):
    C.emit({"correct": True, "metrics": {}}, [("gap_max", 0.01, 0.1)])
    out, err = capsys.readouterr()
    assert err.strip().splitlines()[-1] == "check gap_max 0.01 limit 0.1"
    line = out.strip().splitlines()[-1]
    assert line.endswith('"check": {"gap_max": {"value": 0.01, "limit": 0.1}}}')


def test_seed_halves():
    assert C.split_seed(5) == (5, 0)
    lo, hi = C.split_seed(2**31 + 9)
    assert (lo, hi) == (9, 1)
    with pytest.raises(C.BenchError):
        C.split_seed(-1)
