"""The open-loop schedule: reproducible per seed, the same requests at
the same times on every seed with other token ids, lengths inside their
clips, the order a property of the mix."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic as T

MIX = json.loads((Path(__file__).parents[1] / "traffic" / "alpaca.json").read_text())


def test_same_seed_same_schedule():
    a = T.open_loop(MIX, 30, 2**31 + 7, 92544)
    b = T.open_loop(MIX, 30, 2**31 + 7, 92544)
    assert np.array_equal(a.arrival_s, b.arrival_s)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))


def test_seeds_differ_in_ids_not_in_work():
    a = T.open_loop(MIX, 30, 1, 92544)
    b = T.open_loop(MIX, 30, 2, 92544)
    assert len(a) == len(b) == int(MIX["rate_rps"] * 30)
    assert np.array_equal(a.arrival_s, b.arrival_s)
    assert np.array_equal(a.max_new, b.max_new)
    assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
    assert not any(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))


def test_order_belongs_to_the_mix():
    a = T.open_loop(MIX, 30, 1, 92544)
    b = T.open_loop(dict(MIX, order_seed=MIX["order_seed"] + 1), 30, 1, 92544)
    assert not np.array_equal(a.max_new, b.max_new)
    assert sorted(a.max_new) == sorted(b.max_new)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert a.arrival_s[-1] == pytest.approx(b.arrival_s[-1])


def test_lengths_inside_clips_and_arrivals_inside_window():
    s = T.open_loop(MIX, 40, 123, 1000)
    plen = np.array([len(p) for p in s.prompts])
    assert plen.min() >= MIX["prompt_len"]["min"]
    assert plen.max() <= MIX["prompt_len"]["max"]
    assert s.max_new.min() >= MIX["output_len"]["min"]
    assert s.max_new.max() <= MIX["output_len"]["max"]
    assert np.all(np.diff(s.arrival_s) > 0) and s.arrival_s[-1] < 40
    assert all(p.min() >= 0 and p.max() < 1000 for p in s.prompts)
    # the medians sit at the stated medians, up to rounding to whole tokens
    assert abs(np.median(plen) - MIX["prompt_len"]["median"]) <= 0.5
    assert abs(np.median(s.max_new) - MIX["output_len"]["median"]) <= 0.5


def test_rate_parameter_sets_the_count():
    s = T.open_loop(dict(MIX, rate_rps=0.8), 45, 9, 100)
    assert len(s) == 36


def test_mean_lengths_are_the_sources():
    # the Alpaca means reported with vLLM's evaluation: 19.31 prompt and
    # 58.45 output tokens, which the mix's medians were chosen to give
    s = T.open_loop(MIX, 100, 3, 1000)
    assert np.mean([len(p) for p in s.prompts]) == pytest.approx(19.31, rel=0.02)
    assert np.mean(s.max_new) == pytest.approx(58.45, rel=0.02)

