"""Operation and byte counts against hand arithmetic."""
import pytest

from harness import flops as F
from harness.weights import Dims

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
D = Dims(n_layers=4, d_model=6144, n_heads=48, n_kv_heads=8, head_dim=128,
         d_ff=16384, vocab=92544, tie=False, rope_theta=1e6, norm_eps=1e-6)


def test_one_qmm_call():
    # 3 live rows against a W4 (6144, 16384) weight
    flops, nbytes = F.qmm_work(3, 6144, 16384, 4)
    assert flops == 2 * 3 * 6144 * 16384
    assert nbytes == 6144 * 16384 // 2 + 16384 * 4 + 3 * (6144 + 16384) * 4
    t = F.roofline_time(flops, nbytes, PEAKS)
    assert t == pytest.approx(nbytes / 819e9)          # bandwidth-bound


def test_one_kv_decode_call():
    # two live streams of 100 and 30 tokens, every layer
    flops, nbytes = F.kv_decode_work(D, [100, 30])
    per_layer_bytes = 130 * 8 * (2 * 128 + 2 * 2) + 2 * 2 * 48 * 128 * 4
    assert nbytes == 4 * per_layer_bytes
    assert flops == 4 * 4 * 48 * 128 * 130
    assert F.kv_min_time(D, [100, 30], PEAKS) == pytest.approx(
        4 * per_layer_bytes / 819e9)


def test_step_counts_only_live_rows():
    one = F.qmm_min_time(D, 1, 4, PEAKS)
    eight = F.qmm_min_time(D, 8, 4, PEAKS)
    weights = (4 * sum(k * n / 2 + 4 * n for k, n in F.layer_linears(D))
               + 6144 * 92544 + 4 * 92544)
    assert one == pytest.approx((weights + 4 * 4 * sum(k + n for k, n in F.layer_linears(D))
                                 + 4 * (6144 + 92544)) / 819e9)
    assert eight > one
    # a tied head is no packed weight of its own
    tied = Dims(**{**D.__dict__, "tie": True})
    assert F.qmm_min_time(tied, 1, 4, PEAKS) < one


def test_model_flops_decode():
    f = F.model_flops_decode(D, [10, 20])
    lp = F.layer_params(D)
    assert lp == 6144 * (6144 + 2 * 1024) + 6144 * 6144 + 3 * 6144 * 16384
    assert f == 2 * 2 * (4 * lp + 6144 * 92544) + 4 * 4 * 48 * 128 * 30


def test_model_flops_prefill():
    # 32 rows at positions 100..131 attend to 101..132 keys each
    f = F.model_flops_prefill(D, 32, 100)
    keys = sum(range(101, 133))
    assert f == (2 * 32 * 4 * F.layer_params(D) + 4 * 4 * 48 * 128 * keys
                 + 2 * 6144 * 92544)
