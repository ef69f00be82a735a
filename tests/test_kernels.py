"""Pallas kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantizer import QConfig, init_qstate, quantize_int
from repro.kernels.fakequant.kernel import fakequant
from repro.kernels.fakequant.ref import fakequant_ref
from repro.kernels.kvattn.kernel import kv_decode
from repro.kernels.kvattn.ops import attend_int8, quantize_kv
from repro.kernels.kvattn.ref import kv_decode_ref
from repro.kernels.qmatmul.kernel import qgemv, qmatmul, qmatmul_grouped
from repro.kernels.qmatmul.ops import QuantizedLinear, pack_weights, qmm
from repro.kernels.qmatmul.ref import (qgemv_ref, qmatmul_ref,
                                       qmm_grouped_dense_ref, qmm_grouped_ref)


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("M,K,N,group", [
    (8, 256, 128, 128),
    (128, 512, 256, None),
    (16, 128, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_vs_ref(rng, bits, M, K, N, group, dtype):
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    cfg = QConfig(bits=bits, channel_axis=-1, group_size=group)
    st = init_qstate(w, cfg)
    codes = quantize_int(w, st, cfg)
    scales = st.scale.reshape(-1, N)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    packed = pack_weights(codes, scales, bits).packed
    ref = qmatmul_ref(x, packed, scales, bits)
    out = qmatmul(x, packed, scales, bits=bits,
                  bm=8 if M <= 16 else 128, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_unpack_tile_matches_unpack_int(rng, bits):
    """In-kernel int32 shift/mask field unpack == the pure-jnp widening
    oracle: field plane i holds K rows i, i + per, ... (the rows that
    ``field_planes`` pairs it with)."""
    from repro.core.quantizer import pack_int, unpack_int
    from repro.kernels.qmatmul.kernel import _unpack_fields

    K, N = 64, 128
    codes = jnp.asarray(
        rng.integers(-(2 ** (bits - 1)), 2 ** (bits - 1), size=(K, N)), jnp.int8)
    packed = pack_int(codes, bits)
    got = jnp.stack(_unpack_fields(packed, bits), axis=1).reshape(K, N)
    ref = unpack_int(packed, bits, K).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(codes, np.float32))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("M", [3, 13, 130])
def test_qmm_ragged_m_pads_to_tile(rng, bits, M):
    """M not a multiple of 8/128 pads up + slices instead of bm=1."""
    K, N = 256, 128
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    cfg = QConfig(bits=bits, channel_axis=-1)
    st = init_qstate(w, cfg)
    codes = quantize_int(w, st, cfg)
    qw = pack_weights(codes, st.scale.reshape(-1, N), bits)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    out = qmm(x, qw, backend="pallas")
    ref = qmatmul_ref(x, qw.packed, qw.scales, bits)
    assert out.shape == (M, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("M", [5, 130])
@pytest.mark.parametrize("N", [150, 192])
def test_qmm_ragged_n_pads_lanes(rng, bits, M, N):
    """N not a multiple of the 128 lane tile (and not itself a valid bn)
    zero-pads the packed columns + scales and slices the output back —
    both decode (M=5) and prefill (M=130) tiers."""
    K = 256
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    cfg = QConfig(bits=bits, channel_axis=-1)
    st = init_qstate(w, cfg)
    codes = quantize_int(w, st, cfg)
    qw = pack_weights(codes, st.scale.reshape(-1, N), bits)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    out = qmm(x, qw, backend="pallas")
    ref = qmatmul_ref(x, qw.packed, qw.scales, bits)
    assert out.shape == (M, N)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# decode tier: qgemv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("M", [1, 2, 5])
@pytest.mark.parametrize("group", [None, 64])
def test_qgemv_vs_qmatmul_ref(rng, bits, M, group):
    """Decode gemv (kernel + XLA ref) == the prefill oracle at small M."""
    K, N = 256, 128
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    cfg = QConfig(bits=bits, channel_axis=-1, group_size=group)
    st = init_qstate(w, cfg)
    codes = quantize_int(w, st, cfg)
    scales = st.scale.reshape(-1, N)
    packed = pack_weights(codes, scales, bits).packed
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    ref = qmatmul_ref(x, packed, scales, bits)
    out_ref = qgemv_ref(x, packed, scales, bits)
    out_kern = qgemv(x, packed, scales, bits=bits, interpret=True)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out_kern), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# grouped tier: stacked expert nodes
# ---------------------------------------------------------------------------


def _stacked_node(rng, E, K, N, bits, group=None):
    from repro.deploy import rtn_pack_leaf

    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    wp, qs = rtn_pack_leaf(w, bits, group)
    return {"w": wp, "qscale": qs}


@pytest.mark.parametrize("bits,group", [(8, None), (4, 64), (2, None),
                                        (3, None)])  # 3: int8-container case
def test_qmm_grouped_vs_dequant_einsum(rng, bits, group):
    """Grouped kernel + ref == transient dequant + grouped einsum (the
    path they replaced), incl. a W3 code in an int8 container."""
    from repro.deploy import dequant_leaf
    from repro.kernels.qmatmul.ops import from_node

    E, C, K, N = 3, 5, 128, 256
    node = _stacked_node(rng, E, K, N, bits, group)
    x = jnp.asarray(rng.normal(size=(E, C, K)), jnp.float32)
    w = dequant_leaf(node["w"], node["qscale"], K)
    ref = jnp.einsum("eck,ekn->ecn", x, w)

    qw = from_node(node, K)
    out_scan = qmm_grouped_ref(x, qw.packed, qw.scales, qw.bits)
    out_dense = qmm_grouped_dense_ref(x, qw.packed, qw.scales, qw.bits)
    out_kern = qmatmul_grouped(x, qw.packed, qw.scales, bits=qw.bits, bm=C,
                               interpret=True)
    out_qmm = qmm(x, qw, backend="xla")
    for got in (out_scan, out_dense, out_kern, out_qmm):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("C", [3, 16])  # scan (decode) / dense (prefill) refs
def test_qmm_grouped_batched_lead_dims(rng, C):
    """(B, E, C, K) activations keep the expert axis aligned to the
    stacked codes through the dispatcher (both backends)."""
    B, E, K, N = 2, 4, 64, 128
    node = _stacked_node(rng, E, K, N, 4)
    from repro.deploy import dequant_leaf
    from repro.kernels.qmatmul.ops import from_node

    x = jnp.asarray(rng.normal(size=(B, E, C, K)), jnp.float32)
    w = dequant_leaf(node["w"], node["qscale"], K)
    ref = jnp.einsum("beck,ekn->becn", x, w)
    qw = from_node(node, K)
    for backend in ("xla", "pallas"):
        out = qmm(x, qw, backend=backend)
        assert out.shape == (B, E, C, N)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


def test_qmm_wrapper_matches_dense(rng):
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    cfg = QConfig(bits=8, channel_axis=-1)
    st = init_qstate(w, cfg)
    codes = quantize_int(w, st, cfg)
    qw = pack_weights(codes, st.scale.reshape(-1, 128), 8)
    x = jnp.asarray(rng.normal(size=(4, 8, 256)), jnp.float32)
    out = qmm(x, qw, backend="pallas")
    dense = x @ (codes.astype(jnp.float32) * st.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-4)


@pytest.mark.parametrize("B,H,K,hd,S,bs", [
    (2, 8, 2, 64, 256, 128),
    (1, 4, 4, 32, 128, 128),
    (3, 4, 1, 128, 512, 256),  # MQA
])
@pytest.mark.parametrize("window", [None, 64])
def test_kvattn_vs_ref(rng, B, H, K, hd, S, bs, window):
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    k8, v8, ks, vs = quantize_kv(k, v)
    kpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    cur = jnp.asarray(rng.integers(S // 4, S, size=(B,)), jnp.int32)
    ref = kv_decode_ref(q, k8, v8, ks, vs, kpos, cur, window)
    out = kv_decode(q, k8, v8, ks, vs, kpos, cur, window=window, bs=bs,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_kvattn_int8_vs_fp_reference(rng):
    """int8 KV quantization error stays small vs full-precision attention."""
    from repro.models.common import decode_attend

    B, H, K, hd, S = 2, 4, 2, 64, 128
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    kpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    cur = jnp.full((B, 1), S - 1, jnp.int32)
    fp = decode_attend(q, k, v, kpos, cur)[:, 0]
    k8, v8, ks, vs = quantize_kv(k, v)
    q8out = attend_int8(q[:, 0], k8, v8, ks, vs, kpos, cur[:, 0], backend="xla")
    err = float(jnp.max(jnp.abs(fp - q8out)))
    assert err < 0.05, err


@pytest.mark.parametrize("hard", [False, True])
@pytest.mark.parametrize("K,N,per_row", [(256, 256, False), (64, 128, True)])
def test_fakequant_vs_ref(rng, hard, K, N, per_row):
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    srows = K if per_row else 1
    s = jnp.asarray(rng.uniform(0.01, 0.1, size=(srows, N)), jnp.float32)
    ref = fakequant_ref(w, v, s, -8, 7, hard)
    out = fakequant(w, v, s, qmin=-8, qmax=7, hard=hard, bk=64, bn=128,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_fakequant_matches_core_adaround(rng):
    """Kernel == core.adaround on the shared per-channel symmetric case."""
    from repro.core import adaround
    from repro.kernels.fakequant.ops import adaround_forward

    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    cfg = QConfig(bits=4, channel_axis=-1)
    st = init_qstate(w, cfg)
    v = adaround.init_v(w, st, cfg)
    for hard in (False, True):
        core = (adaround.hard_quant if hard else adaround.soft_quant)(w, v, st, cfg)
        kern = adaround_forward(w, v, st, cfg, hard=hard, backend="pallas")
        np.testing.assert_allclose(np.asarray(kern), np.asarray(core), atol=1e-5)


def test_fakequant_unsupported_config_raises_typed(rng):
    """Grouped or asymmetric configs the fused kernel does not cover
    raise KernelSpecError naming the config (used to be a bare assert
    with no message), and bad ranks name the offending shape."""
    import pytest

    from repro.kernels import KernelSpecError
    from repro.kernels.fakequant.ops import adaround_forward

    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    cfg = QConfig(bits=4, channel_axis=-1)
    st = init_qstate(w, cfg)
    v = jnp.zeros_like(w)

    grouped = QConfig(bits=4, channel_axis=-1, group_size=32)
    with pytest.raises(KernelSpecError, match="group_size=32"):
        adaround_forward(w, v, st, grouped)
    asym = QConfig(bits=4, channel_axis=-1, symmetric=False)
    with pytest.raises(KernelSpecError, match="symmetric=False"):
        adaround_forward(w, v, st, asym)
    with pytest.raises(KernelSpecError, match=r"\(64, 32, 1\)"):
        adaround_forward(w[..., None], v, st, cfg)
