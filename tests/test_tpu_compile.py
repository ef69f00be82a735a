"""Compile rehearsals of the serving kernels for a described TPU v5e.

Each test lowers and compiles Pallas kernels of the serving path at
``brecq_lm_100m``'s published widths for a v5e chip that is described,
not attached (the TPU compiler ships with jax), with the tiles the ops
wrappers pick, and checks that the compiled program holds the Mosaic
kernel (``tpu_custom_call``). What the chip's compiler refuses — a block
that does not tile, an op Mosaic cannot legalize, too much VMEM — fails
here without a chip. Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.kvattn.kernel import kv_decode
from repro.kernels.qmatmul.kernel import qgemv, qmatmul, qmatmul_grouped
from repro.kernels.spec import kv_stream_tile, qmm_row_tile

# brecq_lm_100m's (K, N) pairs: wq/wk/wv/wo, w_up/w_gate, w_down and the
# tied 8192-way head.
QMM_SHAPES = [(768, 768), (768, 2048), (2048, 768), (768, 8192)]
DECODE_M = 8      # engine decode batch: num_slots rows
PREFILL_M = 32    # one prefill chunk of one stream
EXPERTS = 4


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off around the compiles (a cache entry compiled for a described
    chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"


@pytest.mark.parametrize("grouped", [False, True], ids=["per_channel", "g64"])
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("tier", ["qgemv", "qmatmul", "qmatmul_grouped"])
def test_qmm_tier_compiles_for_v5e(one_chip, tier, bits, grouped):
    per = 8 // bits
    for K, N in QMM_SHAPES:
        if tier == "qmatmul_grouped" and N == 8192:
            continue  # the head is never a stacked expert node
        G = K // 64 if grouped else 1
        w = ((K // per, N), jnp.int8)
        s = ((G, N), jnp.float32)
        if tier == "qgemv":
            fn = lambda x, wp, sc: qgemv(x, wp, sc, bits=bits, bn=128)
            _compile(fn, ((DECODE_M, K), jnp.float32), w, s, sharding=one_chip)
        elif tier == "qmatmul":
            bm = qmm_row_tile(PREFILL_M, K, G, bits=bits, bn=128)
            fn = lambda x, wp, sc: qmatmul(x, wp, sc, bits=bits, bm=bm, bn=128)
            _compile(fn, ((PREFILL_M, K), jnp.float32), w, s, sharding=one_chip)
        else:
            bm = qmm_row_tile(PREFILL_M, K, G, bits=bits, bn=128)
            fn = lambda x, wp, sc: qmatmul_grouped(x, wp, sc, bits=bits,
                                                   bm=bm, bn=128)
            lead = lambda sh: ((EXPERTS, *sh[0]), sh[1])
            _compile(fn, lead(((PREFILL_M, K), jnp.float32)), lead(w),
                     lead(s), sharding=one_chip)


@pytest.mark.parametrize("S", [288, 512])
@pytest.mark.parametrize("H,K", [(12, 12), (32, 4)], ids=["G1", "G8"])
def test_kv_decode_compiles_for_v5e(one_chip, H, K, S):
    """G=1 is brecq_lm_100m's head layout, G=8 a GQA layout
    (tinyllama_1_1b); S=288 is the smoke engine's max_len, which has no
    128-row divisor and streams in one step."""
    B, hd = DECODE_M, 64
    fn = lambda *a: kv_decode(*a, bs=kv_stream_tile(S))
    _compile(fn, ((B, H, hd), jnp.float32), ((B, S, K, hd), jnp.int8),
             ((B, S, K, hd), jnp.int8), ((B, S, K), jnp.float32),
             ((B, S, K), jnp.float32), ((B, S), jnp.int32), ((B,), jnp.int32),
             sharding=one_chip)
