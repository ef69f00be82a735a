"""Architecture modules (``bench/archs/<arch>.py``): how a configuration
names one, and the dense module pinned to the values the harness gave
before its architecture became a module.

The pinned values were recorded on the CPU from commit 9ae01b3, whose
``harness/weights.py``, ``harness/reference.py`` and ``harness/flops.py``
held the dense architecture themselves: the hash of every leaf of the
seed's float32 tree, the reference's logits at small widths, and the
work counts at InternLM2-20B's sizes. Nothing may move them: the served
weights, the reference that decides ``correct``, and every roofline and
``mfu`` share rest on them.
"""
import hashlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from harness import common as C
from harness.reference import Reference
from harness.trace import Trace

A = C.load_arch({"arch": "dense_gqa"})
CONFIG = C.load_json(C.ROOT / "bench/configs/internlm2-20b.json")
SEED = 2**31 + 12345
PEAKS = C.load_peaks("TPU v5 lite")

LEAVES = {
    "['body']['sub0']['attn']['wk']['w']": "62b83ecc9840a5ef",
    "['body']['sub0']['attn']['wo']['w']": "1d008fe6f216bc58",
    "['body']['sub0']['attn']['wq']['w']": "8d942a2a9b4f9348",
    "['body']['sub0']['attn']['wv']['w']": "78163da4b569f4cb",
    "['body']['sub0']['mlp']['w_down']['w']": "4bdeff46eab4bb6e",
    "['body']['sub0']['mlp']['w_gate']['w']": "f1a319de68598a0d",
    "['body']['sub0']['mlp']['w_up']['w']": "60d087f90df8e179",
    "['body']['sub0']['norm1']['g']": "e75a8ea9e43524f2",
    "['body']['sub0']['norm2']['g']": "ef32c00f9a7c8f8c",
    "['embed']['table']": "133fe3b5b0f13d52",
    "['final_norm']['g']": "001cd50ea21fe148",
    "['head']['w']": "dd3f70b712f81f07",
}
LOGITS = {None: "a06a75b19002da78", "float8_e4m3fn": "ef66fef25af2db64"}
COUNTS = {
    "qmm_min_time rows 1": (lambda d: A.qmm_min_time(d, 1, 4, PEAKS),
                            0.005473550065934065),
    "qmm_min_time rows 7": (lambda d: A.qmm_min_time(d, 7, 4, PEAKS),
                            0.005538857260073259),
    "qmm_min_time rows 32": (lambda d: A.qmm_min_time(d, 32, 4, PEAKS),
                             0.005810970568986569),
    "kv_min_time": (lambda d: A.kv_min_time(d, [100, 30, 2047], PEAKS),
                    0.00011417865689865689),
    "model_flops_decode": (lambda d: A.model_flops_decode(d, [10, 20, 2000]),
                           51217760256.0),
    "model_flops_prefill 32 at 0": (lambda d: A.model_flops_prefill(d, 32, 0),
                                    500686651392.0),
    "model_flops_prefill 17 at 96": (lambda d: A.model_flops_prefill(d, 17, 96),
                                     267262328832.0),
}


def _small():
    return A.dims({**CONFIG, **A.small})


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def small_tree():
    tree = A.served_maker(_small(), lambda t: t)(*C.split_seed(SEED))
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_dense_weights_pinned(small_tree, leaf):
    assert set(small_tree) == set(LEAVES)
    assert _digest(small_tree[leaf]) == LEAVES[leaf]


@pytest.mark.parametrize("operands", sorted(LOGITS, key=str))
def test_dense_reference_logits_pinned(operands):
    d = _small()
    toks = (np.arange(2 * 12).reshape(2, 12) * 37 % 512).astype(np.int32)
    pos = np.array([[3, 7, 11], [0, 5, 9]], np.int32)
    ref = Reference(A, d, 4, C.split_seed(SEED), operands)
    assert _digest(ref.logits(toks, pos)) == LOGITS[operands]


@pytest.mark.parametrize("module,kinds", [("dense_gqa", 1), ("twin_moe", 2)])
def test_reference_compiles_once_per_layer_kind(module, kinds):
    """Every layer of one kind runs through one compiled layer maker and
    one compiled block, the layer index traced; a module whose layer 0
    has leaves of its own (``tests/data/twin_moe.py``) gets two of each."""
    arch = A if module == "dense_gqa" else C._load_module(
        C.BENCH_DIR / "tests" / "data" / "twin_moe.py", "bench_arch_twin_moe")
    d = arch.dims({**CONFIG, **arch.small, "num_hidden_layers": 3})
    ref = Reference(arch, d, 4, C.split_seed(SEED))
    toks = (np.arange(2 * 12).reshape(2, 12) * 37 % 512).astype(np.int32)
    out = ref.logits(toks, np.array([[3, 11], [0, 9]], np.int32))
    assert out.shape == (2, 2, d.vocab) and np.isfinite(out).all()
    assert len(ref.kinds) == d.n_layers >= 3
    assert ref._layer._cache_size() == ref._blocks._cache_size() == kinds


@pytest.mark.parametrize("count", sorted(COUNTS))
def test_dense_counts_pinned(count):
    fn, value = COUNTS[count]
    assert fn(A.dims(CONFIG)) == value


def test_dense_reader_counts_take_the_notes():
    """The counts the readers call, on notes as the harness takes them."""
    d = A.dims(CONFIG)
    lens, chunk = [100, 30, 2047], (17, 96)
    assert A.decode_qmm_s(d, lens, 4, PEAKS) == A.qmm_min_time(d, 3, 4, PEAKS)
    assert A.chunk_qmm_s(d, chunk, 4, PEAKS) == A.qmm_min_time(d, 17, 4, PEAKS)
    assert A.chunk_flops(d, chunk) == A.model_flops_prefill(d, 17, 96)


def test_dense_arch_config():
    d = A.dims(CONFIG)
    ac = A.arch_config(CONFIG, d)
    assert (ac.family, ac.n_layers, ac.d_model, ac.n_heads, ac.n_kv_heads,
            ac.d_ff, ac.vocab, ac.hd, ac.tie_embeddings) == (
        "dense", 20, 6144, 48, 8, 16384, 92544, 128, False)


@pytest.mark.parametrize("config", [{"name": "x", "arch": "no_such_arch"},
                                    {"name": "x"}], ids=["unknown", "missing"])
def test_unknown_or_missing_arch_is_refused(config):
    with pytest.raises(C.BenchError, match=r"bench/archs has \['dense_gqa'"):
        C.load_arch(config)


def test_every_configuration_names_an_architecture():
    bench = C.load_benchmark()
    for c in bench["configs"]:
        config = C.load_json(C.ROOT / c["file"])
        assert C.load_arch(config).dims(config).vocab > 0


# -- a count that is not known makes its reader report nothing -------------

COUNTING = {"qmm_roofline.decode": "decode_qmm_s",
            "qmm_roofline.prefill": "chunk_qmm_s",
            "kv_roofline.decode": "kv_min_time",
            "mfu.decode": "model_flops_decode", "mfu.prefill": "chunk_flops"}


def _ctx(arch):
    # a decode program 10..50 and a chunk program 60..80, each with a
    # qmatmul kernel; the decode program also runs kv_decode
    ops = [["%qmatmul.3", 15, 10, {"kernel": 1}],
           ["%kv_decode.4", 26, 4, {"kernel": 1}],
           ["%qmatmul.7", 62, 8, {"kernel": 1}]]
    mods = [["jit_decode_fn", 10, 40], ["jit_chunk_fn", 60, 20]]
    rec = {"device": {"XLA Ops": ops, "XLA Modules": mods}, "n_devices": 1,
           "spans": [["bench.window", 0, 100]]}
    run = SimpleNamespace(decode_calls=[[40, 12]], chunk_calls=[(20, 0)],
                          q={"w_bits": 4})
    return SimpleNamespace(run=run, trace=Trace(rec), arch=arch,
                           dims=A.dims(CONFIG), peaks=PEAKS)


@pytest.mark.parametrize("reader", sorted(COUNTING))
def test_reader_reports_nothing_for_an_unknown_count(reader):
    read = C.load_reader(reader)
    assert read(_ctx(A)) > 0
    unknown = SimpleNamespace(**{COUNTING[reader]: lambda *a: None})
    assert read(_ctx(unknown)) is None


def test_notes_come_from_the_module_where_it_has_them():
    """``ServeRun`` notes each program call with the architecture
    module's ``decode_note`` / ``chunk_note``, and the readers get the
    notes as they were taken."""
    from harness.serve import ServeRun

    run = ServeRun.__new__(ServeRun)
    run.arch = SimpleNamespace(decode_note=lambda eng: ("decode", eng.tag),
                               chunk_note=lambda eng: ("chunk", eng.tag))
    run.engine = SimpleNamespace(tag=7, _decode_c=lambda *a: "d",
                                 _chunk_c=lambda *a: "c")
    run._wrap_programs()
    run._recording(True)
    assert run.engine._decode_c(1) == "d" and run.engine._chunk_c(2) == "c"
    run.engine._decode_c(3)
    run._recording(False)
    assert run.decode_calls == [("decode", 7)] * 2
    assert run.chunk_calls == [("chunk", 7)]
