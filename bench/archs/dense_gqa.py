"""A dense GQA decoder with RoPE, RMSNorm and a SwiGLU feed-forward: one
architecture of the benchmark, chosen by a configuration's ``"arch":
"dense_gqa"``.

An architecture module gives the harness (``harness/common.py``
``load_arch``):

* ``dims(config)``: the sizes, at least ``vocab``, ``d_model``, ``tie``
  and ``norm_eps`` (traffic, warm-up, the shared embedding and head);
* ``arch_config(config, dims)``: the program's ``ArchConfig``;
* ``served_maker(dims, pack)``: the jitted set-up from the seed halves
  to the served weights;
* ``small``: the keys the CPU rehearsal overrides to run the cell at
  small widths;
* ``reference_weights(dims, w_bits) -> (outer, layer)``,
  ``reference_layers(dims)`` and ``block(dims, i, w, x, operands, kv8,
  kind)``: the plain reference's weights, depth and layer ``i``
  (``harness/reference.py`` ``Reference``); the ``layer(lo, hi, i,
  kind)`` it returns makes layer ``i``'s leaves as the reference uses
  them;
* optionally ``layer_kind(dims, i)``: a static key (a string, a number)
  for layers that differ in their leaves or code, such as a leading
  dense layer before sparse-expert layers, or window and global
  attention. ``layer`` and ``block`` get it as ``kind`` and may branch
  on it; each kind compiles once, with ``i`` traced. Without it every
  layer is of kind 0, as here;
* the work of one program call, from the note the harness took at that
  call (``None`` where the call's work is not known): ``decode_qmm_s``,
  ``chunk_qmm_s``, ``kv_min_time`` (least seconds on the chip), and
  ``model_flops_decode``, ``chunk_flops`` (model FLOPs);
* optionally ``decode_note(engine)`` and ``chunk_note(engine)``, the
  notes themselves; without them the harness notes a decode call's live
  stream lengths and a chunk call's ``(rows, offset)``
  (``harness/serve.py``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from harness import flops as F
from harness import weights as W
from harness.reference import (HIGHEST, _rounder, kv_int8, rmsnorm, rope,
                               rtn)

LAYER_LEAVES = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
                "w_down")

# the CPU rehearsal's widths
small = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
             vocab_size=512)


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder with a SwiGLU feed-forward."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        heads = c["num_attention_heads"]
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=heads, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tie=c["tie_word_embeddings"],
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]))

    def leaf_shape(self, leaf: str) -> tuple[int, ...]:
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv_heads * self.head_dim, self.d_ff)
        return {"norm1": (d,), "norm2": (d,), "wq": (d, q), "wk": (d, kv),
                "wv": (d, kv), "wo": (q, d), "w_gate": (d, f), "w_up": (d, f),
                "w_down": (f, d)}[leaf]


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def arch_config(config: dict, d: Dims):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=config["name"], family="dense", n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        d_ff=d.d_ff, vocab=d.vocab, head_dim=d.head_dim,
        rope_theta=d.rope_theta, tie_embeddings=d.tie)


# -- weights ------------------------------------------------------------------

def layer(d: Dims, lo, hi, i) -> dict:
    """Layer ``i``'s float32 leaves."""
    return W.leaves(W.layer_key(lo, hi, i),
                    {leaf: d.leaf_shape(leaf) for leaf in LAYER_LEAVES})


def program_tree(d: Dims, lo, hi) -> dict:
    """The whole float32 tree in the layout of ``repro.models.LM``."""
    o = W.outer(d, lo, hi)
    layers = [layer(d, lo, hi, i) for i in range(d.n_layers)]

    def stack(leaf):
        return jnp.stack([lay[leaf] for lay in layers])

    tree = {"embed": {"table": o["embed"]}, "final_norm": {"g": o["final_norm"]},
            "body": {"sub0": {
                "norm1": {"g": stack("norm1")},
                "attn": {n: {"w": stack(n)} for n in ("wq", "wk", "wv", "wo")},
                "norm2": {"g": stack("norm2")},
                "mlp": {n: {"w": stack(n)} for n in ("w_gate", "w_up", "w_down")},
            }}}
    if not d.tie:
        tree["head"] = {"w": o["head"]}
    return tree


def served_maker(d: Dims, pack):
    """One jitted program from the seed to the served weights:
    ``pack`` (the program's packing transform) over the float32 tree, so
    the float32 leaves live only inside the program."""
    return jax.jit(lambda lo, hi: pack(program_tree(d, lo, hi)))


# -- the plain reference ------------------------------------------------------

def reference_weights(d: Dims, w_bits: int):
    """(outer, layer) weight makers of the served model: the seed's
    weights under the deployment's round-to-nearest. Both take the seed
    halves as traced arguments, so one compiled program serves every
    seed."""

    def outer(lo, hi):
        o = W.outer(d, lo, hi)
        emb = rtn(o["embed"], 8)
        return emb, o["final_norm"], (emb.T if d.tie else rtn(o["head"], 8))

    def layer_w(lo, hi, i, kind=0):
        return {n: (a if n.startswith("norm") else rtn(a, w_bits))
                for n, a in layer(d, lo, hi, i).items()}

    return outer, layer_w


def reference_layers(d: Dims) -> int:
    return d.n_layers


def matmuls(operands=None):
    """(matmul, einsum) at ``HIGHEST`` with each operand rounded to
    ``operands`` (as they are for None)."""
    r = _rounder(operands)

    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    def es(spec, a, b):
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    return mm, es


def attend(d: Dims, w: dict, x, operands=None, kv8: bool = True):
    """``x`` plus its causal GQA self-attention with RoPE, over one
    request's (S, d) hidden states (``norm1``, ``wq``, ``wk``, ``wv``,
    ``wo``); K and V through int8 where ``kv8``."""
    mm, es = matmuls(operands)
    S = x.shape[0]
    H, K, hd = d.n_heads, d.n_kv_heads, d.head_dim
    h = rmsnorm(x, w["norm1"], d.norm_eps)
    q = rope(mm(h, w["wq"]).reshape(S, H, hd), d.rope_theta)
    k = rope(mm(h, w["wk"]).reshape(S, K, hd), d.rope_theta)
    v = mm(h, w["wv"]).reshape(S, K, hd)
    if kv8:
        k, v = kv_int8(k), kv_int8(v)
    qg = q.reshape(S, K, H // K, hd)
    s = es("qkgd,pkd->kgqp", qg, k) / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = es("kgqp,pkd->qkgd", a, v).reshape(S, H * hd)
    return x + mm(o, w["wo"])


def swiglu(w: dict, h, operands=None):
    """The SwiGLU feed-forward (``w_gate``, ``w_up``, ``w_down``) of ``h``."""
    mm, _ = matmuls(operands)
    return mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]), w["w_down"])


def block(d: Dims, i, w: dict, x, operands=None, kv8: bool = True, kind=0):
    """Decoder layer ``i`` over one request's (S, d) hidden states; every
    layer is alike here."""
    x = attend(d, w, x, operands, kv8)
    return x + swiglu(w, rmsnorm(x, w["norm2"], d.norm_eps), operands)


# -- work counts --------------------------------------------------------------

def layer_linears(d: Dims) -> list[tuple[int, int]]:
    """(K, N) of one layer's linear weights."""
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    return [(d.d_model, q), (d.d_model, kv), (d.d_model, kv), (q, d.d_model),
            (d.d_model, d.d_ff), (d.d_model, d.d_ff), (d.d_ff, d.d_model)]


def kv_decode_work(d: Dims, lengths) -> tuple[float, float]:
    """(FLOPs, bytes) of one int8 KV decode read over every layer: each
    live stream's K and V codes and scales up to its own length, its
    query in and its output out."""
    toks = float(sum(lengths))
    per_tok = d.n_kv_heads * (2 * d.head_dim + 2 * F.KV_SCALE_BYTES)
    qo = len(lengths) * 2 * d.n_heads * d.head_dim * F.ACT_BYTES
    flops = 4.0 * d.n_heads * d.head_dim * toks
    return d.n_layers * flops, d.n_layers * (toks * per_tok + qo)


def layer_params(d: Dims) -> int:
    return sum(k * n for k, n in layer_linears(d))


def model_flops_decode(d: Dims, lengths) -> float:
    """Model FLOPs of one decode step for the live streams (each at its
    own length): every matmul and the attention over the stream's
    cache, embedding lookups not counted."""
    rows = len(lengths)
    mat = 2.0 * rows * (d.n_layers * layer_params(d) + d.d_model * d.vocab)
    att = 4.0 * d.n_layers * d.n_heads * d.head_dim * float(sum(lengths))
    return mat + att


def model_flops_prefill(d: Dims, rows: int, offset: int) -> float:
    """Model FLOPs of one prefill chunk: ``rows`` real prompt tokens at
    positions ``offset``.. through every layer, each attending to the
    keys up to its own position, and the head for the chunk's last row
    (the only logits a prompt needs)."""
    keys = rows * offset + rows * (rows + 1) / 2
    att = 4.0 * d.n_layers * d.n_heads * d.head_dim * keys
    return 2.0 * rows * d.n_layers * layer_params(d) + att + 2.0 * d.d_model * d.vocab


def qmm_min_time(d: Dims, rows: int, w_bits: int, peaks: dict,
                 head_bits: int = 8) -> float:
    """Least time for every packed matmul of one step, matrix by matrix."""
    t = d.n_layers * sum(F.roofline_time(*F.qmm_work(rows, k, n, w_bits), peaks)
                         for k, n in layer_linears(d))
    if not d.tie:
        t += F.roofline_time(*F.qmm_work(rows, d.d_model, d.vocab, head_bits),
                             peaks)
    return t


def kv_min_time(d: Dims, lengths, peaks: dict) -> float:
    """Least time for one decode step's int8 KV reads, layer by layer."""
    f, b = kv_decode_work(d, lengths)
    return d.n_layers * F.roofline_time(f / d.n_layers, b / d.n_layers, peaks)


# What the readers call, once per noted program call, with the note as
# the harness took it: a decode call's live stream lengths (also for
# ``kv_min_time`` and ``model_flops_decode``), a prefill chunk's
# (rows, offset).

def decode_qmm_s(d: Dims, lengths, w_bits: int, peaks: dict) -> float:
    return qmm_min_time(d, len(lengths), w_bits, peaks)


def chunk_qmm_s(d: Dims, chunk, w_bits: int, peaks: dict) -> float:
    return qmm_min_time(d, chunk[0], w_bits, peaks)


def chunk_flops(d: Dims, chunk) -> float:
    return model_flops_prefill(d, *chunk)
