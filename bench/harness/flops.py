"""Operations and bytes that a step needs, from the model's sizes and the
live rows and lengths the harness recorded: never from the padded
shapes a program happens to run.

Activations are float32, the precision the served model states; packed
weights hold ``bits`` per code with one float32 scale per output column;
int8 KV holds one float16 scale per (token, kv head).
"""
from __future__ import annotations

from .weights import Dims

ACT_BYTES = 4
SCALE_BYTES = 4
KV_SCALE_BYTES = 2


def qmm_work(rows: int, k: int, n: int, bits: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``rows`` live activation rows times one packed
    (k, n) weight: the codes and scales once, the rows in and out."""
    flops = 2.0 * rows * k * n
    nbytes = k * n * bits / 8 + n * SCALE_BYTES + rows * (k + n) * ACT_BYTES
    return flops, nbytes


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
    per_tok = d.n_kv_heads * (2 * d.head_dim + 2 * KV_SCALE_BYTES)
    qo = len(lengths) * 2 * d.n_heads * d.head_dim * ACT_BYTES
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


def roofline_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for the work."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def qmm_min_time(d: Dims, rows: int, w_bits: int, peaks: dict,
                 head_bits: int = 8) -> float:
    """Least time for every packed matmul of one step, matrix by matrix."""
    t = d.n_layers * sum(roofline_time(*qmm_work(rows, k, n, w_bits), peaks)
                         for k, n in layer_linears(d))
    if not d.tie:
        t += roofline_time(*qmm_work(rows, d.d_model, d.vocab, head_bits), peaks)
    return t


def kv_min_time(d: Dims, lengths, peaks: dict) -> float:
    """Least time for one decode step's int8 KV reads, layer by layer."""
    f, b = kv_decode_work(d, lengths)
    return d.n_layers * roofline_time(f / d.n_layers, b / d.n_layers, peaks)
