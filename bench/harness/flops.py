"""Operations, bytes and least times on the chip: what every architecture
(``bench/archs/<arch>.py``) counts its work with. Work comes from the
model's sizes and the live rows and lengths the harness noted at each
program call: never from the padded shapes a program happens to run.

Activations are float32, the precision the served model states; packed
weights hold ``bits`` per code with one float32 scale per output column;
int8 KV holds one float16 scale per (token, kv head).
"""
from __future__ import annotations

ACT_BYTES = 4
SCALE_BYTES = 4
KV_SCALE_BYTES = 2


def qmm_work(rows: int, k: int, n: int, bits: int) -> tuple[float, float]:
    """(FLOPs, bytes) of ``rows`` live activation rows times one packed
    (k, n) weight: the codes and scales once, the rows in and out."""
    flops = 2.0 * rows * k * n
    nbytes = k * n * bits / 8 + n * SCALE_BYTES + rows * (k + n) * ACT_BYTES
    return flops, nbytes


def roofline_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take for the work."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def total(counts):
    """The sum of the calls' counts, or None if any call's is unknown: a
    reader reports nothing rather than a share of a partial count."""
    counts = list(counts)
    if any(c is None for c in counts):
        return None
    return sum(counts)
