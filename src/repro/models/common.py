"""Shared building blocks for all model families.

Conventions
-----------
* Params are nested dicts of ``jnp`` arrays. A linear layer is
  ``{'w': (in, out)}`` (+ optional ``'b'``). Weight layout is always
  (reduction_dim, output_dim) so quantization group axes are uniform.
* Every matmul goes through :func:`dense`, which consults the quant
  context ``ctx.quant`` — the single hook BRECQ needs inside models.
* ``ctx`` is a :class:`Ctx` carrying config, positions, masks and the
  quant hook. It is closed over by scan bodies; all array members are
  valid tracers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Any


# ---------------------------------------------------------------------------
# quant hook
# ---------------------------------------------------------------------------


class QuantHook:
    """Interface the models call; the default is a no-op (FP model).

    ``weight(name, w)``: returns the (possibly fake-quantized) weight.
    ``act(name, x)``: returns the (possibly fake-quantized) activation.
    The BRECQ engine installs real implementations during calibration;
    the serving path installs a baked/LSQ variant.

    Weight-provider protocol: when a params node carries packed int
    codes (a ``qscale`` sibling — the `repro.deploy` artifact format),
    :func:`dense`/:func:`lm_head` hand the whole matmul to
    ``packed_matmul`` instead of materializing an f32 weight. The
    default executes via the packed ``qmm`` dispatcher (weights stay int
    codes in HBM; dequant happens tile-wise in-register), after routing
    the activation through ``act`` so serve-time LSQ still applies.
    ``qmm`` picks the execution tier by shape — decode gemv for M up to
    a sublane of rows, the tiled prefill GEMM otherwise, and the grouped
    expert kernel for stacked 3-D nodes (x is then (..., E, C, K)).
    ``packed_backend`` picks the qmm execution path ('auto': Pallas on
    TPU, XLA reference elsewhere). Callers that already applied
    activation fake-quant themselves (the MoE layer shares one
    quantized activation across its gate/up matmuls) pass
    ``apply_act=False``.
    """

    packed_backend: str = "auto"

    def weight(self, name: str, w: Array) -> Array:
        return w

    def act(self, name: str, x: Array) -> Array:
        return x

    def packed_matmul(self, name: str, x: Array, node: Params,
                      apply_act: bool = True) -> Array:
        from ..kernels.qmatmul.ops import from_node, qmm

        if apply_act:
            x = self.act(name, x)
        return qmm(x, from_node(node, x.shape[-1], path=name),
                   backend=self.packed_backend)


NO_QUANT = QuantHook()


@dataclasses.dataclass
class Ctx:
    """Per-forward context threaded through blocks."""

    cfg: Any
    positions: Array  # (B, S) absolute positions of the current tokens
    quant: QuantHook = dataclasses.field(default_factory=lambda: NO_QUANT)
    deterministic: bool = True
    # decode-time info
    decode: bool = False
    cache_index: Optional[Array] = None  # scalar: #tokens already cached
    # modality extras (VLM image embeds, enc-dec memory)
    extras: dict = dataclasses.field(default_factory=dict)
    # name scope for quant hook paths
    scope: str = ""

    def scoped(self, name: str) -> "Ctx":
        return dataclasses.replace(self, scope=f"{self.scope}/{name}" if self.scope else name)


# ---------------------------------------------------------------------------
# initialisation helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> Params:
    scale = 1.0 / jnp.sqrt(d_in)
    return {"w": jax.random.uniform(key, (d_in, d_out), dtype, -scale, scale)}


def dense(ctx: Ctx, p: Params, name: str, x: Array) -> Array:
    """Quant-aware linear: x @ W. The only matmul entry point.

    A ``qscale`` sibling marks a packed-int deployment weight
    (`repro.deploy` artifact format); it is executed through the quant
    hook's weight-provider (``packed_matmul`` -> ``qmm``), with bits and
    group inferred from the shapes.
    """
    node = p[name]
    path = f"{ctx.scope}/{name}" if ctx.scope else name
    if "qscale" in node:
        y = ctx.quant.packed_matmul(path, x, node)
    else:
        w = ctx.quant.weight(path, node["w"])
        x = ctx.quant.act(path, x)
        y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if "b" in node:
        y = y + node["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    """Variance reduced in f32; normalization stays in x.dtype.

    Deliberate: a full f32 copy of the hidden state as the first op of a
    rematerialized block gets loop-hoisted by XLA into an f32 replica of
    the whole saved-activation stack (~2x remat memory). The f32->reduce
    chain here fuses into the reduction instead.
    """
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * p["g"].astype(x.dtype)


def layernorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True).astype(x.dtype)
    var = jnp.var(x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x - mu) * inv * p["g"].astype(x.dtype) + p["b"].astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (B, S, H, hd); positions: (B, S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32) * 0.02}


def embed_lookup(ctx: Ctx, p: Params, tokens: Array) -> Array:
    if "table_qscale" in p:  # int8 deployment table: gather, then dequant
        rows = jnp.take(p["table"], tokens, axis=0).astype(jnp.float32)
        return rows * p["table_qscale"][0]
    table = ctx.quant.weight("embed/table", p["table"])
    return jnp.take(table, tokens, axis=0)


def lm_head(ctx: Ctx, p: Params, x: Array) -> Array:
    """Output projection to vocab logits; may be tied to the embedding.

    ``p`` is either a head node (``{"w": (d, V)}``, possibly packed with
    a ``qscale``) or — when embeddings are tied — the embedding node
    itself (``{"table": (V, d)}``, possibly int8 with ``table_qscale``).
    """
    if "qscale" in p:
        return ctx.quant.packed_matmul("head/w", x, p)
    if "table_qscale" in p:  # tied to an int8 table: (V, d) -> (d, V)
        w = (p["table"].astype(jnp.float32) * p["table_qscale"][0]).T
    elif "table" in p:  # tied FP table
        w = ctx.quant.weight("head/w", p["table"].T)
        x = ctx.quant.act("head/w", x)
    else:
        w = ctx.quant.weight("head/w", p["w"])  # (d, vocab)
        x = ctx.quant.act("head/w", x)
    return jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_xent(logits: Array, labels: Array, mask: Optional[Array] = None) -> Array:
    """Mean next-token cross entropy. logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# attention masks
# ---------------------------------------------------------------------------


def causal_mask(q_pos: Array, k_pos: Array, window: Optional[int] = None) -> Array:
    """(..., Sq, Sk) boolean mask. ``window`` enables sliding-window attn."""
    m = q_pos[..., :, None] >= k_pos[..., None, :]
    if window is not None:
        m = m & (q_pos[..., :, None] - k_pos[..., None, :] < window)
    return m


MASK_VALUE = -1e30


def mha(q: Array, k: Array, v: Array, mask: Optional[Array]) -> Array:
    """Plain attention. q: (B,Sq,H,hd), k/v: (B,Sk,K,hd) with GQA repeat.

    Suitable for short sequences; long-sequence paths use
    :func:`chunked_attention`.
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, None] if mask.ndim == 3 else mask, scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    q_pos: Array,
    k_pos: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    iota_pos: bool = False,
) -> Array:
    """Memory-efficient (flash-style) attention via double lax.scan.

    Online-softmax over KV chunks, scanned over Q chunks. Peak transient
    is (B, H, q_chunk, kv_chunk) instead of (B, H, Sq, Sk). This is the
    XLA path; the Pallas TPU kernel mirrors the same schedule.

    ``iota_pos=True`` asserts positions are plain aranges (train/prefill):
    masks are then derived from broadcasted iota + scalar chunk offsets,
    so XLA never materializes position-dependent mask stacks (those
    dominate memory otherwise), and fully-masked KV chunks contribute a
    constant that folds away.

    q: (B,Sq,H,hd) k/v: (B,Sk,K,hd) q_pos: (B,Sq) k_pos: (B,Sk)
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % kv_chunk == 0, (Sq, q_chunk, Sk, kv_chunk)
    rep = H // K
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    scale = 1.0 / jnp.sqrt(hd)

    qc = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(B, nk, kv_chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, kv_chunk, K, hd).transpose(1, 0, 2, 3, 4)
    if iota_pos:
        qp = jnp.arange(nq, dtype=jnp.int32) * q_chunk  # chunk start offsets
        kp = jnp.arange(nk, dtype=jnp.int32) * kv_chunk
        rel = (jnp.arange(q_chunk, dtype=jnp.int32)[:, None]
               - jnp.arange(kv_chunk, dtype=jnp.int32)[None, :])  # (qc, kc)
    else:
        qp = q_pos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
        kp = k_pos.reshape(B, nk, kv_chunk).transpose(1, 0, 2)

    def q_step(_, q_in, kv_lo=0, kv_hi=nk):
        qi, qpi = q_in  # (B, qc, H, hd), (B, qc) or scalar chunk offset

        def kv_step(carry, kv_in):
            m_prev, l_prev, acc = carry
            ki, vi, kpi = kv_in  # (B, kc, K, hd), (B, kc) or scalar
            if rep != 1:
                ki = jnp.repeat(ki, rep, axis=2)
                vi = jnp.repeat(vi, rep, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, ki).astype(jnp.float32) * scale
            if iota_pos:
                # delta(q_abs - k_abs) = rel + (q0 - k0); mask from scalars
                delta = rel + (qpi - kpi)  # (qc, kc)
                mask = delta >= 0 if causal else jnp.full_like(delta, True, bool)
                if window is not None:
                    mask = mask & (delta < window)
                if causal or window is not None:
                    s = jnp.where(mask[None, None], s, MASK_VALUE)
            else:
                mask = qpi[:, None, :, None] >= kpi[:, None, None, :] if causal else True
                if window is not None:
                    mask = mask & (qpi[:, None, :, None] - kpi[:, None, None, :] < window)
                if causal or window is not None:
                    s = jnp.where(mask, s, MASK_VALUE)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vi.dtype), vi
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (
            jnp.full((B, H, q_chunk), -jnp.inf, jnp.float32),
            jnp.zeros((B, H, q_chunk), jnp.float32),
            jnp.zeros((B, H, q_chunk, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (kc[kv_lo:kv_hi], vc[kv_lo:kv_hi], kp[kv_lo:kv_hi]))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, qc, H, hd)

    if iota_pos and causal and q_chunk == kv_chunk and Sq == Sk and nq <= 8:
        # Triangle unroll: q-chunk loop unrolled in python with statically
        # bounded inner KV scans — fully-masked chunk pairs are never
        # computed (2x fewer attention FLOPs/bytes; more with a window).
        # Bounded to nq<=8: at 32k (nq=32) the unroll made GSPMD reshard
        # k/v per chunk and collectives grew 5.6x (measured, cell A).
        outs = []
        for i in range(nq):
            lo = 0
            if window is not None:
                lo = max(0, (i * q_chunk - (window - 1)) // kv_chunk)
            _, o = q_step(None, (qc[i], qp[i]), kv_lo=lo, kv_hi=i + 1)
            outs.append(o)
        return jnp.stack(outs, 0).transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)

    _, outs = jax.lax.scan(q_step, None, (qc, qp))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def decode_attend(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    k_pos: Array,
    cur_pos: Array,
    *,
    window: Optional[int] = None,
    shard=None,
) -> Array:
    """Decode attention against a cache for one or a few query tokens.

    GQA-native (no head-repeat of the cache): the cache stays in its
    (B, S, K, hd) layout — typically sequence-sharded — and the grouped
    einsums contract against it in place. ``shard`` optionally pins the
    score sharding so GSPMD keeps the reduction distributed.

    q: (B,C,H,hd) — C=1 for single-token decode, C>1 for a chunked
    prefill step reading KV already appended to the cache (per-token
    causality falls out of the position mask); caches (B,S,K,hd); k_pos
    (B,S) absolute positions of cache slots (-1 for empty); cur_pos
    (B,C) current position of each query token.
    """
    B, C, H, hd = q.shape
    K = k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, C, K, G, hd)
    s = jnp.einsum("bckgd,bskd->bckgs", qg, k_cache).astype(jnp.float32)
    s = s / jnp.sqrt(hd)
    valid = (k_pos[:, None] >= 0) & (k_pos[:, None] <= cur_pos[..., None])
    if window is not None:
        valid = valid & (cur_pos[..., None] - k_pos[:, None] < window)
    s = jnp.where(valid[:, :, None, None, :], s, MASK_VALUE)
    if shard is not None:
        s = shard(s, "scores")
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bckgs,bskd->bckgd", p, v_cache)
    return out.reshape(B, C, H, hd)


# ---------------------------------------------------------------------------
# paged KV cache (serve engine)
# ---------------------------------------------------------------------------
#
# The serve engine stores KV in a global page pool per attention layer
# instead of one dense (B, S, K, hd) buffer per stream. A *page* holds
# ``page_size`` consecutive token slots for every kv head; a stream owns
# an ordered list of pages (its *block table* row, shared by all layers
# since every layer caches the same token sequence). Token at absolute
# position ``t`` always lives at row ``t`` of its stream's gathered view
# (identity layout: page ``t // page_size``, offset ``t % page_size``),
# so masks reduce to plain position comparisons and batched serving is
# bitwise independent of which physical pages a stream happened to get.
#
# Attention reads KV through this handle — :func:`paged_append` then
# :func:`paged_attend` — never through dense arrays. ``kv_dtype='int8'``
# stores codes + per-(token, head) scales produced by
# ``kernels.kvattn.quantize_kv`` and decodes single-token steps through
# ``kernels.kvattn.attend_int8`` (the int8 decode-attention kernel);
# float dtypes are the reference mode. Scales are stored float16: the
# resident-bytes win is the point of int8 KV, and at head_dim 32 an f32
# scale pair would eat a third of it.

PAGED_KV_DTYPES = ("int8", "float16", "bfloat16", "float32")


def init_paged_kv(num_pages: int, page_size: int, n_kv_heads: int,
                  head_dim: int, kv_dtype: str = "int8") -> Params:
    """One attention layer's share of the paged KV pool.

    int8 pools carry ``k_scale``/``v_scale`` pages beside the code
    pages; float pools are just typed pages. Page 0 is reserved by the
    engine as the write sink for inactive slots and never handed to a
    stream."""
    if kv_dtype not in PAGED_KV_DTYPES:
        raise ValueError(f"kv_dtype {kv_dtype!r} not in {PAGED_KV_DTYPES}")
    if kv_dtype == "int8":
        return {
            "k_pages": jnp.zeros((num_pages, page_size, n_kv_heads, head_dim), jnp.int8),
            "v_pages": jnp.zeros((num_pages, page_size, n_kv_heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((num_pages, page_size, n_kv_heads), jnp.float16),
            "v_scale": jnp.zeros((num_pages, page_size, n_kv_heads), jnp.float16),
        }
    dt = jnp.dtype(kv_dtype)
    return {
        "k_pages": jnp.zeros((num_pages, page_size, n_kv_heads, head_dim), dt),
        "v_pages": jnp.zeros((num_pages, page_size, n_kv_heads, head_dim), dt),
    }


def is_paged(cache: Params) -> bool:
    """Distinguishes a paged-pool cache node from the dense ``{k, v,
    pos}`` ring buffer — the dispatch point for the KV handle."""
    return isinstance(cache, dict) and "k_pages" in cache


def _page_rows(block_tables: Array, positions: Array, page_size: int) -> Array:
    """Flat pool-row index for each (stream, position). Writes with no
    real page — unallocated block-table entries (-1), positions past the
    table's capacity (padded chunk tails) — land on page 0, the engine's
    write sink."""
    pidx = positions // page_size
    page_ids = jnp.take_along_axis(
        block_tables, jnp.clip(pidx, 0, block_tables.shape[1] - 1), axis=1)
    page_ids = jnp.where(pidx < block_tables.shape[1], page_ids, -1)
    return jnp.maximum(page_ids, 0) * page_size + positions % page_size


def paged_append(cache: Params, k: Array, v: Array, block_tables: Array,
                 positions: Array, page_size: int) -> Params:
    """Write C new tokens' K/V into the page pool.

    k, v: (B, C, K, hd) float; block_tables (B, max_pages) int32 (-1 =
    unallocated); positions (B, C) absolute token positions. int8 pools
    quantize through ``kernels.kvattn.quantize_kv`` on the way in.
    Distinct streams own distinct pages, so the scatter has no
    cross-stream collisions; all inactive-slot writes land on page 0.
    Its operations carry the scope ``kv_write`` in their metadata.
    """
    B, C = positions.shape

    def scat(pool, vals):
        flat = pool.reshape(pool.shape[0] * page_size, *pool.shape[2:])
        flat = flat.at[rows].set(
            vals.reshape(B * C, *vals.shape[2:]).astype(pool.dtype))
        return flat.reshape(pool.shape)

    with jax.named_scope("kv_write"):
        rows = _page_rows(block_tables, positions, page_size).reshape(-1)
        if "k_scale" in cache:
            from ..kernels.kvattn.ops import quantize_kv

            k8, v8, ks, vs = quantize_kv(k, v)
            return {"k_pages": scat(cache["k_pages"], k8),
                    "v_pages": scat(cache["v_pages"], v8),
                    "k_scale": scat(cache["k_scale"], ks),
                    "v_scale": scat(cache["v_scale"], vs)}
        return {"k_pages": scat(cache["k_pages"], k),
                "v_pages": scat(cache["v_pages"], v)}


def paged_view(cache: Params, block_tables: Array, page_size: int):
    """Gather a dense per-stream view of the pool.

    Returns ``(gather, kpos)``: ``gather(pool)`` -> (B, S_cap, K, hd)
    with token ``t`` at row ``t`` (S_cap = max_pages * page_size), and
    ``kpos`` (B, S_cap) int32 — the row's token position where the row's
    page is allocated, -1 elsewhere (rows of an allocated page beyond
    the stream's written length are masked by the caller's ``<= cur``
    position check, exactly like the dense cache's empty slots)."""
    B, MP = block_tables.shape
    s_cap = MP * page_size
    rows = (jnp.maximum(block_tables, 0)[..., None] * page_size
            + jnp.arange(page_size, dtype=jnp.int32)).reshape(B, s_cap)

    def gather(pool):
        flat = pool.reshape(pool.shape[0] * page_size, *pool.shape[2:])
        return flat[rows]

    allocated = jnp.repeat(block_tables >= 0, page_size, axis=1)
    kpos = jnp.where(allocated, jnp.arange(s_cap, dtype=jnp.int32)[None], -1)
    return gather, kpos


def paged_attend(q: Array, cache: Params, block_tables: Array,
                 positions: Array, page_size: int, *,
                 window: Optional[int] = None, backend: str = "auto") -> Array:
    """Attention over a paged KV cache: the read half of the handle.

    q: (B, C, H, hd); positions (B, C) absolute positions of the query
    tokens (already appended). Single-token int8 decode goes through the
    ``kernels.kvattn`` int8 decode-attention kernel (``attend_int8``);
    chunked-prefill reads (C > 1) and float pools dequantize the
    gathered view and share :func:`decode_attend`. Its operations carry
    the scope ``kv_read`` in their metadata.
    """
    with jax.named_scope("kv_read"):
        gather, kpos = paged_view(cache, block_tables, page_size)
        if "k_scale" in cache:
            k8, v8 = gather(cache["k_pages"]), gather(cache["v_pages"])
            ks = gather(cache["k_scale"]).astype(jnp.float32)
            vs = gather(cache["v_scale"]).astype(jnp.float32)
            if q.shape[1] == 1:
                from ..kernels.kvattn.ops import attend_int8

                out = attend_int8(q[:, 0], k8, v8, ks, vs, kpos,
                                  positions[:, 0], window=window,
                                  backend=backend)
                return out[:, None]
            k = (k8.astype(jnp.float32) * ks[..., None]).astype(q.dtype)
            v = (v8.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
            return decode_attend(q, k, v, kpos, positions, window=window)
        k = gather(cache["k_pages"]).astype(q.dtype)
        v = gather(cache["v_pages"]).astype(q.dtype)
        return decode_attend(q, k, v, kpos, positions, window=window)
