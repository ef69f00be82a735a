"""Scheduler test suite for the continuous-batching serve engine.

Pins the behaviors the multi-stream benchmark relies on: continuous
batching must be *invisible* to any single request (staggered admission
produces exactly the tokens sequential batch-1 serving produces — exact
for fp KV pools, greedy-argmax-identical with a pinned logit tolerance
for int8), slots are reused across requests, chunked prefill interleaves
with decode instead of stalling it, and every KV page is returned to the
pool when a request finishes.
"""
import numpy as np
import pytest

from repro.serve_engine import EngineConfig, ServeEngine

# small enough to keep compiles cheap, big enough to exercise paging:
# 2-page prompts, multi-chunk prefill, ragged tails
ECFG = dict(num_slots=3, page_size=4, num_pages=49, max_len=32,
            prefill_chunk=8, backend="xla", record_logits=True)

PROMPT_LENS = (5, 13, 9, 17, 6)
MAX_NEW = (6, 3, 9, 4, 5)
ARRIVALS = (0, 0, 2, 5, 9)


def _prompts(vocab, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _run_staggered(model, params, kv_dtype, *, quant=None, cancel_uid=None,
                   cancel_at_len=2, decode_ticks=None):
    """All requests in flight together, admitted on their arrival ticks.
    ``decode_ticks``: a list that collects the ticks that ran a decode
    step, seen through ``metrics()["decode_ticks"]``."""
    from repro.models.common import NO_QUANT

    eng = ServeEngine(model, params, EngineConfig(kv_dtype=kv_dtype, **ECFG),
                      quant=quant or NO_QUANT)
    prompts = _prompts(model.cfg.vocab)
    nxt, slots_seen = 0, {}
    while nxt < len(prompts) or eng.pending():
        while nxt < len(prompts) and ARRIVALS[nxt] <= eng.tick:
            eng.submit(prompts[nxt], MAX_NEW[nxt], uid=nxt)
            nxt += 1
        ran = eng.metrics()["decode_ticks"]
        eng.step()
        if decode_ticks is not None and eng.metrics()["decode_ticks"] > ran:
            decode_ticks.append(eng.tick - 1)
        for s, req in enumerate(eng.slot_req):
            if req is not None:
                slots_seen.setdefault(req.uid, s)
        if (cancel_uid is not None and cancel_uid in eng.requests
                and len(eng.requests[cancel_uid].generated) >= cancel_at_len
                and eng.requests[cancel_uid].state == "decode"):
            eng.cancel(cancel_uid)
            cancel_uid = None
    return eng, slots_seen


def _run_sequential(model, params, kv_dtype, *, quant=None):
    """Same engine config, one request at a time: batch-1 serving."""
    from repro.models.common import NO_QUANT

    eng = ServeEngine(model, params, EngineConfig(kv_dtype=kv_dtype, **ECFG),
                      quant=quant or NO_QUANT)
    for uid, prompt in enumerate(_prompts(model.cfg.vocab)):
        eng.submit(prompt, MAX_NEW[uid], uid=uid)
        eng.run()
    return eng


def _tokens(eng):
    return {uid: list(req.generated) for uid, req in eng.requests.items()}


def test_continuous_matches_sequential_fp(tiny_trained):
    """fp KV: staggered continuous batching is EXACTLY sequential batch-1."""
    _, model, params, _, _, _ = tiny_trained
    stag, _ = _run_staggered(model, params, "float32")
    seq = _run_sequential(model, params, "float32")
    assert _tokens(stag) == _tokens(seq)
    for uid, req in stag.requests.items():
        assert req.state == "done" and len(req.generated) == MAX_NEW[uid]
    # exact: the two schedules run the same compiled programs over the
    # same per-stream rows, so even the logits are bit-identical
    for uid in stag.requests:
        np.testing.assert_array_equal(
            np.stack(stag.requests[uid].logits),
            np.stack(seq.requests[uid].logits))
    stag.assert_no_leaks()
    seq.assert_no_leaks()


def test_continuous_matches_vanilla_decode_fp(tiny_trained):
    """Engine fp serving argmax-matches the plain prefill+decode_step path
    (different attention grouping at prefill, so logits only agree to a
    tolerance — greedy tokens must agree exactly)."""
    import jax
    import jax.numpy as jnp

    _, model, params, _, _, _ = tiny_trained
    eng, _ = _run_staggered(model, params, "float32")
    for uid, prompt in enumerate(_prompts(model.cfg.vocab)):
        cache = model.init_cache(1, ECFG["max_len"], jnp.float32)
        logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])}, cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        ref_logits = [np.asarray(logits[0])]
        pos = jnp.full((1,), len(prompt), jnp.int32)
        for _ in range(MAX_NEW[uid] - 1):
            logits, cache = model.decode_step(
                params, jnp.asarray([[toks[-1]]], jnp.int32), cache, pos)
            toks.append(int(jnp.argmax(logits, -1)[0]))
            ref_logits.append(np.asarray(logits[0]))
            pos = pos + 1
        assert eng.requests[uid].generated == toks, uid
        np.testing.assert_allclose(np.stack(eng.requests[uid].logits),
                                   np.stack(ref_logits), atol=1e-4)


def test_continuous_matches_sequential_int8(tiny_trained):
    """int8 KV: scheduling is still invisible (staggered == sequential,
    exact), and the int8 path tracks the fp reference within a pinned
    logit tolerance with identical greedy tokens."""
    _, model, params, _, _, _ = tiny_trained
    stag, _ = _run_staggered(model, params, "int8")
    seq = _run_sequential(model, params, "int8")
    assert _tokens(stag) == _tokens(seq)
    for uid in stag.requests:
        np.testing.assert_array_equal(
            np.stack(stag.requests[uid].logits),
            np.stack(seq.requests[uid].logits))
    # int8 vs fp reference mode: pinned tolerance + greedy-argmax-identical
    fp, _ = _run_staggered(model, params, "float32")
    assert _tokens(stag) == _tokens(fp)
    for uid in stag.requests:
        np.testing.assert_allclose(np.stack(stag.requests[uid].logits),
                                   np.stack(fp.requests[uid].logits),
                                   atol=0.5)
    stag.assert_no_leaks()


def test_slot_reuse(tiny_trained):
    """5 requests over 3 slots: some slot hosts at least two requests."""
    _, model, params, _, _, _ = tiny_trained
    eng, slots_seen = _run_staggered(model, params, "int8")
    assert all(r.state == "done" for r in eng.requests.values())
    by_slot: dict = {}
    for uid, s in slots_seen.items():
        by_slot.setdefault(s, []).append(uid)
    assert any(len(uids) >= 2 for uids in by_slot.values()), by_slot
    eng.assert_no_leaks()


def test_chunked_prefill_interleaves_decode(tiny_trained):
    """A long prompt prefills in chunks WHILE other streams decode: a
    decode step runs on a tick strictly between two of its chunks."""
    _, model, params, _, _, _ = tiny_trained
    decode_ticks = []
    eng, _ = _run_staggered(model, params, "int8", decode_ticks=decode_ticks)
    # uid 3: prompt 17 over chunk 8 -> 3 prefill_chunk events
    chunk_ticks = [t for t, ev, uid in eng.events
                   if ev == "prefill_chunk" and uid == 3]
    assert len(chunk_ticks) == 3
    assert chunk_ticks[0] < chunk_ticks[-1], "chunks all ran in one tick"
    between = [t for t in decode_ticks
               if chunk_ticks[0] <= t < chunk_ticks[-1]]
    assert between, (
        f"no decode step between prefill chunks {chunk_ticks} "
        f"(decode ticks: {decode_ticks})")


def _serve_spans(trace_dir):
    """(name, start ns, end ns, stats) of every ``serve.*`` host event in
    the newest profile under ``trace_dir``, in start order."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced_run(tiny_trained, tmp_path_factory):
    """The staggered int8 schedule served while the profiler records, and
    the engine's spans from that trace."""
    import jax

    _, model, params, _, _, _ = tiny_trained
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng, _ = _run_staggered(model, params, "int8")
    finally:
        jax.profiler.stop_trace()
    return eng, _serve_spans(trace_dir)


def _ticks(spans):
    """Each serve.tick span with the phase spans that lie inside it."""
    ticks = [s for s in spans if s[0] == "serve.tick"]
    return [(t, [s for s in spans if s[0] != "serve.tick"
                 and t[1] <= s[1] and s[2] <= t[2]]) for t in ticks]


def test_engine_spans_are_declared(traced_run):
    from repro.serve_engine.engine import SPANS

    eng, spans = traced_run
    assert {s[0] for s in spans} <= set(SPANS)
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert [int(s[3]["tick"]) for s in ticks] == list(range(eng.tick))
    assert all("rows" in s[3] for s in spans if s[0] == "serve.decode.run")
    assert all("uid" in s[3] for s in spans if s[0] == "serve.prefill.stage")


def test_engine_phases_lie_inside_ticks(traced_run):
    _, spans = traced_run
    phases = [s for s in spans if s[0] != "serve.tick"]
    inside = sum(len(p) for _, p in _ticks(spans))
    assert phases and inside == len(phases)


def test_engine_decode_tick_phase_order(traced_run):
    """Every tick that decodes stages, dispatches, fetches and samples,
    in that order; a prompt's last chunk fetches and samples its first
    token before the decode phases start."""
    eng, spans = traced_run
    decoding = 0
    for _, phases in _ticks(spans):
        names = [p[0] for p in phases]
        if "serve.decode.run" not in names:
            continue
        decoding += 1
        dec = [n for n in names if n.startswith("serve.decode.")]
        assert dec == ["serve.decode.stage", "serve.decode.run",
                       "serve.decode.fetch", "serve.decode.sample"]
        first = names.index("serve.decode.stage")
        assert all(not n.startswith("serve.prefill.") for n in names[first:])
    assert decoding == eng.metrics()["decode_ticks"]
    fetches = [s for s in spans if s[0] == "serve.prefill.fetch"]
    assert len(fetches) == len(eng.requests)   # one first token each


def test_engine_tokens_same_with_profiler(traced_run, tiny_trained):
    _, model, params, _, _, _ = tiny_trained
    eng, _ = traced_run
    plain, _ = _run_staggered(model, params, "int8")
    assert _tokens(eng) == _tokens(plain)
    assert eng.metrics()["mean_slot_occupancy"] == pytest.approx(
        plain.metrics()["mean_slot_occupancy"])


def test_no_page_leak_and_refcounts(tiny_trained):
    """Pool pristine after completion; pages were actually used."""
    _, model, params, _, _, _ = tiny_trained
    eng, _ = _run_staggered(model, params, "int8")
    assert eng.pool.pages_in_use == 0
    assert eng.pool.reserved_pages == 0
    assert (eng.block_tables == -1).all()
    assert eng.metrics()["peak_pages_in_use"] > 0
    eng.assert_no_leaks()


def test_admission_waits_for_pages(tiny_trained):
    """A pool too small for all requests at once admits in waves and
    still completes everything (reservation-based admission)."""
    _, model, params, _, _, _ = tiny_trained
    cfg = dict(ECFG)
    cfg["num_pages"] = 13  # 12 usable pages; each request needs <= 8
    eng = ServeEngine(model, params, EngineConfig(kv_dtype="int8", **cfg))
    for uid, prompt in enumerate(_prompts(model.cfg.vocab)):
        eng.submit(prompt, MAX_NEW[uid], uid=uid)
    eng.run(max_ticks=500)
    assert all(r.state == "done" for r in eng.requests.values())
    eng.assert_no_leaks()


def test_rejects_oversized_and_recurrent():
    """Requests beyond max_len are rejected at submit; non-attention
    archs are rejected at engine construction."""
    import jax

    from repro.models import get_model

    _, model = get_model("brecq_lm_100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, EngineConfig(kv_dtype="int8", **ECFG))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.zeros(30, np.int32), 10)
    _, xl = get_model("xlstm_350m", reduced=True)
    xp = xl.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention-only"):
        ServeEngine(xl, xp, EngineConfig(kv_dtype="int8", **ECFG))
