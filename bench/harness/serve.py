"""Open-loop serving of one configuration through ``repro.serve_engine``.

The configuration's architecture module (``bench/archs/<arch>.py``,
``common.load_arch``) gives the sizes, the program's ``ArchConfig`` and
the weights. Set-up makes the served weights on the device from the
seed (the module's ``served_maker``: the float32 tree under the
program's own packing, ``repro.deploy.quantize_tree``), builds the
engine, compiles its two programs and warms every host-side shape the
window will use. The
window submits each request at its scheduled arrival and ticks the
engine whenever it has work; every request due in the window is then
followed to its end. A request's times are those at which the host saw
its tokens, measured from its scheduled arrival.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import numpy as np

from . import common as C
from . import traffic as T
from .reference import Reference, served_gaps

DRAIN_LIMIT_S = 60.0


def engine_config(config: dict):
    from repro.serve_engine import EngineConfig

    return EngineConfig(**config["engine"])


def request_outcomes(arrival_s, max_new, tok_t, done, seconds: float) -> dict:
    """Times and counts over every request due in the window.

    A request that was refused, failed, or did not get all its tokens
    counts as failed and as infinitely late for its first token. The
    gaps between tokens are those of every request; the tokens counted
    for the rate are those the host saw inside the window."""
    ttft, itl, failed, in_window = [], [], 0, 0
    for i in range(len(arrival_s)):
        ts = tok_t[i]
        if done[i] and len(ts) == int(max_new[i]):
            ttft.append(ts[0] - arrival_s[i])
            itl.extend(np.diff(ts).tolist())
        else:
            failed += 1
            ttft.append(math.inf)
        in_window += sum(t <= seconds for t in ts)
    return {"ttft_s": ttft, "itl_s": itl, "failed": failed,
            "attempted": len(arrival_s), "tokens_in_window": in_window}


def end_to_end(o: dict, seconds: float) -> dict:
    """The serving cell's end-to-end metrics from :func:`request_outcomes`."""
    itl = C.percentile(o["itl_s"], 95) if o["itl_s"] else math.inf
    ttft = {f"ttft_p{q}_ms": C.finite_ms(1e3 * float(C.percentile(o["ttft_s"], q)))
            for q in (90, 95)}
    return {**ttft, "itl_p95_ms": C.finite_ms(1e3 * float(itl)),
            "out_tok_s": o["tokens_in_window"] / seconds}


def decode_note(eng) -> list[int]:
    """The live streams' lengths at a decode call."""
    return [len(r.prompt) + len(r.generated) for r in eng.slot_req
            if r is not None and r.state == "decode"]


def chunk_note(eng) -> tuple[int, int]:
    """(rows, offset) of the prompt rows a prefill-chunk call runs."""
    ns = eng.cfg.num_slots
    req = eng.slot_req[(eng._pf_ptr - 1) % ns]
    src = req.prefill_src if req.prefill_src is not None else req.prompt
    rows = min(eng.cfg.prefill_chunk, len(src) - req.prefill_off)
    return rows, req.prefill_off


class _Recorder:
    """Stands in for one of the engine's compiled programs and notes, per
    call, the work the call needs (from the engine's host-side state)."""

    def __init__(self, fn, note):
        self.fn, self.note, self.calls = fn, note, None

    def __call__(self, *args):
        if self.calls is not None:
            self.calls.append(self.note())
        return self.fn(*args)


class ServeRun:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.arch = C.load_arch(config)
        self.dims = self.arch.dims(config)
        self.q = config["quant"]
        self.compiles = 0

    # -- set-up ------------------------------------------------------------

    def setup(self, record: bool = False, share=None) -> None:
        """``share``: an engine (or its compiled programs) of the same
        program shape whose compiled programs this run reuses."""
        from repro.deploy import quantize_tree
        from repro.models.transformer import LM
        from repro.serve_engine import ServeEngine

        d, q = self.dims, self.q
        model = LM(self.arch.arch_config(self.config, d))
        make = self.arch.served_maker(
            d, lambda tree: quantize_tree(tree, q["w_bits"], q["w_group"]))
        params = make(*C.split_seed(self.seed))
        jax.block_until_ready(params)
        self.engine = eng = ServeEngine(model, params, engine_config(self.config),
                                        share_compiled=share)
        eng.compile()
        if record:
            self._wrap_programs()
        self._warm_up()
        self.schedule = T.open_loop(self.mix, self.seconds, self.seed, d.vocab)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, *_args, **_kw) -> None:
        if event in C.COMPILE_EVENTS:
            self.compiles += 1

    def _wrap_programs(self) -> None:
        """Note each program call's work: with the architecture module's
        ``decode_note`` / ``chunk_note`` where it has them, else this
        module's."""
        eng = self.engine
        dn = getattr(self.arch, "decode_note", decode_note)
        cn = getattr(self.arch, "chunk_note", chunk_note)
        eng._decode_c = self.decode_rec = _Recorder(eng._decode_c, lambda: dn(eng))
        eng._chunk_c = self.chunk_rec = _Recorder(eng._chunk_c, lambda: cn(eng))

    def _warm_up(self) -> None:
        """Requests whose prompts end at every offset inside a prefill
        chunk, so each host-side slice of the chunk's logits has run once,
        and a full batch of decoding slots."""
        eng = self.engine
        chunk = eng.cfg.prefill_chunk
        for n in range(1, chunk + 2):
            eng.submit(np.arange(n, dtype=np.int32) % self.dims.vocab, 2)
        while eng.pending():
            eng.step()

    # -- window ------------------------------------------------------------

    def window(self, trace_dir: str | None = None, trace_s: float = 0.0) -> None:
        """Serve the schedule for ``seconds``, then follow every request
        due in the window to its end. With ``trace_dir`` the profiler
        records the window's last ``trace_s`` seconds."""
        eng, sch = self.engine, self.schedule
        n = len(sch)
        self.uid = [-1] * n
        self.submit_late = np.zeros(n)
        self.tok_t: list[list[float]] = [[] for _ in range(n)]
        seen = [0] * n
        active: dict[int, int] = {}   # engine uid -> schedule index
        compiles0 = self.compiles
        trace_at = self.seconds - trace_s if trace_dir else math.inf
        tracing = False
        win = None
        t0 = time.perf_counter()

        def observe(now):
            for uid, i in list(active.items()):
                req = eng.requests[uid]
                g = len(req.generated)
                if g > seen[i]:
                    self.tok_t[i].extend([now] * (g - seen[i]))
                    seen[i] = g
                if req.state in ("done", "cancelled", "expired", "failed"):
                    del active[uid]

        def submit_due(now):
            nonlocal nxt
            with jax.profiler.TraceAnnotation("bench.submit"):
                while nxt < n and sch.arrival_s[nxt] <= now:
                    i = nxt
                    nxt += 1
                    self.submit_late[i] = now - sch.arrival_s[i]
                    try:
                        u = eng.submit(sch.prompts[i], int(sch.max_new[i]))
                    except Exception as e:  # refused: never served
                        print(f"request {i} refused: {e}", file=sys.stderr)
                        continue
                    self.uid[i] = u
                    active[u] = i

        nxt = 0
        while True:
            now = time.perf_counter() - t0
            if now >= self.seconds:
                break
            if not tracing and now >= trace_at:
                jax.profiler.start_trace(trace_dir)
                win = jax.profiler.TraceAnnotation("bench.window")
                win.__enter__()
                self._recording(True)
                tracing = True
                now = time.perf_counter() - t0
            submit_due(now)
            if eng.pending():
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
                observe(time.perf_counter() - t0)
            else:
                nxt_t = sch.arrival_s[nxt] if nxt < n else self.seconds
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt_t, self.seconds)
                                   - (time.perf_counter() - t0)))
        if tracing:
            win.__exit__(None, None, None)
            self._recording(False)
            jax.profiler.stop_trace()
        self.window_compiles = self.compiles - compiles0
        # requests due in the window that the loop had not reached yet
        submit_due(self.seconds)
        limit = time.perf_counter() + DRAIN_LIMIT_S
        while active and time.perf_counter() < limit:
            eng.step()
            observe(time.perf_counter() - t0)
        self.unfinished = len(active)
        self.drain_s = time.perf_counter() - t0 - self.seconds

    def _recording(self, on: bool) -> None:
        """Start or stop noting each program call's work; the notes of the
        traced stretch end up in ``decode_calls`` and ``chunk_calls``."""
        if on:
            self.decode_rec.calls, self.chunk_rec.calls = [], []
        else:
            self.decode_calls, self.decode_rec.calls = self.decode_rec.calls, None
            self.chunk_calls, self.chunk_rec.calls = self.chunk_rec.calls, None

    # -- end to end ----------------------------------------------------------

    def outcomes(self) -> dict:
        """Per-request times and the counts the result reports."""
        sch, eng = self.schedule, self.engine
        done = [u >= 0 and eng.requests[u].state == "done" for u in self.uid]
        return request_outcomes(sch.arrival_s, sch.max_new, self.tok_t, done,
                                self.seconds)

    def end_to_end(self) -> dict:
        o = self.outcomes()
        return end_to_end(o, self.seconds)

    # -- correctness ---------------------------------------------------------

    def sample(self) -> list[int]:
        """Schedule indices of the finished requests the check compares:
        the longest, then others in an order drawn from the seed, until
        the served tokens reach the configuration's count."""
        chk = self.config["check"]
        done = [i for i in range(len(self.schedule))
                if self.uid[i] >= 0
                and self.engine.requests[self.uid[i]].state == "done"]
        if not done:
            return []
        sch = self.schedule
        longest = max(done, key=lambda i: len(sch.prompts[i]) + int(sch.max_new[i]))
        rng = np.random.default_rng([self.seed, 0xC4EC])
        rest = [i for i in rng.permutation(done).tolist() if i != longest]
        out, toks = [longest], int(sch.max_new[longest])
        for i in rest:
            if toks >= chk["served_tokens"] or len(out) >= chk["max_requests"]:
                break
            out.append(i)
            toks += int(sch.max_new[i])
        return out

    def release(self) -> None:
        """Drop the program's device state before the reference runs."""
        eng = self.engine
        self.served = {i: (self.schedule.prompts[i],
                           np.asarray(eng.requests[self.uid[i]].generated, np.int32))
                       for i in self.sample()}
        del self.engine
        gc.collect()

    def check(self, operands=None) -> dict:
        """The compared numbers: the widest and the mean gap of a served
        token below the reference's best, as shares of the logit spread.
        With ``operands`` (the control: the reference with its matmul
        operands in that dtype) the served tokens are replaced by what
        the control puts first. Requests are padded to fixed shapes (the
        sample's most requests, the mix's longest prompt and output), so
        the reference compiles once per cell."""
        import jax.numpy as jnp

        if not self.served:
            return {"gap_max": math.inf, "gap_mean": math.inf}
        d, chk = self.dims, self.config["check"]
        npos = self.mix["output_len"]["max"]
        nb, seq = chk["max_requests"], self.mix["prompt_len"]["max"] + npos
        toks = np.zeros((nb, seq), np.int32)
        pos = np.zeros((nb, npos), np.int32)
        served = np.zeros((nb, npos), np.int32)
        valid = np.zeros((nb, npos), bool)
        for b, (p, s) in enumerate(self.served.values()):
            full = np.concatenate([p, s[:-1]])
            toks[b, :len(full)] = full
            pos[b, :len(s)] = len(p) - 1 + np.arange(len(s))
            served[b, :len(s)] = s
            valid[b, :len(s)] = True
        args = (self.arch, d, self.q["w_bits"], C.split_seed(self.seed))
        ref = Reference(*args).logits(toks, pos)
        if operands is not None:
            low = Reference(*args, operands).logits(toks, pos)
            served = np.asarray(jnp.argmax(low, axis=-1), np.int32)
        g = np.asarray(served_gaps(ref, jnp.asarray(served), jnp.asarray(valid)))
        return {"gap_max": float(g.max()),
                "gap_mean": float(g.sum() / valid.sum())}
