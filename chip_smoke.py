"""Smoke run of the main path on one TPU chip, in one process.

    python chip_smoke.py

At the full published width of ``brecq_lm_100m`` (12 blocks, d_model 768,
12 heads, d_ff 2048, vocab 8192), with random weights from
``model.init(PRNGKey(SEED))`` and the synthetic corpus:

  calibrate  BRECQ W4 ``quantize()``, block granularity, all 12 blocks
  artifact   ``export`` -> ``save`` -> ``QuantizedArtifact.load(verify=True)``
  serve      ``ServeEngine`` with int8 paged KV answers 16 requests over
             8 slots through the Pallas kernels
  reference  the same requests through the same artifact on the kernels'
             XLA references; prefill and first-decode logits must agree

It exits non-zero and prints no ``ok`` line when the device is not a
TPU, when ``repro`` is not importable (the script outside its checkout),
when a calibration unit fell back to RTN or halved its minibatch for
OOM, when a request ends in a state other than ``done``, when a page
leaks, when the compiled decode program lacks the qmm or ``kv_decode``
Pallas kernel, or when the logits disagree. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Earlier lines report phase walls, compile seconds and peak HBM: set-up
information, not benchmark metrics.
"""
from __future__ import annotations

import copy
import json
import re
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ARCH = "brecq_lm_100m"
SEED = 0
CALIB_SEQS, CALIB_LEN, CALIB_BATCH = 32, 256, 8
ITERS = 200
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, MAX_NEW = 16, 64, 256, 32
# Pallas kernels the engine's compiled decode program must contain: the
# decode qmm tier and the int8-KV attention kernel.
DECODE_KERNELS = ("qgemv", "kv_decode")
# Pallas and XLA-reference engines read the same int codes, scales and
# int8 KV; they differ only in matmul precision and summation order. An
# f32 dot that XLA lowers at default precision on the TPU rounds its
# operands to bf16 (relative 2**-9), and the error of 12 residual
# blocks adds up; 0.02 of the row's logit range is about ten times
# that sum and still far below a wrong kernel's error (order 1).
LOGIT_RTOL = 0.02


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _pallas_kernels(compiled) -> set[str]:
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled
    program, from each call's ``jit(<kernel>)/pallas_call`` op name."""
    found = set()
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" in line:
            found.update(re.findall(r"jit\((\w+)\)/pallas_call", line))
    return found


def calibrate(model, params, cfg, *, iters: int):
    import jax

    from repro.core import ReconConfig, quantize
    from repro.data import Corpus, CorpusConfig, make_batches

    corpus = Corpus(CorpusConfig(vocab=cfg.vocab))
    calib = make_batches(corpus, CALIB_SEQS // CALIB_BATCH, CALIB_BATCH,
                         CALIB_LEN, seed=SEED + 1)
    t0 = time.perf_counter()
    res = quantize(model, params, calib,
                   ReconConfig(w_bits=4, iters=iters, granularity="block",
                               calib_bs=CALIB_BATCH, seed=SEED))
    jax.block_until_ready(res.params_q)
    wall = time.perf_counter() - t0
    st = res.stats
    units = st["units"]
    print(f"[calibrate] {len(units)} units in {wall:.2f}s "
          f"(first unit {units[0]['opt_wall_s']:.2f}s incl. compile, "
          f"last {units[-1]['opt_wall_s']:.2f}s; fisher "
          f"{st.get('fisher_wall_s', 0.0):.2f}s; unit_cache "
          f"{st.get('unit_cache')}; retries {st.get('unit_retries')})")
    _check(len(units) == cfg.n_layers,
           f"calibrated {len(units)} units, expected {cfg.n_layers} blocks")
    _check(st["unit_fallbacks"] == 0,
           f"{st['unit_fallbacks']} calibration unit(s) fell back to RTN")
    _check(st["unit_oom_halvings"] == 0,
           f"{st['unit_oom_halvings']} calibration OOM minibatch halving(s)")
    return res, wall


def build_artifact(model, res):
    from repro.deploy import QuantizedArtifact, export

    out = HERE / "artifacts" / "chip_smoke"
    shutil.rmtree(out, ignore_errors=True)
    t0 = time.perf_counter()
    art = export(model, res)
    art.save(str(out))
    loaded = QuantizedArtifact.load(str(out), verify=True)
    wall = time.perf_counter() - t0
    print(f"[artifact] export+save+verified load in {wall:.2f}s: "
          f"{loaded.nbytes() / 1e6:.2f}MB, bits "
          f"{art.stats.get('bits_histogram')}")
    return loaded, wall


def serve(model, artifact, prompts, *, backend: str):
    """Serve ``prompts`` through one engine. ``backend='auto'`` is the
    production path (Pallas on a TPU); ``'xla'`` runs the kernels' XLA
    references for both the qmm tiers and the int8-KV read."""
    from repro.serve_engine import EngineConfig, ServeEngine

    hook = artifact.hook()
    if backend != "auto":
        hook = copy.copy(hook)
        hook.packed_backend = backend
    ecfg = EngineConfig(num_slots=8, max_len=PROMPT_MAX + MAX_NEW,
                        kv_dtype="int8", backend=backend, record_logits=True)
    eng = ServeEngine(model, artifact.params, ecfg, quant=hook)
    compile_s = eng.compile()
    for p in prompts:
        eng.submit(p, MAX_NEW)
    m = eng.run()
    states = {u: r.state for u, r in eng.requests.items()}
    _check(all(s == "done" for s in states.values()),
           f"[{backend}] requests not done: "
           f"{ {u: s for u, s in states.items() if s != 'done'} }")
    eng.assert_no_leaks()
    print(f"[serve:{backend}] compile {compile_s:.2f}s; {len(prompts)} "
          f"requests, {m['tokens_generated']} tokens in {m['wall_s']:.2f}s "
          f"over {m['ticks']} ticks; failed {m['failed']} preemptions "
          f"{m['preemptions']}; decode kernels "
          f"{sorted(_pallas_kernels(eng._decode_c))}, prefill kernels "
          f"{sorted(_pallas_kernels(eng._chunk_c))}")
    return eng, compile_s, m["wall_s"]


def compare_logits(eng, ref) -> None:
    """Prefill (first-token) and first-decode logits of each request,
    Pallas engine against the XLA-reference engine. A first decode step
    is compared only where both engines picked the same first token
    (otherwise its inputs differ)."""
    import numpy as np

    worst, compared = 0.0, 0
    for uid, r in eng.requests.items():
        q = ref.requests[uid]
        steps = 2 if r.generated[0] == q.generated[0] else 1
        for i in range(steps):
            got, want = np.asarray(r.logits[i]), np.asarray(q.logits[i])
            span = float(want.max() - want.min())
            err = float(np.abs(got - want).max()) / max(span, 1e-6)
            worst = max(worst, err)
            compared += 1
            _check(err <= LOGIT_RTOL,
                   f"request {uid} step {i}: max |logit diff| is {err:.3g} "
                   f"of the logit range, tolerance {LOGIT_RTOL}")
    _check(compared > len(eng.requests),
           "no first-decode step could be compared (every first token "
           "differed between Pallas and XLA)")
    print(f"[compare] {compared} logit rows, worst max|diff|/range "
          f"{worst:.3g} (tolerance {LOGIT_RTOL})")


def smoke(*, reduced: bool = False, iters: int = ITERS) -> dict:
    """Calibrate, export, serve and compare; returns what main() checks.
    ``reduced`` swaps in the config's reduced widths (a CPU rehearsal)."""
    import jax
    import numpy as np

    from repro.data import Corpus, CorpusConfig
    from repro.models import get_model

    cfg, model = get_model(ARCH, reduced=reduced)
    print(f"[model] {ARCH}{' (reduced)' if reduced else ''}: "
          f"{cfg.n_layers} x d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    params = model.init(jax.random.PRNGKey(SEED))
    res, calib_s = calibrate(model, params, cfg, iters=iters)
    artifact, art_s = build_artifact(model, res)

    rng = np.random.default_rng(SEED)
    corpus = Corpus(CorpusConfig(vocab=cfg.vocab))
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    prompts = [corpus.sample(1, int(n), seed=SEED + 100 + i)[0]
               for i, n in enumerate(lens)]
    eng, compile_s, serve_s = serve(model, artifact, prompts, backend="auto")
    ref, ref_compile_s, ref_s = serve(model, artifact, prompts, backend="xla")
    compare_logits(eng, ref)
    return {"walls_s": {"calibrate": calib_s, "artifact": art_s,
                        "serve": serve_s, "serve_ref": ref_s},
            "compile_s": {"serve": compile_s, "serve_ref": ref_compile_s},
            "decode_kernels": _pallas_kernels(eng._decode_c)}


def main() -> int:
    sys.path.insert(0, str(HERE / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import repro from {HERE / 'src'}: {e}",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    print(f"[device] {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        out = smoke()
        missing = set(DECODE_KERNELS) - out["decode_kernels"]
        _check(not missing, f"compiled decode program lacks Pallas kernel(s) "
               f"{sorted(missing)}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[summary] walls_s {json.dumps(out['walls_s'])} compile_s "
          f"{json.dumps(out['compile_s'])} total {time.perf_counter() - t0:.2f}s "
          f"peak_hbm_bytes {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
