"""Batched serving driver: prefill + decode from a packed QuantizedArtifact.

Serves a (small, host-runnable) model with continuous batched requests:
  1. resolve weights — FP params, a saved :class:`QuantizedArtifact`
     (``--artifact DIR``), or a fresh RTN artifact (``--quant BITS``,
     which is saved and re-loaded so the served bytes are exactly what a
     deployment would ship),
  2. prefill the prompt batch, 3. decode N tokens with the jitted step,
  4. report artifact bytes vs FP, tokens/s packed-vs-fp (steady state —
     compile is AOT'd out of the timed loops) and which qmm tiers fired
     (decode steps dispatch to the ``qgemv`` fast path by shape).

Packed weights stay int8 codes in HBM end-to-end: every linear resolves
through the ``QuantHook.packed_matmul`` weight-provider (``qmm``), so the
resident bytes printed here are the real serving footprint. The
production-mesh serving path is exercised by dryrun.py decode cells; this
driver runs the same model code end-to-end on the host.
"""
from __future__ import annotations

import argparse
import copy
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data import Corpus, CorpusConfig
from ..deploy import (ArtifactMismatchError, QuantizedArtifact, rtn_artifact,
                      tree_bytes)
from ..models import get_model
from .compile_cache import use_compile_cache


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="brecq_lm_100m")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen-len", type=int, default=32)
    p.add_argument("--quant", type=int, default=None, choices=[2, 4, 8],
                   help="pack weights to this many bits (RTN artifact)")
    p.add_argument("--group", type=int, default=None)
    p.add_argument("--budget-bytes", type=float, default=None,
                   help="solve per-layer bits so the whole artifact fits "
                        "this many bytes, then serve it "
                        "(repro.deploy.budget)")
    p.add_argument("--budget-decode-ms", type=float, default=None,
                   help="solve per-layer bits so the summed measured "
                        "per-layer decode matmul time fits this many ms, "
                        "then serve it")
    p.add_argument("--sens", default=None,
                   help="SensTable JSON (core.sensitivity.SensTable.save) "
                        "for --budget-*; default: calibration-free RTN "
                        "weight-error proxy")
    p.add_argument("--dispatch", default="auto",
                   choices=["auto", "heuristic", "measured"],
                   help="qmm decode-shape tier dispatch: measured times "
                        "each eligible tier at the served shapes (cached "
                        "in the artifact manifest per backend) and routes "
                        "by the winners; heuristic keeps the M<=8 gemv "
                        "guess; auto = measured iff a table is installed")
    p.add_argument("--artifact", default=None,
                   help="serve from a saved QuantizedArtifact directory")
    p.add_argument("--save-artifact", default=None,
                   help="where --quant saves its artifact (default: tmpdir)")
    p.add_argument("--no-compare-fp", action="store_true",
                   help="skip the FP throughput reference pass")
    p.add_argument("--packed-backend", default="auto",
                   choices=["auto", "xla", "pallas"],
                   help="qmm execution path for packed weights (tiers are "
                        "still picked by shape)")
    p.add_argument("--no-verify", action="store_true",
                   help="skip artifact schema/checksum verification at load "
                        "(escape hatch for pre-v2 or known-good artifacts)")
    p.add_argument("--engine", action="store_true",
                   help="serve through the continuous-batching engine "
                        "(repro.serve_engine) instead of the fixed-batch "
                        "harness; --batch becomes the slot count")
    p.add_argument("--streams", type=int, default=None,
                   help="number of synthetic request streams for --engine "
                        "(staggered arrivals, mixed lengths; default: "
                        "2x the slot count)")
    p.add_argument("--kv-dtype", default=None,
                   choices=["int8", "float16", "bfloat16", "float32"],
                   help="engine KV pool dtype (default: artifact manifest "
                        "kv_dtype, else int8)")
    p.add_argument("--page-size", type=int, default=None,
                   help="engine KV page size in tokens (default: manifest "
                        "kv_page_size, else 16)")
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="engine prefill chunk length (tokens per tick)")
    p.add_argument("--num-pages", type=int, default=None,
                   help="engine KV pool size in pages incl. the sink "
                        "(default: worst-case sizing — every slot can hold "
                        "a full-length stream); set it below that to create "
                        "page pressure")
    p.add_argument("--overcommit", default="none",
                   choices=["none", "prompt"],
                   help="engine admission policy: 'none' reserves the "
                        "worst-case page need up front (reference); "
                        "'prompt' reserves only the prompt's pages plus a "
                        "small headroom and preempts the newest / lowest-"
                        "priority stream on pool exhaustion (bit-exact "
                        "re-prefill resume)")
    p.add_argument("--deadline-ticks", type=int, default=None,
                   help="per-request relative deadline for --engine: a "
                        "stream not finished within this many ticks of "
                        "submission moves to the terminal 'expired' state "
                        "and its pages are reclaimed")
    p.add_argument("--drain-on-sigterm", action="store_true",
                   help="install GracefulShutdown for the --engine loop: "
                        "SIGTERM/SIGINT stops admission, finishes in-flight "
                        "streams and reports per-request statuses instead "
                        "of killing them dead")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def _check_manifest(manifest: dict, cfg) -> None:
    """Fail fast (clearly) when a loaded artifact doesn't match the model
    built from --arch/--reduced, instead of an opaque shape error deep in
    prefill."""
    for field, got in (("arch", cfg.name), ("n_layers", cfg.n_layers),
                       ("d_model", cfg.d_model), ("vocab", cfg.vocab)):
        want = manifest.get(field)
        if want is not None and want != got:
            raise ArtifactMismatchError(
                f"artifact was exported for {field}={want!r} but the served "
                f"model has {field}={got!r} — pass the matching --arch/"
                f"--reduced flags (manifest: arch={manifest.get('arch')!r}, "
                f"n_layers={manifest.get('n_layers')}, "
                f"d_model={manifest.get('d_model')}, "
                f"vocab={manifest.get('vocab')})")


def _solve_budget_artifact(args, cfg, params):
    """--budget-bytes/--budget-decode-ms: sensitivity table (measured
    JSON via --sens, else the RTN weight-error proxy) -> exact solver ->
    packed mixed-precision artifact. Raises on an infeasible budget."""
    from ..core.sensitivity import SensTable
    from ..deploy.budget import budget_artifact, weight_sens_table

    if args.budget_bytes is not None and args.budget_decode_ms is not None:
        raise SystemExit("pass --budget-bytes or --budget-decode-ms, not both")
    if args.sens:
        sens = SensTable.load(args.sens)
    else:
        sens = weight_sens_table(params, cfg.n_layers, group=args.group)
    if args.budget_bytes is not None:
        kind, budget = "bytes", args.budget_bytes
    else:
        kind, budget = "decode_ms", args.budget_decode_ms
    art, sol, _ = budget_artifact(params, sens, budget, kind=kind, cfg=cfg,
                                  group=args.group,
                                  m=min(args.batch, 8) if kind != "bytes" else 1)
    if kind == "bytes" and art.nbytes() > budget:
        raise ArtifactMismatchError(
            f"budget solve produced a {art.nbytes()}-byte artifact over the "
            f"{budget:g}-byte budget")
    return art


def _setup_dispatch(args, cfg, params, artifact) -> None:
    """--dispatch: route decode-shaped qmm calls by measured tier
    winners. 'measured' times the served shapes now (reusing the
    artifact's per-backend manifest cache when present); 'heuristic'
    pins the env override so even an installed table is ignored."""
    import os

    if args.dispatch == "heuristic":
        os.environ["REPRO_QMM_DISPATCH"] = "heuristic"
        return
    if args.dispatch != "measured":
        return
    if artifact is None:
        raise SystemExit("--dispatch measured needs packed weights "
                         "(--artifact/--quant/--budget-*)")
    from ..deploy.budget import (ensure_cost_table, install_dispatch,
                                 weight_shapes)

    os.environ["REPRO_QMM_DISPATCH"] = "measured"
    table = ensure_cost_table(artifact, weight_shapes(params, cfg.n_layers),
                              m=min(args.batch, 8))
    install_dispatch(table)
    wins = {}
    for key, tier in table.dispatch.items():
        wins[tier] = wins.get(tier, 0) + 1
    print(f"[dispatch] measured tier winners on {table.backend} "
          f"(m={table.meta['m']}): {wins} over "
          f"{table.meta['unique_shapes']} shapes")


def run_prefill_decode(model, params, batch, *, batch_size: int,
                       prompt_len: int, gen_len: int, hook=None, tag="fp",
                       quiet=False):
    """One prefill + ``gen_len`` greedy decode steps with the jitted
    step; returns (gen tokens, stats). The single timing harness shared
    by this driver and ``benchmarks/table6_deploy.py``.

    Both programs are AOT-compiled (``lower().compile()``) before the
    clock starts, so ``t_prefill``/``t_decode`` are steady-state serving
    walls — compile time is reported separately as ``t_compile`` (it
    used to land inside the decode loop and dominate short packed runs).
    ``qmm_tiers`` records which packed execution tiers the two programs
    traced (all zero for FP params).
    """
    from ..kernels.qmatmul import ops as qmm_ops
    from ..models.common import NO_QUANT

    hook = hook or NO_QUANT
    cache = model.init_cache(batch_size, prompt_len + gen_len, jnp.float32)

    prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c, hook, remat="none"))
    decode = jax.jit(
        lambda p, t, c, pos: model.decode_step(p, t, c, pos, hook),
        donate_argnums=(2,))
    tiers0 = dict(qmm_ops.TIER_COUNTS)
    t0 = time.time()
    prefill_c = prefill.lower(params, batch, cache).compile()
    tok0 = jnp.zeros((batch_size, 1), jnp.int32)
    pos0 = jnp.full((batch_size,), prompt_len, jnp.int32)
    decode_c = decode.lower(params, tok0, cache, pos0).compile()
    t_compile = time.time() - t0
    tiers = {k: qmm_ops.TIER_COUNTS[k] - tiers0[k] for k in tiers0}

    t0 = time.time()
    logits, cache = prefill_c(params, batch, cache)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    for i in range(gen_len - 1):
        pos = jnp.full((batch_size,), prompt_len + i, jnp.int32)
        logits, cache = decode_c(params, tok, cache, pos)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = batch_size * (gen_len - 1)
    tok_s = toks / max(t_decode, 1e-9)
    prefill_tok_s = batch_size * prompt_len / max(t_prefill, 1e-9)
    if not quiet:
        used = ",".join(f"{k}={v}" for k, v in tiers.items() if v) or "none"
        note = "" if qmm_ops.decode_tier_enabled() else " (decode tier off)"
        print(f"[{tag}] compile {t_compile:.2f}s; prefill {batch_size}x"
              f"{prompt_len} in {t_prefill:.2f}s ({prefill_tok_s:.0f} tok/s); "
              f"decode {toks} tokens in {t_decode:.2f}s ({tok_s:.1f} tok/s); "
              f"qmm tiers: {used}{note}")
    gen = jnp.concatenate(out_tokens, axis=1)
    return gen, {"t_prefill": t_prefill, "t_decode": t_decode,
                 "t_compile": t_compile, "tok_s": tok_s,
                 "prefill_tok_s": prefill_tok_s, "qmm_tiers": tiers,
                 "decode_tier_enabled": qmm_ops.decode_tier_enabled()}


def _run_once(model, params, batch, args, hook=None, tag="fp"):
    return run_prefill_decode(model, params, batch, batch_size=args.batch,
                              prompt_len=args.prompt_len,
                              gen_len=args.gen_len, hook=hook, tag=tag)


def main(argv=None, params=None):
    args = parse_args(argv)
    use_compile_cache()
    cfg, model = get_model(args.arch, reduced=args.reduced)
    if params is None:
        params = model.init(jax.random.PRNGKey(args.seed))
    fp_bytes = tree_bytes(params)

    artifact = None
    tmp_dir = None  # cleaned on exit when the user didn't ask to keep it
    try:
        if args.artifact:
            # verifying load: schema + per-leaf checksums, unless --no-verify
            artifact = QuantizedArtifact.load(args.artifact,
                                              verify=not args.no_verify)
            _check_manifest(artifact.manifest, cfg)
            print(f"loaded artifact {args.artifact}: "
                  f"{artifact.nbytes()/1e6:.1f}MB, manifest arch="
                  f"{artifact.manifest.get('arch')}")
        elif args.budget_bytes is not None or args.budget_decode_ms is not None:
            art = _solve_budget_artifact(args, cfg, params)
            if args.save_artifact:
                out_dir = args.save_artifact
            else:
                tmp_dir = tempfile.TemporaryDirectory(prefix="brecq_art_")
                out_dir = tmp_dir.name
            art.save(out_dir)
            artifact = QuantizedArtifact.load(out_dir,
                                              verify=not args.no_verify)
            info = artifact.manifest["budget"]
            print(f"[budget] {info['kind']} <= {info['budget']:g}: solved "
                  f"bits {info['bits_histogram']} predicted-loss "
                  f"{info['predicted_loss']:.4g}; artifact_bytes="
                  f"{artifact.nbytes()} -> {out_dir}")
        elif args.quant is not None:
            art = rtn_artifact(params, args.quant, args.group, cfg=cfg)
            if args.save_artifact:
                out_dir = args.save_artifact
            else:
                tmp_dir = tempfile.TemporaryDirectory(prefix="brecq_art_")
                out_dir = tmp_dir.name
            art.save(out_dir)
            # serve what was shipped, through the same verifying loader
            artifact = QuantizedArtifact.load(out_dir,
                                              verify=not args.no_verify)
            print(f"packed W{args.quant} artifact in "
                  f"{art.stats['pack_wall_s']:.2f}s -> {out_dir}")
        _setup_dispatch(args, cfg, params, artifact)
        return _serve(args, cfg, model, params, artifact, fp_bytes)
    finally:
        if tmp_dir is not None:
            tmp_dir.cleanup()


def _serve_engine(args, cfg, model, params, artifact, fp_bytes):
    """Continuous-batching mode: N synthetic streams with staggered
    arrivals and mixed prompt/gen lengths through the serve engine.
    The fixed-batch harness is the degenerate case (one arrival wave,
    uniform lengths)."""
    from ..serve_engine import EngineConfig, ServeEngine

    manifest = artifact.manifest if artifact is not None else {}
    kv_dtype = args.kv_dtype or manifest.get("kv_dtype") or "int8"
    page_size = args.page_size or int(manifest.get("kv_page_size") or 16)
    from ..launch.watchdog import GracefulShutdown

    num_slots = args.batch
    streams = args.streams or 2 * num_slots
    max_len = args.prompt_len + args.gen_len
    pages_per = -(-max_len // page_size)
    num_pages = args.num_pages or 1 + num_slots * pages_per
    ecfg = EngineConfig(
        num_slots=num_slots, page_size=page_size,
        num_pages=num_pages, max_len=max_len,
        prefill_chunk=min(args.prefill_chunk, max(args.prompt_len, 1)),
        kv_dtype=kv_dtype, overcommit=args.overcommit)
    hook = artifact.hook() if artifact is not None else None
    weights = artifact.params if artifact is not None else params
    from ..models.common import NO_QUANT
    eng = ServeEngine(model, weights, ecfg, quant=hook or NO_QUANT)
    t_compile = eng.compile()

    rng = np.random.default_rng(args.seed)
    corpus = Corpus(CorpusConfig(vocab=cfg.vocab))
    arrivals = sorted(int(a) for a in rng.integers(0, 4 * streams, streams))
    plens = rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1,
                         streams)
    gens = rng.integers(max(args.gen_len // 2, 1), args.gen_len + 1, streams)
    prompts = [corpus.sample(1, int(plens[i]), seed=args.seed + i)[0]
               for i in range(streams)]
    gs = GracefulShutdown() if args.drain_on_sigterm else None
    nxt = 0
    try:
        while nxt < streams or eng.pending():
            if gs is not None and gs.requested:
                statuses = eng.drain(finish=True)
                counts: dict = {}
                for st in statuses.values():
                    counts[st] = counts.get(st, 0) + 1
                print(f"[drain] signal received: admission stopped, "
                      f"in-flight work settled; request statuses {counts} "
                      f"({streams - nxt} never submitted)")
                break
            while nxt < streams and arrivals[nxt] <= eng.tick:
                eng.submit(prompts[nxt], int(gens[nxt]),
                           deadline_ticks=args.deadline_ticks)
                nxt += 1
            eng.step()
    finally:
        if gs is not None:
            gs.restore()
    eng.assert_no_leaks()
    m = eng.metrics()
    pressure = (f"; preempt {m['preemptions']} expired {m['expired']} "
                f"failed {m['failed']} stragglers {m['stragglers']}"
                if (m["preemptions"] or m["expired"] or m["failed"]
                    or m["stragglers"]) else "")
    print(f"[engine {kv_dtype}] compile {t_compile:.2f}s; {streams} streams "
          f"over {num_slots} slots ({num_pages} pages, overcommit="
          f"{args.overcommit}): {m['tokens_generated']} tokens in "
          f"{m['wall_s']:.2f}s ({m['sustained_tok_s']:.1f} tok/s sustained); "
          f"occupancy {m['mean_slot_occupancy']:.2f}; resident KV "
          f"{m['mean_resident_kv_bytes_per_stream']/1e3:.1f}KB/stream "
          f"(page {page_size} tok, {m['bytes_per_page']/1e3:.1f}KB)"
          f"{pressure}")
    return m


def _serve(args, cfg, model, params, artifact, fp_bytes):
    if args.engine:
        if artifact is not None:
            art_bytes = artifact.nbytes()
            print(f"weights resident as packed int codes: "
                  f"{fp_bytes/1e6:.1f}MB fp32 -> {art_bytes/1e6:.1f}MB packed")
        return _serve_engine(args, cfg, model, params, artifact, fp_bytes)
    corpus = Corpus(CorpusConfig(vocab=cfg.vocab))
    prompts = jnp.asarray(corpus.sample(args.batch, args.prompt_len, seed=7))
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        rng = np.random.default_rng(0)
        batch["patches"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.enc_dec:
        rng = np.random.default_rng(0)
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, args.prompt_len, cfg.d_model)), jnp.float32)

    if artifact is None:
        gen, _ = _run_once(model, params, batch, args, tag="fp")
        print("sample:", np.asarray(gen[0][:16]))
        return gen

    art_bytes = artifact.nbytes()
    print(f"weights resident as packed int codes: {fp_bytes/1e6:.1f}MB fp32 -> "
          f"{art_bytes/1e6:.1f}MB packed ({art_bytes/fp_bytes:.3f}x)")
    if art_bytes >= fp_bytes:
        raise ArtifactMismatchError(
            f"packed artifact ({art_bytes} bytes) is not smaller than the FP "
            f"model ({fp_bytes} bytes) — the artifact does not belong to "
            f"this model or holds unpacked weights")

    hook = artifact.hook()
    if args.packed_backend != "auto":
        hook = copy.copy(hook)  # NO_QUANT is a shared singleton
        hook.packed_backend = args.packed_backend
    gen, qstat = _run_once(model, artifact.params, batch, args,
                           hook=hook, tag="packed")
    if not args.no_compare_fp:
        _, fstat = _run_once(model, params, batch, args, tag="fp")
        print(f"packed vs fp: {qstat['tok_s']:.1f} vs {fstat['tok_s']:.1f} tok/s "
              f"decode; bytes {art_bytes/1e6:.1f}MB vs {fp_bytes/1e6:.1f}MB")
    print("sample:", np.asarray(gen[0][:16]))
    return gen


if __name__ == "__main__":
    main()
