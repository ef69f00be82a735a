"""The static auditor catches a seeded violation of each rule family —
and passes the repo's real programs at HEAD.

Program family: each rule is driven to fire by injecting its failure
mode into a real-shaped program (forced full f32 dequant on a decode
path, a donation the compiled module drops, a host callback, a retrace).
Kernel family: a mis-tiled BlockSpec and a VMEM blow-up through the same
describe_* specs the kernel wrappers call. AST family: an offending
source file through the same linter CI runs over src/.
"""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.audit import ast_lint, kernel_check  # noqa: F401 (register catalog rules)
from repro.analysis.audit.program_check import (forbidden_f32_shapes,
                                                qmm_programs)
from repro.analysis.audit.rules import (AuditProgram, Violation,
                                        count_io_aliases, iter_jaxprs,
                                        registered_rules, run_program_rules)
from repro.deploy import dequant_leaf, rtn_pack_leaf
from repro.kernels.spec import (VMEM_BUDGET_BYTES, BlockTilingError,
                                KernelSpecError, aligned_tile,
                                describe_fakequant, describe_kv_decode,
                                describe_qgemv, describe_qmatmul,
                                describe_qmatmul_grouped, fakequant_tiles,
                                qmm_tiles)

ROOT = Path(__file__).resolve().parents[1]


def _packed(rng, shape, bits=4):
    w = jnp.asarray(rng.normal(size=shape), jnp.float32)
    wp, qs = rtn_pack_leaf(w, bits, None)
    return {"w": wp, "qscale": qs}


# ---------------------------------------------------------------------------
# program rules: seeded violations
# ---------------------------------------------------------------------------


def test_seeded_f32_dequant_fires(rng):
    """A decode-shaped program that routes a stacked expert node through
    the full dequant reference trips no_materialized_f32_weight."""
    E, K, N = 4, 64, 128
    node = _packed(rng, (E, K, N))
    forbidden = forbidden_f32_shapes({"moe": node})
    assert (E, K, N) in forbidden

    def bad_decode(x, w, qs):
        wf = dequant_leaf(w, qs, K)  # f32 (E, K, N) materialized
        return jnp.einsum("emk,ekn->emn", x, wf)

    prog = AuditProgram(
        name="seeded_dequant", fn=bad_decode,
        args=(jnp.ones((E, 2, K), jnp.float32), node["w"], node["qscale"]),
        forbidden_f32=forbidden)
    found = run_program_rules([prog], rules=("no_materialized_f32_weight",))
    assert found and all(v.rule == "no_materialized_f32_weight"
                         for v in found)
    assert f"{(E, K, N)}" in found[0].message


def test_seeded_dropped_donation_fires():
    """A declared donation the compiled module cannot honor (no output
    matches the donated buffer) trips donation_respected."""

    def f(x, c):
        return x + 1.0  # c: declared donated, aliased into nothing

    prog = AuditProgram(
        name="seeded_drop", fn=f,
        args=(jnp.ones((4,), jnp.float32), jnp.ones((8,), jnp.float32)),
        donate_argnums=(1,))
    found = run_program_rules([prog], rules=("donation_respected",))
    assert found and found[0].rule == "donation_respected"
    assert "aliases only 0" in found[0].message


def test_donation_respected_on_honored_donation():
    """Sanity: a donation the compiler keeps passes the same rule."""

    def f(x, c):
        return x + c

    prog = AuditProgram(
        name="honored", fn=f,
        args=(jnp.ones((4,), jnp.float32), jnp.ones((4,), jnp.float32)),
        donate_argnums=(1,))
    assert run_program_rules([prog], rules=("donation_respected",)) == []


def test_seeded_host_callback_fires():
    """A python callback smuggled into a 'hot' program trips
    no_host_transfer via its custom-call in the optimized HLO."""

    def f(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1

    prog = AuditProgram(name="seeded_callback", fn=f,
                        args=(jnp.ones((4,), jnp.float32),))
    found = run_program_rules([prog], rules=("no_host_transfer",))
    assert found and found[0].rule == "no_host_transfer"
    assert "callback" in found[0].message


def test_seeded_retrace_fires():
    """repeat_args with a different structure force a second trace —
    stable_compile_cache reports the cache growth."""

    def f(x):
        return x * 2

    prog = AuditProgram(
        name="seeded_retrace", fn=f,
        args=(jnp.ones((4,), jnp.float32),),
        repeat_args=(jnp.ones((4,), jnp.bfloat16),))
    found = run_program_rules([prog], rules=("stable_compile_cache",))
    assert found and "retraced" in found[0].message


def test_suppression_skips_rule_and_is_surfaced():
    def f(x):
        return x * 2

    prog = AuditProgram(
        name="suppressed", fn=f, args=(jnp.ones((4,), jnp.float32),),
        repeat_args=(jnp.ones((4,), jnp.bfloat16),),
        suppress={"stable_compile_cache": "intentional dtype probe"})
    log = []
    assert run_program_rules([prog], rules=("stable_compile_cache",),
                             verbose=log.append) == []
    assert any("intentional dtype probe" in s for s in log)


def test_real_qmm_programs_clean(rng):
    """The actual dispatch-tier programs audit clean (HEAD must pass)."""
    assert run_program_rules(qmm_programs(jax.random.PRNGKey(7))) == []


def test_iter_jaxprs_covers_scan_body():
    def f(x):
        def body(c, _):
            return c * 2.0, ()
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    prims = {e.primitive.name for jx in iter_jaxprs(jaxpr.jaxpr)
             for e in jx.eqns}
    assert "scan" in prims and "mul" in prims  # outer + body both walked


def test_count_io_aliases_nested_braces():
    hlo = ('HloModule m, input_output_alias={ {}: (1, {}, may-alias), '
           '{0}: (2, {}, must-alias) }\n')
    assert count_io_aliases(hlo) == 2
    assert count_io_aliases("HloModule m\n") == 0


# ---------------------------------------------------------------------------
# kernel rules: seeded violations
# ---------------------------------------------------------------------------


def test_seeded_mistiled_blockspec_fires():
    # bm does not divide M
    with pytest.raises(KernelSpecError, match="M=100 is not a multiple"):
        describe_qmatmul((100, 64), (32, 128), (1, 128), bits=4, bm=128,
                         bkp=32, bn=128)
    # packed rows inconsistent with K/bits
    with pytest.raises(KernelSpecError, match="values/byte"):
        describe_qgemv((4, 64), (40, 128), (1, 128), bits=4, bkp=40, bn=128)
    # expert axes disagree
    with pytest.raises(KernelSpecError, match="expert axes"):
        describe_qmatmul_grouped((4, 8, 64), (3, 32, 128), (3, 1, 128),
                                 bits=4, bm=8, bkp=32, bn=128)


def test_seeded_vmem_blowup_fires():
    sp = describe_qmatmul((4096, 512), (256, 8192), (1, 8192), bits=4,
                          bm=4096, bkp=256, bn=8192)
    assert sp.vmem_bytes > VMEM_BUDGET_BYTES
    with pytest.raises(KernelSpecError, match="exceeds the declared budget"):
        sp.check_budget()


def test_kernel_sweep_flags_bad_leaf():
    """The audit sweep converts KernelSpecError into rule violations."""
    out = []
    kernel_check._sweep_leaf(
        "fake_arch", "body/w", 100, (40, 128), (1, 128),
        lambda r, s, m: out.append(Violation(r, s, m)))
    assert out and out[0].rule == "kernel_tile_divisibility"
    # the weight sweep mirrors the runtime tile caps, which bound VMEM
    # by construction — the budget rule is seeded through the KV sweep,
    # whose query-group block scales with the config's head layout
    import dataclasses

    @dataclasses.dataclass
    class FakeCfg:
        n_heads: int = 8
        n_kv_heads: int = 1
        hd: int = 1 << 19

    out2 = []
    kernel_check._sweep_kv("fake_arch", FakeCfg(),
                           lambda r, s, m: out2.append(Violation(r, s, m)))
    assert any(v.rule == "kernel_vmem_budget" for v in out2)


def test_registered_configs_sweep_clean():
    """Every registered full-scale config's launches pass the kernel
    rules (HEAD must pass; brecq + the two canonical serving archs keep
    this test fast, CI's audit job sweeps all archs)."""
    got = kernel_check.run_kernel_checks(
        ["brecq_lm_100m", "deepseek_moe_16b", "h2o_danube3_4b"])
    assert got == [], [str(v) for v in got]


@pytest.mark.parametrize("describe,operand", [
    # a 64-row KV stream tile: scale and position blocks 64 lanes wide
    (lambda: describe_kv_decode((8, 12, 64), (8, 512, 12, 64), bs=64),
     "kscale"),
    # a 4-row fakequant tile: the sublane dim is not a multiple of 8
    (lambda: describe_fakequant((768, 768), (1, 768), bk=4, bn=128), "w"),
], ids=["kv_decode_64_lanes", "fakequant_4_sublanes"])
def test_seeded_untileable_block_fires(describe, operand):
    """The chip's block rule: a block's last two dims are multiples of
    (8, 128) or equal the array's. Mosaic refuses anything else, so the
    CPU audit must too."""
    with pytest.raises(BlockTilingError, match=f"operand '{operand}'"):
        describe()


def test_aligned_tile_picks_lane_aligned_divisors():
    assert aligned_tile(3840, 512, 128) == 384
    assert aligned_tile(512, 512, 128) == 512
    assert aligned_tile(288, 512, 128) == 288  # no aligned divisor: whole
    assert aligned_tile(10944, 256, 8) == 192
    assert aligned_tile(10944, 256, 128) == 10944


def test_row_and_fakequant_tiles_fit_vmem_for_untiled_k():
    """d_ff 10944 has no 128-lane divisor, so a k-step or a fakequant
    tile spans it whole; the pickers shrink the other tile to fit."""
    bm, bkp, bn = qmm_tiles(128, 10944, 128, 1, bits=4)
    assert bkp == 5472 and 8 <= bm < 128 and bn == 128
    describe_qmatmul((128, 10944), (5472, 128), (1, 128), bits=4, bm=bm,
                     bkp=bkp, bn=bn).check_budget()
    bk, bn = fakequant_tiles(2048, 10944)
    assert bn == 10944 and bk % 8 == 0
    describe_fakequant((2048, 10944), (1, 10944), bk=bk,
                       bn=bn).check_budget()


# InternLM2-20B's packed linears: wq, wk, wv, wo, w_gate, w_up, w_down
# (W4, per column) and the untied 8-bit head.
INTERNLM2_20B = [(6144, 6144, 4), (6144, 1024, 4), (6144, 1024, 4),
                 (6144, 6144, 4), (6144, 16384, 4), (6144, 16384, 4),
                 (16384, 6144, 4)]
INTERNLM2_20B_HEAD = (6144, 92544, 8)


@pytest.mark.parametrize("M", [32, 8])
def test_qmm_tiles_stream_weights_once_at_internlm2_20b(M):
    """A 32-slot decode step (or a 32-row prefill chunk) is one row block,
    so each packed weight tile streams once per call, in steps large
    enough that 20 layers and the head take under 10,000 grid steps
    (bm = 8, bn = 128 and 512-row k-steps took 272,784); every launch
    fits the VMEM budget with its unpacked field planes counted."""
    steps = 0
    for (K, N, bits), layers in [*[(s, 20) for s in INTERNLM2_20B],
                                 (INTERNLM2_20B_HEAD, 1)]:
        bm, bkp, bn = qmm_tiles(M, K, N, 1, bits=bits)
        assert bm == M
        sp = describe_qmatmul((M, K), (K * bits // 8, N), (1, N), bits=bits,
                              bm=bm, bkp=bkp, bn=bn)
        sp.check_budget()
        assert sp.grid[0] == 1 and sp.meta["x_resident"]
        assert "planes" in sp.temps and bkp * bn >= 256 * 1024
        steps += layers * sp.num_programs
    assert steps < 10_000


# ---------------------------------------------------------------------------
# AST rules: seeded violations
# ---------------------------------------------------------------------------

BAD_SOURCE = '''
import time, jax
import numpy as np

@jax.jit
def step(x):
    t0 = time.perf_counter()
    y = np.asarray(x)
    return x.item()

def helper(x):
    return jax.device_get(x)

jit_helper = jax.jit(helper)

def bad_default(xs=[]):
    return xs

def kern(x, interpret=True):
    assert x.ndim == 2
    return x

def fine(x, interpret=False):  # audit: ignore[no_interpret_default_true]
    return x
'''


def test_seeded_ast_offenders_fire(tmp_path):
    pkg = tmp_path / "kernels"
    pkg.mkdir()
    (pkg / "bad.py").write_text(BAD_SOURCE)
    rules = {v.rule for v in ast_lint.run_ast_lint(tmp_path)}
    assert rules == {"no_host_sync_in_jit", "no_mutable_default_arg",
                     "no_bare_assert_in_kernels",
                     "no_interpret_default_true"}


def test_ast_suppression_comment(tmp_path):
    (tmp_path / "s.py").write_text(
        "def f(xs=[]):  # audit: ignore[no_mutable_default_arg]\n"
        "    return xs\n")
    assert ast_lint.run_ast_lint(tmp_path) == []


def test_src_tree_lints_clean():
    """HEAD must pass its own AST lints."""
    got = ast_lint.run_ast_lint(ROOT / "src")
    assert got == [], [str(v) for v in got]


def test_bare_assert_only_checked_under_kernels(tmp_path):
    (tmp_path / "other.py").write_text("def f(x):\n    assert x\n    return x\n")
    assert ast_lint.run_ast_lint(tmp_path) == []


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def test_run_audit_cli_ast_family_clean():
    p = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "run_audit.py"),
         "--family", "ast"], capture_output=True, text=True)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "audit clean" in p.stdout


def test_run_audit_cli_exits_nonzero_on_violation(tmp_path):
    """A seeded AST offender dropped into the linted tree flips the CLI
    to exit 1 and the violation is listed."""
    pkg = tmp_path / "kernels"
    pkg.mkdir()
    (pkg / "bad.py").write_text(BAD_SOURCE)
    p = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "run_audit.py"),
         "--family", "ast", "--src", str(tmp_path)],
        capture_output=True, text=True)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "AUDIT FAILED" in p.stdout
    assert "no_bare_assert_in_kernels" in p.stdout


def test_run_audit_cli_lists_rules():
    p = subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "run_audit.py"),
         "--list-rules"], capture_output=True, text=True)
    assert p.returncode == 0
    for name in ("no_materialized_f32_weight", "donation_respected",
                 "no_host_transfer", "stable_compile_cache",
                 "kernel_tile_divisibility", "kernel_vmem_budget",
                 "no_host_sync_in_jit", "no_mutable_default_arg",
                 "no_bare_assert_in_kernels", "no_interpret_default_true"):
        assert name in p.stdout, name
