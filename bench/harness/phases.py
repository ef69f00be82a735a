"""The engine's own spans and the scopes of its device operations, for the
readers that need more of a traced run than ``trace.read_xplane`` keeps.

``read_xplane`` keeps the harness's ``bench.*`` host spans and each device
operation's HLO instruction name. This module adds, for the same run:

- the engine's ``serve.*`` host spans (``repro.serve_engine.engine.SPANS``),
  read again from the same ``.xplane.pb``; they lie on the profiler's
  clock, which the device events share;
- the scope path of each device operation: the ``op_name`` of its XLA op
  metadata (``jit(decode_fn)/while/body/closed_call/kv_write/scatter``).
  On a TPU trace the operation's event metadata holds it (stat
  ``tf_op``), but ``jax.profiler.ProfileData`` gives only each event's
  own stats, so it is read from the compiled programs' HLO text, by
  module and instruction name. A fusion is attributed by its root: it
  takes the ``op_name`` of
  the root instruction of the computation it calls, so a fusion that
  holds operations of several scopes counts wholly to its root's scope.

``with_phases`` joins both to a ``read_xplane`` record, so the spans sit
beside the harness's own in ``spans`` and each operation's marks hold its
``scope``. ``Phases`` reduces such a record; on a record with neither
(a program without the spans and scopes) its readings are ``None``.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import tempfile
from collections import defaultdict

from .trace import OP_LINE, Trace, gaps, self_times

SPAN_PREFIX = "serve."
TICK = "serve.tick"
FETCH_SUFFIX = ".fetch"
# ``bench/run.py`` records into ``tempfile.mkdtemp(prefix=...)`` and removes
# that directory after every reader has run
TRACE_DIR_PREFIX = "bench_trace_"
QMM_KERNELS = ("qgemv", "qmatmul")
SCOPES = ("kv_write", "kv_read")

_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%\S+) ")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?(%\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=(%[^\s,]+)")


def hlo_scopes(text: str) -> tuple[str, dict]:
    """(module name, {instruction name: op_name}) of one compiled
    program's HLO text. A fusion takes its called computation's root's
    op_name, or its own where the root has none (a tuple)."""
    module, comp = "", None
    own, root, calls = {}, {}, {}
    for line in text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m:
            name = m.group(2)
            op = _OP_NAME.search(line)
            own[name] = op.group(1) if op else ""
            if m.group(1):
                root[comp] = name
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)

    def scope(name, depth=0):
        called = calls.get(name)
        if called in root and depth < 8:
            inner = scope(root[called], depth + 1)
            if inner:
                return inner
        return own.get(name, "")

    return module, {name: scope(name) for name in own}


def serve_spans(trace_dir: str) -> list:
    """[name, start ns, duration ns] of every ``serve.*`` host event in the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                        if e.name.startswith(SPAN_PREFIX)]
    return out


def with_phases(rec: dict, spans: list, scopes: dict) -> dict:
    """A copy of a ``read_xplane`` record with the engine's spans added to
    ``spans`` and each device operation's ``scope`` in its marks.
    ``scopes``: {module name: {instruction name: op_name}}."""
    tr = Trace(rec)
    ops = []
    for row, ev in zip(rec["device"].get(OP_LINE, []), tr.ops):
        marks = dict(row[3]) if len(row) > 3 else {}
        scope = scopes.get(tr.program_of(ev), {}).get(row[0], "")
        if scope:
            marks["scope"] = scope
        ops.append([*row[:3], marks])
    device = dict(rec["device"], **{OP_LINE: ops})
    return dict(rec, device=device, spans=rec["spans"] + spans)


class Phases(Trace):
    """Reductions over a record that holds the engine's spans and scopes."""

    @staticmethod
    def scope_of(op) -> str:
        return op.stats.get("scope", "")

    def _calls(self, fn_name: str):
        """(executions of the program, the ops inside them) or None."""
        runs = self.module_runs(fn_name)
        return (runs, self.ops_within(runs)) if runs else None

    def has_scopes(self, fn_name: str) -> bool:
        calls = self._calls(fn_name)
        return bool(calls) and any(
            part in SCOPES for o in calls[1]
            for part in self.scope_of(o).split("/"))

    def split(self, fn_name: str) -> dict | None:
        """Seconds per execution of the program, by part: the qmm kernels,
        the ``kv_decode`` kernel, the other ops of each scope in ``SCOPES``,
        and the rest. Parts are self times (a loop's own time goes to the
        rest), and add up to the device time less the gaps between
        operations."""
        calls = self._calls(fn_name)
        if not calls:
            return None
        runs, ops = calls
        out = dict.fromkeys(("qmm", "kv_decode", *SCOPES, "rest"), 0.0)
        for o, own in self_times(ops):
            kernel = self.kernel_of(o)
            if kernel in QMM_KERNELS:
                part = "qmm"
            elif kernel == "kv_decode":
                part = kernel
            else:
                parts = self.scope_of(o).split("/")
                part = next((s for s in SCOPES if s in parts), "rest")
            out[part] += own
        return {k: v / len(runs) for k, v in out.items()}

    def ticks(self) -> list:
        return self.in_window(self.spans_named(TICK))

    def tick_host_s(self) -> float | None:
        """Mean over ticks of the tick's span less the parts its
        ``serve.*.fetch`` spans cover: host work per tick."""
        ticks = self.ticks()
        if not ticks:
            return None
        fetches = [(s.start, s.end) for s in self.spans
                   if s.name.startswith(SPAN_PREFIX)
                   and s.name.endswith(FETCH_SUFFIX)]
        total = 0.0
        for t in ticks:
            inside = [(a, b) for a, b in fetches if t.start <= a and b <= t.end]
            total += t.dur - sum(b - a for a, b in inside)
        return total / len(ticks)

    def readback_s(self, fn_name: str, fetch: str) -> float | None:
        """Mean over the ``fetch`` spans of (end of the span - end of the
        program execution that ended inside it); None if any such span
        holds no execution end."""
        spans = self.in_window(self.spans_named(fetch))
        ends = sorted(r.end for r in self.module_runs(fn_name))
        if not spans or not ends:
            return None
        total = 0.0
        for s in spans:
            inside = [e for e in ends if s.start <= e <= s.end]
            if not inside:
                return None
            total += s.end - inside[-1]
        return total / len(spans)

    def idle_by_phase(self) -> dict:
        """Device idle seconds inside the harness's ``bench.step`` spans, by
        the innermost span they fall in: a ``serve.*`` phase,
        ``serve.tick`` outside every phase, or ``bench.step`` outside the
        tick."""
        busy = [(o.start, o.end) for o in self.ops]
        named = sorted((s for s in self.spans if s.name.startswith(SPAN_PREFIX)
                        or s.name == "bench.step"), key=lambda s: s.dur)
        out = defaultdict(float)
        for step in self.spans_named("bench.step"):
            lo, hi = max(step.start, self.lo), min(step.end, self.hi)
            near = [s for s in named if s.end > lo and s.start < hi]
            for a, b in gaps(busy, lo, hi) if hi > lo else []:
                # split the gap at every span edge inside it; name each piece
                # by the shortest span that covers it
                cuts = sorted({a, b, *(x for s in near for x in (s.start, s.end)
                                       if a < x < b)})
                for p, q in zip(cuts, cuts[1:]):
                    mid = 0.5 * (p + q)
                    inner = next(s.name for s in near if s.start <= mid <= s.end)
                    out[inner] += q - p
        return dict(out)


@functools.lru_cache(maxsize=1)
def of_run(ctx) -> Phases | None:
    """The ``Phases`` of the traced run a per-layer reader sees: the run's
    record with the spans of its trace directory and the scopes of its
    engine's two compiled programs; None when the run left no trace."""
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIR_PREFIX + "*"))
    engine = getattr(ctx.run, "engine", None)
    if not dirs or engine is None:
        return None
    spans = serve_spans(max(dirs, key=os.path.getmtime))
    scopes = {}
    for prog in (engine._decode_c, engine._chunk_c):
        prog = getattr(prog, "fn", prog)   # the harness's call recorder
        module, table = hlo_scopes(prog.as_text())
        scopes[module] = table
    return Phases(with_phases(ctx.trace.rec, spans, scopes))
