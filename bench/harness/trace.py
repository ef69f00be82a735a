"""From a profiler trace to intervals the metric readers can add up.

``read_xplane`` keeps, from the ``.xplane.pb`` that ``jax.profiler``
writes, the events of the first TPU device plane (program executions
on its ``XLA Modules`` line, operations on its ``XLA Ops`` line) and
the harness's own ``bench.*`` host spans, as a small JSON-able dict.
``Trace`` reduces that dict. Times are seconds on the profiler's clock,
which the host spans and the device events share. Device operations
keep their HLO instruction name (``%qmatmul.83``) and a mark when they
are a Pallas kernel.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# a Pallas kernel is a custom call with this target; its HLO instruction
# carries the kernel's name (``%qmatmul.83 = f32[..] custom-call(..)``)
KERNEL_TARGET = "tpu_custom_call"


def _op_row(e) -> list:
    """[instruction name, start ns, duration ns, marks] of one device op;
    the op's event name is its whole HLO instruction."""
    marks = {"kernel": 1} if KERNEL_TARGET in e.name else {}
    return [e.name.split(" = ")[0], e.start_ns, e.duration_ns, marks]


def _device_plane_index(name: str):
    """Chip number of a TPU device plane name ('/device:TPU:3' -> 3)."""
    if not name.startswith("/device:TPU:"):
        return None
    tail = name[len("/device:TPU:"):]
    return int(tail) if tail.isdigit() else None


def read_xplane(trace_dir: str) -> dict:
    """The compact record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        idx = _device_plane_index(plane.name)
        if idx is not None:
            lines = {}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    lines[line.name] = [[e.name.split("(")[0], e.start_ns,
                                         e.duration_ns] for e in line.events]
                elif line.name == OP_LINE:
                    lines[line.name] = [_op_row(e) for e in line.events]
            devices[idx] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.duration_ns])
    if not devices:
        raise ValueError(f"no TPU device plane in {paths[-1]}")
    first = min(devices)
    return {"device": devices[first], "n_devices": len(devices),
            "spans": spans}


def union_length(intervals) -> float:
    """Length covered by a set of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(intervals, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def self_times(ops) -> list:
    """(op, its own seconds): an op's time less that of the ops nested in
    it (a ``while`` loop holds the ops of its body on the same line)."""
    out, stack = [], []   # stack: [op, own time] of the open enclosing ops
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= op.start:
            out.append(tuple(stack.pop()))
        if stack and op.end <= stack[-1][0].end:
            stack[-1][1] -= op.dur
        stack.append([op, op.dur])
    out.extend(tuple(x) for x in stack)
    return out


@dataclasses.dataclass
class Ev:
    name: str
    start: float
    end: float
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def _evs(rows) -> list:
    return [Ev(r[0], r[1] * 1e-9, (r[1] + r[2]) * 1e-9,
               r[3] if len(r) > 3 else {}) for r in rows]


class Trace:
    """Reductions over one compact trace record."""

    def __init__(self, rec: dict):
        self.rec = rec
        self.modules = _evs(rec["device"].get(MODULE_LINE, []))
        self.ops = _evs(rec["device"].get(OP_LINE, []))
        self.spans = _evs(rec["spans"])
        win = self.spans_named("bench.window")
        if win:
            self.lo, self.hi = win[0].start, win[0].end
        else:
            pts = [e.start for e in self.ops] + [e.end for e in self.ops]
            self.lo, self.hi = min(pts), max(pts)

    # -- selections --------------------------------------------------------

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def in_window(self, evs) -> list:
        return [e for e in evs if e.start >= self.lo and e.end <= self.hi]

    def module_runs(self, fn_name: str) -> list:
        """Executions of the program jitted from the function ``fn_name``
        (XLA names the module ``jit_<fn_name>``)."""
        want = f"jit_{fn_name}"
        return [m for m in self.in_window(self.modules) if m.name == want]

    def ops_within(self, runs) -> list:
        """Device operations that lie inside any of the given runs."""
        runs = sorted(runs, key=lambda r: r.start)
        out, j = [], 0
        for op in sorted(self.in_window(self.ops), key=lambda o: o.start):
            while j < len(runs) and runs[j].end < op.start:
                j += 1
            if j < len(runs) and runs[j].start <= op.start and op.end <= runs[j].end + 1e-9:
                out.append(op)
        return out

    @staticmethod
    def kernel_of(op: Ev) -> str:
        """The Pallas kernel an operation runs (``%qmatmul.83`` ->
        ``qmatmul``), or '' for any other op."""
        if not op.stats.get("kernel"):
            return ""
        return op.name.lstrip("%").split(".")[0]

    def kernel_time(self, fn_name: str, kernels) -> float:
        """Seconds the named Pallas kernels ran inside executions of the
        program jitted from ``fn_name``."""
        ops = self.ops_within(self.module_runs(fn_name))
        return sum(o.dur for o in ops if self.kernel_of(o) in kernels)

    def program_of(self, op: Ev) -> str:
        """The program execution an operation lies in ('' if none)."""
        if not hasattr(self, "_mod_starts"):
            self._mods = sorted(self.modules, key=lambda m: m.start)
            self._mod_starts = [m.start for m in self._mods]
        i = bisect.bisect_right(self._mod_starts, op.start) - 1
        if i >= 0 and op.end <= self._mods[i].end + 1e-9:
            return self._mods[i].name
        return ""

    # -- time --------------------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        return union_length(clip([(o.start, o.end) for o in self.ops],
                                 self.lo, self.hi))

    def idle_within(self, span_name: str) -> tuple[float, float]:
        """(device idle seconds, total seconds) inside the host spans of
        that name."""
        busy = [(o.start, o.end) for o in self.ops]
        idle = total = 0.0
        for s in self.spans_named(span_name):
            lo, hi = max(s.start, self.lo), min(s.end, self.hi)
            if hi <= lo:
                continue
            total += hi - lo
            idle += sum(e - b for b, e in gaps(busy, lo, hi))
        return idle, total

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest
        idle gaps named by the harness span open at their middle."""
        by_op = defaultdict(float)
        for o, own in self_times(self.in_window(self.ops)):
            by_op[f"{self.program_of(o)}/{o.name.lstrip('%')}"] += own
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        holes = sorted(gaps([(o.start, o.end) for o in self.ops],
                            self.lo, self.hi), key=lambda g: g[0] - g[1])[:top]
        named = []
        for b, e in holes:
            mid = 0.5 * (b + e)
            opens = [s for s in self.spans if s.name != "bench.window"
                     and s.start <= mid <= s.end]
            inner = min(opens, key=lambda s: s.dur).name if opens else "outside"
            named.append([inner, e - b])
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
