"""Reduce a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``
alone.  From it come:

* device operations: the events of each device plane's ops line (on a
  TPU, ``/device:TPU:<n>`` / ``XLA Ops``).  An event's name is the HLO
  instruction's text, shapes and memory spaces included;
* program executions: the device plane's modules line (``XLA
  Modules``), one event per run of a compiled program;
* host spans: the harness's ``TraceAnnotation`` events, by name.

From those it computes, inside the traced window (the harness's
``traced_window`` span): the union of busy intervals and the idle
share; device time and executions per program kind; for each kernel,
per program kind, its device time and the sum of its calls' least
times (the larger of operations over peak and HBM bytes over
bandwidth, each call's shapes read from its instruction text); the
device operations that took most time; and the longest idle gaps, each
labelled by the innermost harness span open at its middle.

The names the program gives its compiled steps and kernels live here,
in :data:`PROGRAMS` and :data:`KERNELS`, and nowhere else.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import heapq
import re

import roofline

#: program kind -> pattern of the compiled program's name in the trace
PROGRAMS = {"decode": re.compile(r"jit__decode\b"),
            "prefill_chunk": re.compile(r"jit__prefill_chunk\b")}

#: kernel -> pattern of its device operation's instruction name
KERNELS = {"lowrank": re.compile(r"^%lowrank_matmul[.\s]")}

#: operations that contain others (a loop, a branch, a call): left out
#: of sums of device time, which their bodies already count
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s]")

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACED = "traced_window"
TOP = 10

_SHAPE = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]\{([^}]*)\}")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "bf16": 2, "f16": 2, "s16": 2, "f32": 4, "s32": 4, "u32": 4,
             "f64": 8, "s64": 8}


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int          # ns
    end: int            # ns
    module: str = ""

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Ev]]          # device -> operations, by start
    modules: dict[str, list[Ev]]      # device -> program executions
    spans: list[Ev]                   # host spans


def _stats(e) -> dict:
    try:
        return dict(e.stats)
    except Exception:
        return {}


def load(path: str, span_names) -> Trace:
    """Read the device and host events of an ``.xplane.pb`` file.  A
    plane named ``/device:...`` gives its ops and modules lines; the
    host planes give the spans named in ``span_names`` and, where the
    host runs the compiled programs itself (the CPU backend), the
    events that carry an ``hlo_op`` stat, as the operations of device
    ``host`` with their ``hlo_module`` stat naming the program."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = set(span_names) | {TRACED}
    ops, modules, spans = {}, {}, []

    def ev(e, module=""):
        return Ev(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                  module)

    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [ev(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [ev(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append(ev(e))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        # one execution of a program: module and run id
                        ops.setdefault("host", []).append(
                            ev(e, f"{st.get('hlo_module', '')}"
                                  f"#{st.get('run_id', '')}"))
    for dev in ops:
        ops[dev].sort(key=lambda e: e.start)
        if dev in modules:
            ops[dev] = _attribute(ops[dev], modules[dev])
        else:
            modules[dev] = _modules_from_ops(ops[dev])
    return Trace(ops, modules, spans)


def _modules_from_ops(ops: list[Ev]) -> list[Ev]:
    """Program executions as runs of consecutive ops of one module."""
    out: list[Ev] = []
    for e in ops:
        if out and out[-1].name == e.module:
            out[-1] = Ev(e.module, out[-1].start, max(out[-1].end, e.end))
        else:
            out.append(Ev(e.module, e.start, e.end))
    return out


def _attribute(ops: list[Ev], modules: list[Ev]) -> list[Ev]:
    """Give each op the program execution that contains its start."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        name = mods[i].name if i >= 0 and e.start < mods[i].end else ""
        out.append(dataclasses.replace(e, module=name))
    return out


def clip(evs: list[Ev], lo: int, hi: int) -> list[Ev]:
    return [dataclasses.replace(e, start=max(e.start, lo),
                                end=min(e.end, hi))
            for e in evs if e.end > lo and e.start < hi]


def union(evs: list[Ev]) -> list[tuple[int, int]]:
    """Merged busy intervals of ``evs``."""
    out: list[list[int]] = []
    for e in sorted(evs, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class SpanIndex:
    """The innermost harness span open at a time (spans nest at most a
    few deep)."""

    def __init__(self, spans: list[Ev], depth: int = 4):
        self.spans = sorted((s for s in spans if s.name != TRACED),
                            key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]
        self.depth = depth

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        best = None
        for s in self.spans[max(0, i - self.depth + 1):i + 1]:
            if s.start <= t < s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best else "none"


def program_kind(name: str) -> str:
    for kind, pat in PROGRAMS.items():
        if pat.search(name):
            return kind
    return "other"


def kernel_of(name: str) -> str | None:
    for kernel, pat in KERNELS.items():
        if pat.search(name):
            return kernel
    return None


def op_label(e: Ev) -> str:
    """``<program kind>:<instruction name> <result type>``."""
    name = e.name.split(" = ", 1)
    head = name[0]
    first = _SHAPE.search(name[1]) if len(name) > 1 else None
    result = f" {first.group(1)}[{first.group(2)}]" if first else ""
    return f"{program_kind(e.module)}:{head}{result}"


def shapes(text: str) -> list[tuple[str, tuple[int, ...], bool]]:
    """``(dtype, dims, in_hbm)`` of every array in an instruction's text,
    the result first; an array in memory space ``S(1)`` (the chip's
    on-core memory) is not in HBM."""
    out = []
    for dt, dims, layout in _SHAPE.findall(text):
        d = tuple(int(x) for x in dims.split(",") if x)
        out.append((dt, d, "S(1)" not in layout))
    return out


def lowrank_cost(text: str) -> tuple[float, float] | None:
    """Operations and HBM bytes of one ``lowrank_matmul`` call from its
    instruction text, ``y (m, s) = custom-call(x (m, c), w0 (c, r),
    w1 (r, s))``.  Operands already in on-core memory move no HBM
    bytes in this call."""
    arrs = shapes(text)
    if len(arrs) < 4 or any(len(a[1]) != 2 for a in arrs[:4]):
        return None
    (dt, y, yh), (_, (m, c), xh), (_, w0, w0h), (_, (r, s), w1h) = arrs[:4]
    return roofline.lowrank_call(m, c, r, s, _ITEMSIZE.get(dt, 2),
                                 (xh, w0h, w1h, yh))


def reduce(tr: Trace, peaks: roofline.Peaks | None) -> dict | None:
    """Busy and idle time of the traced window, device time and
    executions per program kind, each kernel's device time and summed
    least time per program kind, and the breakdown: the device ops
    that took most time and the longest labelled idle gaps."""
    win = [s for s in tr.spans if s.name == TRACED]
    if not win or not tr.ops:
        return None
    lo, hi = win[0].start, win[0].end
    busy_s, program_s, counts = [], {}, {}
    kernel_s, kernel_least_s = {}, {}
    top = collections.Counter()
    idle, by_span = [], collections.Counter()
    index = SpanIndex(tr.spans)
    for dev, ops in tr.ops.items():
        ops_w = [e for e in clip(ops, lo, hi)
                 if not CONTAINERS.match(e.name)]
        busy = union(ops_w)
        busy_s.append(sum(b - a for a, b in busy) / 1e9)
        for e in ops_w:
            top[op_label(e)] += e.dur / 1e9
            k = kernel_of(e.name)
            if k is None:
                continue
            key = f"{k}/{program_kind(e.module)}"
            kernel_s[key] = kernel_s.get(key, 0.0) + e.dur / 1e9
            cost = lowrank_cost(e.name) if k == "lowrank" else None
            if cost is not None and peaks is not None:
                kernel_least_s[key] = (kernel_least_s.get(key, 0.0)
                                       + roofline.least_time(*cost, peaks))
        for m in clip(tr.modules.get(dev, []), lo, hi):
            kind = program_kind(m.name)
            program_s[kind] = program_s.get(kind, 0.0) + m.dur / 1e9
            counts[kind] = counts.get(kind, 0) + 1
        for a, b in gaps(busy, lo, hi):
            label = index.at((a + b) // 2)
            by_span[label] += (b - a) / 1e9
            idle.append((label, (b - a) / 1e9))
        idle = heapq.nlargest(TOP, idle, key=lambda x: x[1])
    n = len(busy_s)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "program_s": {k: v / n for k, v in program_s.items()},
        "executions": counts,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "kernel_least_s": {k: v / n for k, v in kernel_least_s.items()},
        "idle_by_span_s": {k: v / n for k, v in by_span.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top.most_common(TOP)],
            "idle_gaps": [[label, s] for label, s in
                          heapq.nlargest(TOP, idle, key=lambda x: x[1])],
        },
    }


def reduce_file(path: str, span_names, peaks) -> dict | None:
    return reduce(load(path, span_names), peaks)
