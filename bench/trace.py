"""Reduction of a profiler trace to device time, kept with the benchmark.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. On each device plane (``/device:TPU:<n>``) the
line of XLA operations holds one event per operation run; host threads hold
the benchmark's own ``TraceAnnotation`` spans. Both are on one clock.

* busy: the union of the operation intervals inside the traced window;
* Pallas time: the union of the intervals of operations that are Pallas
  (Mosaic) custom calls, told apart by the HLO text the trace names them by,
  and the same per kernel (the op name up to its instance number, e.g.
  ``logmatmul_pallas``);
* idle gaps: the complements of busy inside the window, each named by the
  innermost benchmark span (``bench.*``) running on the host at its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


@dataclass
class Op:
    name: str
    start: int               # ns
    end: int
    pallas: bool

    @property
    def short(self) -> str:
        """``%logmatmul_pallas.46 = s32[8,2560]{...} custom-call(...)`` ->
        ``logmatmul_pallas s32[8,2560]``: the op without its instance
        number, and its output shape."""
        lhs, _, rhs = self.name.partition(" = ")
        base = lhs.lstrip("%").rsplit(".", 1)[0] if "." in lhs else \
            lhs.lstrip("%")
        shape = rhs.split("{", 1)[0].split(" ", 1)[0] if rhs else ""
        return f"{base} {shape}".strip()

    @property
    def container(self) -> bool:
        """A loop or call whose body's ops are events of their own."""
        return self.short.split(" ", 1)[0] in ("while", "conditional", "call")


@dataclass
class Reduced:
    window_s: float
    busy_s: float            # mean over the device planes
    pallas_s: float | None   # None when no operation can be classified
    devices: int
    ops: list                # [(op, seconds)] most device time first;
    #                          loops are left out (their body ops count)
    kernels: dict            # Pallas kernel (op name, e.g.
    #                          logmatmul_pallas) -> seconds, busy union
    gaps: list               # [(host span, seconds)] most idle time first


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def is_pallas(name: str) -> bool:
    """A Pallas (Mosaic) kernel: the op's HLO text, which the trace gives as
    its name, is a custom call to ``tpu_custom_call``."""
    return "tpu_custom_call" in name


def union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(iv) -> int:
    return sum(b - a for a, b in iv)


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_ops(pd) -> dict:
    """{plane name: [Op]} for every TPU device plane."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                ops.append(Op(ev.name, s, s + int(ev.duration_ns),
                              is_pallas(ev.name)))
        out[plane.name] = ops
    return out


def host_spans(pd, prefix: str = "bench.") -> list:
    """[(name, start, end)] of the benchmark's host spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def reduce(path: str, top: int = 10) -> Reduced:
    pd = load(path)
    return reduce_events(device_ops(pd), host_spans(pd), top)


def reduce_events(planes: dict, spans: list, top: int = 10) -> Reduced:
    """Busy, Pallas and idle time inside the ``bench.window`` span.

    ``planes``: {device plane: [Op]}; ``spans``: [(name, start, end)] of the
    benchmark's host spans, on the same clock (ns).
    """
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = win[0]
    if not planes:
        raise RuntimeError("no TPU device plane in the trace")
    busy, pallas, per_op = [], [], defaultdict(int)
    kern = defaultdict(list)
    gaps = defaultdict(int)
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in inner]
    any_pallas = False
    for ops in planes.values():
        iv = union(_clip([(o.start, o.end) for o in ops], lo, hi))
        busy.append(_length(iv))
        pv = union(_clip([(o.start, o.end) for o in ops if o.pallas],
                         lo, hi))
        any_pallas |= bool(pv)
        pallas.append(_length(pv))
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0 and not o.container:
                per_op[o.short] += d
        by_kernel = defaultdict(list)
        for o in ops:
            if o.pallas:
                by_kernel[o.short.split(" ", 1)[0]].append((o.start, o.end))
        for k, kv in by_kernel.items():
            kern[k].append(_length(union(_clip(kv, lo, hi))))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_span_at(inner, starts, (a + b) // 2)] += b - a
    n = len(planes)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=sum(busy) / n * ns,
        pallas_s=(sum(pallas) / n * ns) if any_pallas else None,
        devices=n,
        ops=[[k, v / n * ns] for k, v in
             sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        gaps=[[k, v / n * ns] for k, v in
              sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        kernels={k: sum(v) / n * ns for k, v in kern.items()})


def _span_at(spans, starts, t) -> str:
    """The host span (they do not nest) that covers t."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][2] >= t:
        return spans[i][0]
    return "between bench spans"
