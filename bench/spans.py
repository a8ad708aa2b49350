"""Reduction of the program's own spans and scopes in a profiler trace.

The scheduler marks each tick and its phases with ``jax.profiler``
annotations (``sched.step`` and its children, ``launch/scheduler.py``); the
model marks device work with ``jax.named_scope`` (``approx.quantize``,
``approx.rescale``, ``model.kv_cache``, ``model.head``), which XLA carries
into each operation's ``op_name`` and the trace into a stat of its event.
Everything is on the clock of the device trace, so inside the
``bench.window`` span this reads:

* per ``sched.*`` span: count, total time and self time (its length less
  the time its child spans cover), spans nested by containment on their
  host thread;
* idle by span: each idle gap of the device, named by the innermost
  ``sched.*`` span running on the host at its middle;
* decode idle: device idle inside the ``sched.step`` spans of kind
  ``decode``, and how many such ticks there were;
* device time by scope: the device time outside Pallas kernels (what
  ``approx.xla_pct`` measures) split by the outermost program scope in each
  operation's ``op_name``; a fusion counts under the ``op_name`` XLA gave
  it, and operations with no program scope count as ``unscoped``;
* the longest tick: how the window's longest ``sched.step`` splits into
  the self time of each span inside it.

It reads nothing the benchmark's own reduction (``bench/trace.py``) reads
differently, and a trace of a program without these spans and scopes reads
as no ticks and no scoped operation.

``bench/run.py`` does not call it yet. Run from the root of a checkout,

    python -m bench.spans --workload <cell> --seed <n> --seconds <s>

serves one window of a cell under the profiler as ``bench/run.py --trace 1``
does, without the reference check, and prints this reduction as one JSON
line (``--rehearse``: the CPU rehearsal, no device planes).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass

from bench.trace import DEVICE_PREFIX, WINDOW_SPAN, _clip, _length, union

PREFIX = "sched."
STEP = "sched.step"
OUTSIDE = "outside sched.step"
UNSCOPED = "unscoped"
#: the stat of an XLA operation's event metadata that holds its
#: ``op_name`` (``<op_name>:``); ``jax.profiler.ProfileData`` gives an event's
#: own stats but not its metadata's, so ``op_names`` reads it from the file
OP_NAME_STAT = b"tf_op"
#: a program scope in an ``op_name`` path: ``<layer>.<name>``, which no
#: jit frame (``jit(f)``), transform or primitive name matches
_SCOPE = re.compile(r"[a-z][a-z0-9_]*\.[a-z][a-z0-9_.]*")


@dataclass
class Span:
    name: str
    start: int               # ns
    end: int
    args: dict
    children: list

    @property
    def length(self) -> int:
        return self.end - self.start

    @property
    def self_ns(self) -> int:
        return self.length - sum(c.length for c in self.children)


@dataclass
class Spans:
    per_span: dict           # name -> {"count", "total_s", "self_s"}
    idle_by_span: list       # [(innermost span, seconds)] most idle first
    decode_ticks: int        # sched.step spans of kind decode
    decode_idle_s: float     # device idle inside them, summed
    scopes: dict             # top-level scope (or UNSCOPED) -> seconds
    scoped: bool             # some operation carried a program scope
    longest_tick: dict | None

    def decode_idle_ms(self) -> float | None:
        """Device idle inside the decode ticks, per such tick."""
        if not self.decode_ticks:
            return None
        return 1e3 * self.decode_idle_s / self.decode_ticks

    def mean_ms(self, name: str) -> float | None:
        """Mean length of the spans called ``name``."""
        d = self.per_span.get(name)
        return 1e3 * d["total_s"] / d["count"] if d else None

    def scope_pct(self, prefix: str, busy_s: float) -> float | None:
        """Share of ``busy_s`` in operations under the scopes that start
        with ``prefix``; None where no operation carried a scope."""
        if not self.scoped or busy_s <= 0:
            return None
        return 100.0 * sum(v for k, v in self.scopes.items()
                           if k.startswith(prefix)) / busy_s


def scope_of(op_name: str) -> str | None:
    """The outermost program scope of an ``op_name`` path, e.g.
    ``jit(step)/while/body/approx.quantize/mul`` -> ``approx.quantize``."""
    for part in op_name.split("/"):
        if _SCOPE.fullmatch(part):
            return part
    return None


def host_spans(pd, prefix: str = PREFIX) -> list:
    """[[Span]] per host thread: the ``prefix`` spans, with their keyword
    arguments, in start order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = int(ev.start_ns)
                    spans.append(Span(ev.name, s, s + int(ev.duration_ns),
                                      dict(ev.stats), []))
            if spans:
                out.append(spans)
    return out


def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, i: int, end: int):
    """(field number, varint value or (start, end) of a length-delimited
    value) of the protobuf message in b[i:end]; fixed-width values skipped."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise RuntimeError(f"unreadable xplane field (wire type {wire})")


def op_names(path: str) -> dict:
    """{device plane: {event name: op_name}} from the ``.xplane.pb``.

    An XSpace holds planes (field 1); a plane its name (2), event metadata
    (4: map of id -> {name 2, stats 5}) and stat metadata (5: map of id ->
    {id 1, name 2}); a stat its metadata id (1) and string value (5). The
    event lines (3) are skipped whole. An event's name in ``ProfileData``
    is its metadata's name, the operation's HLO text."""
    with open(path, "rb") as f:
        b = f.read()
    out = {}
    for field, plane in _fields(b, 0, len(b)):
        if field != 1:
            continue
        name, metas, stat_id = None, [], None
        for pf, v in _fields(b, *plane):
            if pf == 2:
                name = b[v[0]:v[1]].decode()
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                for kf, sm in _fields(b, *v):
                    if kf == 2:
                        sm = dict(_fields(b, *sm))
                        if 2 in sm and b[slice(*sm[2])] == OP_NAME_STAT:
                            stat_id = sm.get(1)
        if not name or not name.startswith(DEVICE_PREFIX) or stat_id is None:
            continue
        names = {}
        for entry in metas:
            for kf, meta in _fields(b, *entry):
                if kf != 2:
                    continue
                ev_name = op_name = None
                for mf, v in _fields(b, *meta):
                    if mf == 2:
                        ev_name = b[v[0]:v[1]].decode(errors="replace")
                    elif mf == 5:
                        st = dict(_fields(b, *v))
                        if st.get(1) == stat_id and 5 in st:
                            op_name = b[slice(*st[5])].decode(
                                errors="replace").rstrip(":")
                if ev_name and op_name:
                    names.setdefault(ev_name, op_name)
        out[name] = names
    return out


def device_scoped_ops(planes: dict, names: dict) -> dict:
    """{device plane: [(start, end, scope or None)]} of the operations
    outside Pallas kernels, loops left out (their body ops count);
    ``planes`` is ``bench.trace.device_ops`` and ``names`` ``op_names`` of
    the same trace."""
    out = {}
    for plane, ops in planes.items():
        known = names.get(plane, {})
        out[plane] = [(o.start, o.end, scope_of(known.get(o.name, "")))
                      for o in ops if not (o.pallas or o.container)]
    return out


def nest(threads: list) -> list:
    """Top-level spans of every thread, children attached by containment."""
    roots = []
    for spans in threads:
        stack: list = []
        for s in sorted(spans, key=lambda s: (s.start, -s.end)):
            while stack and s.start >= stack[-1].end:
                stack.pop()
            if stack and s.end <= stack[-1].end:
                stack[-1].children.append(s)
            else:
                roots.append(s)
            stack.append(s)
    return roots


def _walk(spans):
    for s in spans:
        yield s
        yield from _walk(s.children)


def _innermost(roots, starts, t) -> str:
    """The deepest span covering t, or OUTSIDE."""
    name, level, level_starts = OUTSIDE, roots, starts
    while level:
        i = bisect.bisect_right(level_starts, t) - 1
        if i < 0 or level[i].end < t:
            break
        name = level[i].name
        level = level[i].children
        level_starts = [c.start for c in level]
    return name


def _covered(iv, starts, ends, prefix, a, b) -> int:
    """Length of the union ``iv`` (sorted, disjoint; ``starts``, ``ends``
    and running ``prefix`` lengths of it) inside [a, b]."""
    i = bisect.bisect_right(ends, a)
    j = bisect.bisect_left(starts, b)
    if i >= j:
        return 0
    return (prefix[j] - prefix[i] - max(0, a - iv[i][0])
            - max(0, iv[j - 1][1] - b))


def reduce(path: str, pd, busy_planes: dict, bench_spans: list) -> Spans:
    """Read the trace at ``path``, loaded as ``pd``, inside its
    ``bench.window`` span; ``busy_planes`` and ``bench_spans`` are
    ``bench.trace.device_ops`` and ``bench.trace.host_spans`` of it."""
    lo, hi = next((s, e) for n, s, e in bench_spans if n == WINDOW_SPAN)
    return reduce_events(host_spans(pd), busy_planes,
                         device_scoped_ops(busy_planes, op_names(path)),
                         lo, hi)


def reduce_events(threads: list, busy_planes: dict, scoped: dict,
                  lo: int, hi: int) -> Spans:
    """``threads``: [[Span]] per host thread; ``busy_planes``: {device plane:
    [bench.trace.Op]}, every operation; ``scoped``: {device plane: [(start,
    end, scope or None)]}, the operations outside Pallas kernels; all on
    the trace's clock (ns), read inside the window [lo, hi]."""
    threads = [[Span(s.name, s.start, s.end, s.args, []) for s in spans
                if s.start >= lo and s.end <= hi] for spans in threads]
    roots = nest(threads)
    roots.sort(key=lambda s: s.start)
    starts = [s.start for s in roots]
    ticks = [s for s in roots if s.name == STEP]

    per = defaultdict(lambda: [0, 0, 0])
    for s in _walk(roots):
        p = per[s.name]
        p[0] += 1
        p[1] += s.length
        p[2] += s.self_ns
    ns = 1e-9
    per_span = {k: {"count": c, "total_s": t * ns, "self_s": own * ns}
                for k, (c, t, own) in per.items()}

    n = max(len(busy_planes), 1)
    idle = defaultdict(int)
    decode_idle = 0
    tick_idle = defaultdict(int)
    for ops in busy_planes.values():
        iv = union(_clip([(o.start, o.end) for o in ops], lo, hi))
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_innermost(roots, starts, (a + b) // 2)] += b - a
        prefix = [0]
        for a, b in iv:
            prefix.append(prefix[-1] + b - a)
        iv_starts, iv_ends = [a for a, _ in iv], [b for _, b in iv]
        for t in ticks:
            gap = t.length - _covered(iv, iv_starts, iv_ends, prefix,
                                      t.start, t.end)
            tick_idle[id(t)] += gap
            if t.args.get("kind") == "decode":
                decode_idle += gap

    scopes = defaultdict(int)
    any_scope = False
    for ops in scoped.values():
        by = defaultdict(list)
        for a, b, scope in ops:
            any_scope |= scope is not None
            by[scope or UNSCOPED].append((a, b))
        for k, v in by.items():
            scopes[k] += _length(union(_clip(v, lo, hi)))

    longest = max(ticks, key=lambda t: t.length, default=None)
    return Spans(
        per_span=per_span,
        idle_by_span=[[k, v / n * ns] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])],
        decode_ticks=sum(t.args.get("kind") == "decode" for t in ticks),
        decode_idle_s=decode_idle / n * ns,
        scopes={k: v / max(len(scoped), 1) * ns for k, v in
                sorted(scopes.items(), key=lambda kv: -kv[1])},
        scoped=any_scope,
        longest_tick=_split(longest, tick_idle, n) if longest else None)


def _split(tick: Span, tick_idle: dict, n: int) -> dict:
    """A tick's length, device idle and the self time of each span in it,
    the tick's own included (ms)."""
    own = defaultdict(int)
    for s in _walk([tick]):
        own[s.name] += s.self_ns
    ms = 1e-6
    return {"tick": tick.args.get("tick"), "kind": tick.args.get("kind"),
            "ms": tick.length * ms, "idle_ms": tick_idle[id(tick)] / n * ms,
            "self_ms": [[k, v * ms] for k, v in
                        sorted(own.items(), key=lambda kv: -kv[1])]}


def main(argv=None) -> dict:
    import argparse
    import json
    import shutil
    import tempfile
    from collections import deque

    ap = argparse.ArgumentParser(
        description="Serve one window of a benchmark cell under the "
                    "profiler and print its spans and scopes.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke preset, interpret-mode kernels")
    args = ap.parse_args(argv)

    from bench import run  # puts the program on the path
    import jax
    from bench import program, traffic, window
    from bench import trace as tracing

    c = run.prepare(args.workload, rehearse=args.rehearse, control=None)
    weights = program.make_weights(c.sizes, args.seed)
    sched, _ = program.scheduler(
        c.cfg, weights, c.mix,
        kernel_backend="pallas-interpret" if c.rehearse else "pallas-tpu")
    window.warm_up(sched, c.mix, c.cfg.vocab_size)
    pending = deque(traffic.requests(c.mix, c.cfg.vocab_size, args.seed,
                                     run.REQUESTS))
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            win = window.measure(sched, c.mix, pending, args.seconds,
                                 annotate=jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        path = tracing.find_xplane(trace_dir)
        pd = tracing.load(path)
        ops, marks = tracing.device_ops(pd), tracing.host_spans(pd)
        s = reduce(path, pd, ops, marks)
        busy = tracing.reduce_events(ops, marks).busy_s if ops else 0.0
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"ticks": len(win.ticks), "busy_s": busy,
           "decode_idle_ms": s.decode_idle_ms() if ops else None,
           "decode_dispatch_ms": s.mean_ms("sched.decode_dispatch"),
           "approx_pct": s.scope_pct("approx.", busy),
           "kv_cache_pct": s.scope_pct("model.kv_cache", busy),
           "per_span": s.per_span, "idle_by_span": s.idle_by_span,
           "device_by_scope": s.scopes, "longest_tick": s.longest_tick}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
