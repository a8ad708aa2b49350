"""Warm-up and the measured window over ``Scheduler.step()``.

The window is an offline backlog: before every tick the generator tops the
queue up to ``batch`` requests, so no slot ever waits for work. Each token is
stamped with the host clock when the ``step()`` that produced it returns (the
step reads the tokens back to the host before it returns). The window opens
on a tick boundary after warm-up and closes on the first tick boundary after
``seconds`` at which a slot stands free, i.e. right after a retirement: every
window then ends at the same point of the admission cycle, however the ticks
fell.

Before the window opens, everything set-up left on the heap is collected and
frozen (``gc.freeze``), as a long-lived server does after its warm-up: the
collector then walks only what the window itself allocates, not the
millions of objects that tracing and compiling leave behind. Every
collection inside the window is timed and reported with the window.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field

import jax
import numpy as np

#: a window closes at the latest this long after ``seconds``
CLOSE_WAIT_S = 30.0


@dataclass
class Tick:
    t0: float
    t1: float
    admitted: int            # requests admitted by this tick's prefill
    decode_ctx: list         # context length of each token decoded
    tokens: int              # tokens delivered by this tick


@dataclass
class Served:
    req: object              # the program's Request
    times: list = field(default_factory=list)   # delivery time per token


@dataclass
class Window:
    t_open: float
    t_close: float
    ticks: list
    served: dict             # rid -> Served, every request submitted
    faults: int              # quarantines, retries, failures, guard trips
    compiles: int = 0        # backend compiles inside the window (want 0)
    gc_pauses: list = field(default_factory=list)  # [(generation, s)]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def tokens(self) -> int:
        return sum(t.tokens for t in self.ticks)

    def tick_summary(self) -> str:
        """Median, 99th percentile and longest tick of each kind, ms."""
        out = []
        for kind, ts in (("admit", [t for t in self.ticks if t.admitted]),
                         ("decode", [t for t in self.ticks
                                     if not t.admitted])):
            if ts:
                d = np.sort([1e3 * (t.t1 - t.t0) for t in ts])
                out.append(f"{kind} ticks {len(d)}: median {np.median(d):.1f}"
                           f" p99 {np.percentile(d, 99):.1f} max {d[-1]:.1f}"
                           f" ms")
        p = [s for _, s in self.gc_pauses]
        out.append(f"gc collections {len(p)}, longest "
                   f"{1e3 * max(p, default=0.0):.1f} ms, total "
                   f"{1e3 * sum(p):.1f} ms")
        return "; ".join(out)

    def finished(self) -> list:
        return [s for s in self.served.values()
                if s.req.finished >= 0 and not s.req.failed
                and len(s.times) == len(s.req.tokens)]


def warm_up(sched, mix, vocab: int):
    """Compile every program the window drives: the scheduler's own warm-up,
    then one admission, decode and retirement through ``step()`` itself
    (which also compiles the host-side argmax and health checks)."""
    sched.warmup()
    rng = np.random.default_rng(0)
    for _ in range(mix.batch):
        sched.submit(rng.integers(0, vocab, mix.prompt_len, dtype=np.int32),
                     max_new=2)
    sched.run()


def measure(sched, mix, pending: deque, seconds: float,
            annotate=None) -> Window:
    """Drive ``sched.step()`` for ``seconds`` (see the module docstring).
    ``annotate(name)`` returns a context manager for a host span, or None."""
    span = annotate or (lambda name: nullcontext())
    compiles = [0]
    pauses, started = [], {}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.perf_counter()
        elif "t" in started:
            pauses.append((info["generation"],
                           time.perf_counter() - started.pop("t")))

    gc.collect()
    gc.freeze()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        win = _drive(sched, mix, pending, seconds, span, compiles)
        win.gc_pauses = pauses
        return win
    finally:
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_duration_listener(on_event)
        gc.unfreeze()


def _drive(sched, mix, pending, seconds, span, compiles) -> Window:
    served: dict = {}
    active: list = []
    ticks: list = []
    faults = 0
    P = mix.prompt_len
    ev = len(sched.events)
    t_open = time.perf_counter()
    while True:
        with span("bench.loadgen"):
            while len(sched.queue) < mix.batch:
                req = sched.submit(*pending.popleft())
                served[req.rid] = Served(req)
        admitting = bool(sched.queue) and any(s is None for s in sched.slots)
        t0 = time.perf_counter()
        with span("bench.admit_tick" if admitting else "bench.decode_tick"):
            sched.step()
        t1 = time.perf_counter()
        new = sched.events[ev:]
        ev = len(sched.events)
        admitted = [served[rid] for _, kind, rid in new
                    if kind == "admit" and rid in served]
        faults += sum(kind in ("quarantine", "retry", "fail", "guard")
                      for _, kind, _ in new)
        active += admitted
        ctx, n_tok = [], 0
        fresh = {id(s) for s in admitted}
        still = []
        for s in active:
            before = len(s.times)
            n = len(s.req.tokens) - before
            if n > 0:
                s.times += [t1] * n
                n_tok += n
                first = 2 if id(s) in fresh else before + 1
                # token j (1-based) is decoded from position P + j - 2
                ctx += [P + j - 1 for j in range(first, before + n + 1)]
            if s.req.finished < 0 and not s.req.failed:
                still.append(s)
        active = still
        ticks.append(Tick(t0, t1, len(admitted), ctx, n_tok))
        elapsed = t1 - t_open
        if elapsed >= seconds and (any(s is None for s in sched.slots)
                                   or elapsed >= seconds + CLOSE_WAIT_S):
            return Window(t_open, t1, ticks, served, faults, compiles[0])
