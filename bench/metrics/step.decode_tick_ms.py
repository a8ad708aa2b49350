"""Mean host-clock length of the window's step() calls that admitted
nothing (one batched decode step each)."""


def read(run):
    d = [t.t1 - t.t0 for t in run.window.ticks if not t.admitted]
    return 1e3 * sum(d) / len(d) if d else None
