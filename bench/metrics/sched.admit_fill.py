"""Requests admitted over (admitting ticks x batch): how much of each
full-batch prefill serves a request."""


def read(run):
    ticks = [t for t in run.window.ticks if t.admitted]
    if not ticks:
        return None
    return 100.0 * sum(t.admitted for t in ticks) / (len(ticks)
                                                     * run.mix.batch)
