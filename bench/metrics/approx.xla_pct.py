"""Share of device-busy time outside Pallas kernels: quantize, rescale,
norms, rope, the exact head and the glue around the kernels."""


def read(run):
    t = run.trace
    if t is None or t.pallas_s is None or t.busy_s <= 0:
        return None
    return 100.0 * (t.busy_s - t.pallas_s) / t.busy_s
