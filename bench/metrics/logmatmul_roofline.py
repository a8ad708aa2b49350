"""The emulated matmuls' least time (bench/work.py: products at the int8
peak, 1-byte operand codes and bf16 outputs over HBM) over the device time
of the ``logmatmul_pallas`` kernel that runs them. The trace names a Pallas
kernel by its wrapper function: a traced run whose Pallas kernels hold none
of that name fails, so that a rename shows instead of the metric going
silent."""
from bench import work

KERNEL = "logmatmul_pallas"


def read(run):
    t = run.trace
    if t is None or run.peak is None:
        return None
    if not t.kernels.get(KERNEL):
        if t.kernels:
            raise RuntimeError(
                f"no Pallas kernel named {KERNEL!r} in the trace (found "
                f"{sorted(t.kernels)}): the emulated matmul's kernel was "
                "renamed or moved; name it here")
        return None
    s = work.sizes(run.sizes)
    least = work.linear_least_seconds(s, run.window, run.mix, run.peak)
    return 100.0 * least / t.kernels[KERNEL]
