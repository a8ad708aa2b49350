"""Model flops of the window's useful tokens (admitted prompts and decoded
tokens, no padding rows) over window length x the chip's bf16 peak."""
from bench import work


def read(run):
    if run.peak is None:
        return None
    s = work.sizes(run.sizes)
    flops = work.useful_flops(s, run.window, run.mix)
    return 100.0 * flops / (run.window.seconds * run.peak["bf16_flops_s"])
