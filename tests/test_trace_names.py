"""The names a profiler trace reads: Pallas kernel names, named scopes on
the approximate dispatch and the model, and the scheduler's host spans.

Kernels are lowered for a TPU on the CPU (no chip, nothing compiled);
the scheduler is profiled on the CPU at the smoke preset.
"""
import glob
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.approx import ApproxConfig, approx_matmul
from repro.core.simdive import SimdiveSpec
from repro.kernels import get_op
from repro.launch.scheduler import Scheduler, ServeLevel
from repro.models import build

W8 = SimdiveSpec(width=8, coeff_bits=6, index_bits=3)
W16 = SimdiveSpec(width=16, coeff_bits=8, index_bits=3)


def _tpu_text(fn, *shapes, debug_info=False):
    """``fn`` lowered for a TPU as the chip runs it: without x64, which
    the conftest turns on for the CPU datapath tests."""
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    with jax.enable_x64(False):
        low = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return low.as_text(debug_info=debug_info)


def _elemwise(a, b):
    return get_op("elemwise", W8, "pallas-tpu")(a, b, op="mul")


def _packed(a, b):
    return get_op("packed", W8, "pallas-tpu")(a, b, op="mul")


def _logmatmul(depth):
    def fn(x, w):
        return get_op("matmul_int", W8, "pallas-tpu",
                      block=(128, 128, 128, 8, depth))(x, w)
    return fn


def _attention(depth):
    def fn(q, k, v):
        return get_op("attention", W16, "pallas-tpu",
                      block=(128, 128, depth))(q, k, v, causal=True,
                                               approx_div=True)
    return fn


LANES = ((256, 256), jnp.uint32)
MAT = ((128, 256), jnp.int32)
QKV = ((2, 256, 64), jnp.bfloat16)


@pytest.mark.parametrize("name, fn, shapes", [
    ("elemwise_pallas", _elemwise, (LANES, LANES)),
    ("packed_pallas", _packed, (LANES, LANES)),
    ("logmatmul_pallas", _logmatmul(0), (MAT, ((256, 256), jnp.int32))),
    ("logmatmul_pallas", _logmatmul(2), (MAT, ((256, 256), jnp.int32))),
    ("flash_attention_pallas", _attention(0), (QKV, QKV, QKV)),
    ("flash_attention_pallas", _attention(2), (QKV, QKV, QKV)),
], ids=["elemwise", "packed", "logmatmul", "logmatmul-pipelined",
        "attention", "attention-pipelined"])
def test_pallas_kernels_carry_their_wrapper_name(name, fn, shapes):
    text = _tpu_text(fn, *shapes)
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{name}"' in text


def test_approx_matmul_dispatch_is_scoped():
    cfg = ApproxConfig(mode="simdive", emulate=True, backend="pallas-tpu")
    text = _tpu_text(lambda x, w: approx_matmul(x, w, cfg),
                     ((8, 256), jnp.bfloat16), ((256, 128), jnp.float32),
                     debug_info=True)
    assert "approx.quantize" in text and "approx.rescale" in text
    assert 'kernel_name = "logmatmul_pallas"' in text


def test_decode_step_scopes_the_embedding_cache_and_head():
    lm = build(get_config("smollm-360m", smoke=True))
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.empty_cache(2, 32))
    tok = jnp.zeros((2,), jnp.int32)
    text = lm.decode_step.trace(lm, params, cache, tok, tok).lower() \
        .as_text(debug_info=True)
    for scope in ("model.embed", "model.kv_cache", "model.head"):
        assert scope in text


def test_spans_format_nothing_without_a_profiler():
    seen = []

    class Loud:
        def __str__(self):
            seen.append(1)
            return "loud"

        __repr__ = __str__

    with jax.profiler.TraceAnnotation("sched.step", tick=Loud()):
        pass
    assert not seen


# -------------------------------------------------------------- scheduler --
def _serve(tmp_path=None):
    """A smoke-preset scheduler over 3 requests on 2 slots: admissions,
    decodes and retirements; profiled into ``tmp_path`` when given."""
    cfg = get_config("smollm-360m", smoke=True)
    approx = ApproxConfig(mode="simdive", use_in_softmax=True)
    cfg = cfg.with_approx(approx)
    sched = Scheduler(cfg, levels=(ServeLevel("fine", approx),), batch=2,
                      prompt_len=8, max_seq=16, seed=0)
    rng = np.random.default_rng(5)
    reqs = [sched.submit(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32),
                         max_new=3) for _ in range(3)]
    sched.warmup()
    if tmp_path is None:
        sched.run()
    else:
        with jax.profiler.trace(str(tmp_path)):
            sched.run()
    return sched, [r.tokens for r in reqs]


def _host_spans(tmp_path):
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sched."):
                        s = int(ev.start_ns)
                        out.append((ev.name, s, s + int(ev.duration_ns),
                                    dict(ev.stats)))
    return out


def test_scheduler_ticks_are_spans_and_tokens_unchanged(tmp_path):
    sched, traced = _serve(tmp_path)
    _, plain = _serve()
    assert traced == plain and all(len(t) == 3 for t in traced)

    spans = _host_spans(tmp_path)
    names = Counter(n for n, *_ in spans)
    steps = [s for s in spans if s[0] == "sched.step"]
    assert len(steps) == sched.tick_no == names["sched.watchdog"]
    assert [s[3]["tick"] for s in sorted(steps, key=lambda s: s[1])] == \
        list(range(1, sched.tick_no + 1))
    kinds = Counter(s[3]["kind"] for s in steps)
    assert kinds["admit"] == names["sched.admit"] == 2
    assert kinds["decode"] == sched.tick_no - 2
    for child in ("sched.prefill", "sched.admit_sync", "sched.insert"):
        assert names[child] == 2
    assert names["sched.rows_ok"] == 2 + names["sched.decode"]

    def inside(child, parent):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in spans
                   if p[0] == parent)

    dispatches = [s for s in spans if s[0] == "sched.decode_dispatch"]
    assert len(dispatches) == names["sched.decode"] == \
        names["sched.decode_sync"]
    assert all(inside(d, "sched.decode") for d in dispatches)
    assert all(inside(d, "sched.step")
               for d in spans if d[0] == "sched.decode")
