#!/usr/bin/env python3
"""Chip smoke test: the SIMDive serving path on one TPU, end to end.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # the sharded training path only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--four-chips]

One process runs every phase in order and exits 0 only when all pass:

  (a) device   — platform, device kind and count; no TPU is a failure.
  (b) kernels  — every registry op on seeded operands at the serve path's
                 shapes, compiled Pallas (``pallas-tpu``) against the
                 bit-exact reference: elemwise / packed / matmul_emul
                 bit for bit, attention within rtol = atol = 1e-3 on
                 f32 (plus one bf16 rounding step on bf16).
  (c) serving  — smollm-360m at its published widths (32 layers, 960
                 wide, GQA 15/5, d_head 64, vocab 49152) with
                 ``--approx simdive --emulate`` semantics and seeded
                 weights: the serving plan must resolve every approximate
                 op to ``pallas-tpu``; ``measure_generate`` once; a
                 scheduler drill that admits and completes a few
                 requests; and the same prefill plus one decode step on
                 the reference backend, whose logits must agree within
                 ``LOGIT_REL_TOL``.
  (d) result   — the last line of stdout is one JSON object,
                 ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the sharded path: ``train()`` at smollm-360m's
full width with ``--tp 2`` on the 2x2 mesh and ``--approx simdive`` for
three steps, and the same three steps unsharded on one of the four
devices; per-step losses must agree within ``LOSS_REL_TOL`` and the
parameters must be spread over all four devices.

``--rehearse`` runs the same phases on the CPU at the smoke size, with
the kernels in interpret mode (four virtual devices for --four-chips:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). A rehearsal is
not a chip result and prints no result line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: serve-path logits, compiled kernels vs reference on the same weights:
#: max |delta| / max |reference| (see CHANGES.md for the reasoning)
LOGIT_REL_TOL = 0.05
#: attention kernel vs reference on f32, as tests/test_flash_kernel.py
#: pins it (bf16 outputs get one bf16 rounding step on top)
ATT_TOL = 1e-3
#: per-step training loss, sharded (2x2 mesh) vs one device
LOSS_REL_TOL = 5e-3


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class Phases:
    """Run named phases in order; a caught failure still fails the run."""

    def __init__(self):
        self.failed: list[str] = []

    def run(self, name: str, fn, *args):
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            fn(*args)
        except Exception as e:                 # noqa: BLE001 - reported
            traceback.print_exc()
            self.failed.append(f"{name}: {type(e).__name__}: {e}")
            print(f"== {name}: FAILED", flush=True)
            return
        print(f"== {name}: ok ({time.perf_counter() - t0:.1f} s)",
              flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------- (b) kernels --
def kernel_parity(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.simdive import SimdiveSpec
    from repro.kernels import get_op

    rng = np.random.default_rng(ctx.seed)
    small = ctx.rehearse
    w8 = SimdiveSpec(width=8, coeff_bits=6, index_bits=3)
    w16 = SimdiveSpec(width=16, coeff_bits=8, index_bits=3)

    def lanes(shape, width):
        return jnp.asarray(rng.integers(0, 1 << width, shape,
                                        dtype=np.int64).astype(np.uint32))

    def same(name, kind, spec, *args, **kw):
        got = get_op(kind, spec, ctx.kernel_backend)(*args, **kw)
        want = get_op(kind, spec, "ref")(*args, **kw)
        got, want = np.asarray(got), np.asarray(want)
        _check(got.shape == want.shape, f"{name}: shape {got.shape} != "
                                        f"{want.shape}")
        bad = int(np.sum(got.astype(np.int64) != want.astype(np.int64)))
        print(f"  {name:<34} {got.shape} bit-exact mismatches={bad}")
        _check(bad == 0, f"{name}: {bad} elements differ from ref")

    # elemwise: the generic tile, and the decode-step attention divider's
    # (batch, kv_heads, groups, d_head) operand
    shp = (16, 96) if small else (1024, 960)
    dec = (2, 1, 3, 32) if small else (8, 5, 3, 64)
    for spec, fo in ((w8, 8), (w16, 15)):
        a, b = lanes(shp, spec.width), lanes(shp, spec.width)
        mode = lanes(shp, 1)
        w = spec.width
        same(f"elemwise w{w} mul", "elemwise", spec, a, b, op="mul")
        same(f"elemwise w{w} div fo{fo}", "elemwise", spec, a, b,
             op="div", frac_out=fo)
        same(f"elemwise w{w} mixed fo8", "elemwise", spec, a, b,
             op="mixed", mode=mode, frac_out=8)
    same("elemwise w16 div (decode operand)", "elemwise", w16,
         lanes(dec, 16), lanes(dec, 16), op="div", frac_out=15)
    # packed: 4 x 8-bit lanes per word, one model row of lanes per word row
    words = (16, 24) if small else (1024, 240)
    aw, bw = lanes(words, 32), lanes(words, 32)
    same("packed w8 mul", "packed", w8, aw, bw, op="mul")
    same("packed w8 div fo8", "packed", w8, aw, bw, op="div", frac_out=8)
    same("packed w8 mixed fo8", "packed", w8, aw, bw, op="mixed",
         mode=lanes(words, 32), frac_out=8)
    # matmul_emul: the model's approximate linear at prefill and decode M
    K, N = (96, 256) if small else (960, 2560)
    for M in ((32, 2) if small else (1024, 8)):
        qx, qw = lanes((M, K), 8), lanes((K, N), 8)
        sx = jnp.asarray(rng.choice([-1, 1], (M, K)).astype(np.int32))
        sw = jnp.asarray(rng.choice([-1, 1], (K, N)).astype(np.int32))
        same(f"matmul_emul w8 M{M}", "matmul_emul", w8, qx, sx, qw, sw)
    # attention: prefill heads (batch x query heads) at d_head 64. The
    # 1e-3 band is the one tests/test_flash_kernel.py holds ref vs
    # interpret to, on f32 operands: here too, with both sides multiplying
    # at full f32 precision (a TPU's default f32 matmul is one bf16 pass).
    # At the serve path's bf16 the output's own rounding step (2^-9 for
    # magnitudes in [0.25, 0.5)) is wider than the band, so bf16 outputs
    # are held to the band plus one bf16 step of the reference value.
    BH, S, dh = (6, 16, 32) if small else (120, 128, 64)
    kw = dict(causal=True, approx_div=True, frac_out=15)
    for dtype, prec, steps in ((jnp.float32, "highest", 0),
                               (jnp.bfloat16, None, 1)):
        q, k, v = (jnp.asarray(rng.standard_normal((BH, S, dh)), dtype)
                   for _ in range(3))
        with (jax.default_matmul_precision(prec) if prec
              else contextlib.nullcontext()):
            got = np.asarray(get_op("attention", w16, ctx.kernel_backend)(
                q, k, v, **kw), np.float32)
            want = np.asarray(get_op("attention", w16, "ref")(q, k, v,
                                                               **kw),
                              np.float32)
        delta = np.abs(got - want)
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 2.0**-126)))
                      - 7)                    # bf16: 7 stored mantissa bits
        bound = ATT_TOL + ATT_TOL * np.abs(want) + steps * ulp
        name = f"attention w16 dh{dh} {np.dtype(dtype).name}"
        print(f"  {name:<34} {got.shape} max|delta|={delta.max():.3g} "
              f"({(delta / ulp).max():.3g} bf16 steps) outside "
              f"band={int(np.sum(delta > bound))}")
        _check(np.all(delta <= bound),
               f"{name}: max |delta| {delta.max():.3g} outside rtol=atol="
               f"{ATT_TOL}" + (f" + {steps} bf16 step" if steps else ""))


# ------------------------------------------------------------- (c) serving --
def serving(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.scheduler import Scheduler, ServeLevel
    from repro.launch.serve import (generate, measure_generate, merge_cache,
                                    render_plan, resolve_serving_plan,
                                    serving_config)
    from repro.models import build

    label = ctx.label
    base = get_config("smollm-360m", smoke=ctx.rehearse)
    B, P, G = (2, 16, 4) if ctx.rehearse else (8, 128, 16)
    cfg = serving_config(base, "simdive", emulate=True,
                         backend=ctx.serve_backend)
    plan = resolve_serving_plan(cfg)
    print(render_plan(plan, cfg))
    wrong = [r.label() for r in plan if r.backend != ctx.kernel_backend]
    _check(plan and not wrong,
           f"serving plan does not resolve every op to "
           f"{ctx.kernel_backend}: {wrong}")
    print(f"  model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_head "
          f"{cfg.d_head}, vocab {cfg.vocab_size}; batch {B}, prompt {P}, "
          f"{G} generated tokens")

    lm = build(cfg)
    params = lm.init(jax.random.PRNGKey(ctx.seed))
    rng = np.random.default_rng(ctx.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P),
                                       dtype=np.int32))
    max_seq = P + G

    t0 = time.perf_counter()
    jax.block_until_ready(generate(lm, params, prompts, max_seq, G))
    warm = time.perf_counter() - t0
    toks, e2e, step = measure_generate(lm, params, prompts, max_seq, G,
                                       iters=2)
    toks = np.asarray(toks)
    _check(toks.shape == (B, G) and toks.min() >= 0
           and toks.max() < cfg.vocab_size, f"bad tokens {toks.shape}")
    print(f"  [{label}] compile + warm-up (first generate): {warm:.2f} s")
    print(f"  [{label}] decode step: {step.best_s * 1e3:.3f} ms best of "
          f"{step.iters}")
    print(f"  [{label}] generate: {B * G / e2e.best_s:.1f} tok/s end to "
          f"end (prefill included), {step.items_per_s:.1f} tok/s decode "
          f"steady state")

    # scheduler drill: more requests than slots, so some queue
    n_req = B + 2
    sched = Scheduler(cfg, params=params,
                      levels=(ServeLevel("fine", cfg.approx),), batch=B,
                      prompt_len=P, max_seq=max_seq, self_heal=False)
    t0 = time.perf_counter()
    sched.warmup()
    for _ in range(n_req):
        sched.submit(rng.integers(0, cfg.vocab_size, P, dtype=np.int32),
                     max_new=G)
    stats = sched.run()
    print(f"  [{label}] scheduler: {stats['completed']}/{n_req} requests, "
          f"{stats['tokens']} tokens in {stats['ticks']} ticks "
          f"({time.perf_counter() - t0:.2f} s incl. warm-up)")
    _check(stats["completed"] == n_req and stats["failed"] == 0,
           f"scheduler completed {stats['completed']}/{n_req}, "
           f"failed {stats['failed']}")

    # the same prefill + one decode step on the reference backend
    lm_ref = build(serving_config(base, "simdive", emulate=True,
                                  backend="ref"))

    def prefill_decode(m):
        logits, cache = m.prefill(params, {"tokens": prompts})
        cache = merge_cache(m.empty_cache(B, max_seq), cache)
        return logits, cache

    lk, ck = prefill_decode(lm)
    lr, cr = prefill_decode(lm_ref)
    tok = jnp.argmax(lr, -1).astype(jnp.int32)
    lk2, _ = lm.decode_step(params, ck, tok, jnp.int32(P))
    lr2, _ = lm_ref.decode_step(params, cr, tok, jnp.int32(P))
    worst = 0.0
    for name, k_, r_ in (("prefill", lk, lr), ("decode", lk2, lr2)):
        k_ = np.asarray(k_, np.float32)
        r_ = np.asarray(r_, np.float32)
        _check(np.isfinite(k_).all(), f"{name}: non-finite kernel logits")
        d = float(np.max(np.abs(k_ - r_)))
        rel = d / max(float(np.max(np.abs(r_))), 1e-30)
        agree = float(np.mean(k_.argmax(-1) == r_.argmax(-1)))
        worst = max(worst, rel)
        print(f"  [{label}] {name} logits vs ref: max|delta|={d:.4g} "
              f"relative={rel:.4g} argmax agreement={agree:.3f}")
    _check(worst <= LOGIT_REL_TOL,
           f"logits differ from ref by {worst:.4g} > {LOGIT_REL_TOL}")
    stats_fn = getattr(jax.devices()[0], "memory_stats", None)
    mem = stats_fn() if stats_fn else None
    peak = (mem or {}).get("peak_bytes_in_use")
    print(f"  [{label}] peak HBM: "
          + (f"{peak / 2**30:.3f} GiB" if peak else "not reported"))


# -------------------------------------------------------- (4) four chips --
def four_chips(ctx):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.approx import ApproxConfig
    from repro.launch.train import train

    devs = jax.devices()
    _check(len(devs) >= 4, f"--four-chips needs 4 devices, found "
                           f"{len(devs)}")
    devs = devs[:4]
    cfg = get_config("smollm-360m", smoke=ctx.rehearse).with_approx(
        ApproxConfig(mode="simdive"))
    B, S = (8, 32) if ctx.rehearse else (8, 64)
    shape = ShapeConfig("chip-smoke", S, B, "train")
    kw = dict(steps=3, ckpt_dir=None, seed=ctx.seed, log_every=1)
    print(f"  model {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}; batch {B} x seq {S}, 3 steps, approx simdive")
    t0 = time.perf_counter()
    params, sharded = train(cfg, shape, tp=2, devices=devs, **kw)
    print(f"  [{ctx.label}] 2x2 mesh (tp 2): {time.perf_counter() - t0:.1f}"
          f" s incl. compile")
    held, split = set(), 0
    for leaf in jax.tree.leaves(params):
        for s in leaf.addressable_shards:
            held.add(s.device)
        split += any(s.data.shape != leaf.shape
                     for s in leaf.addressable_shards)
    print(f"  parameters on {len(held)} device(s); {split} leaves split "
          f"across devices")
    _check(held == set(devs) and split > 0,
           "parameters are not spread over all four devices")
    t0 = time.perf_counter()
    _, single = train(cfg, shape, tp=1, devices=devs[:1], **kw)
    print(f"  [{ctx.label}] one device: {time.perf_counter() - t0:.1f} s "
          f"incl. compile")
    rel = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(sharded, single)]
    for i, (a, b, r) in enumerate(zip(sharded, single, rel)):
        print(f"  step {i}: loss sharded {a:.6f} one-device {b:.6f} "
              f"relative delta {r:.3g}")
    _check(len(sharded) == len(single) == 3 and np.all(np.isfinite(sharded))
           and max(rel) <= LOSS_REL_TOL,
           f"sharded vs one-device losses differ by {max(rel):.3g} > "
           f"{LOSS_REL_TOL}")


# --------------------------------------------------------------------- cli --
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the smoke size with interpret-"
                         "mode kernels; prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        _fail(f"cannot import the program ({e}); run chip_smoke.py from "
              "the root of the repository")
    use_compile_cache()

    # (a) device
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if dev.platform != "tpu" and not args.rehearse:
        _fail(f"no TPU found (JAX platform {dev.platform!r})")

    args.label = f"{dev.platform}:{dev.device_kind}"
    # on the chip 'auto' must resolve to the compiled kernels; the
    # rehearsal asks for 'pallas', which resolves to interpret mode
    args.serve_backend = "pallas" if args.rehearse else "auto"
    args.kernel_backend = "pallas-interpret" if args.rehearse \
        else "pallas-tpu"

    phases = Phases()
    if args.four_chips:
        phases.run("four chips: sharded vs one-device training",
                   four_chips, args)
    else:
        phases.run("kernel parity", kernel_parity, args)
        phases.run("serving smollm-360m", serving, args)
    if phases.failed:
        _fail("; ".join(phases.failed))
    if args.rehearse:
        print("chip_smoke: rehearsal passed (CPU; not a chip result)")
        return
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
