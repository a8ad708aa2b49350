"""Benchmark driver: paper tables/figures + the machine-readable trajectory.

Usage:
  PYTHONPATH=src python benchmarks/run.py                # everything
  PYTHONPATH=src python benchmarks/run.py --quick        # CI-sized sweep
  PYTHONPATH=src python benchmarks/run.py --only table2,grid

Two outputs per run:

  results/bench.csv      the human-readable ``<table>,<row>,<values>`` CSV
                         stream (one ``main(report=...)`` per suite module,
                         unchanged format).
  BENCH_simdive.json     the machine-readable trajectory. Every invocation
                         *appends* one run record, so the file accumulates
                         the per-PR perf/accuracy history CI diffs against.

A run record's ``grid`` section is the conformance-shaped sweep: one entry
per (kernel, op, width, coeff_bits, backend) combination — ``elemwise``
mul/div, ``packed`` (all four 8-bit lanes per word, mul/div/mixed mode),
``matmul_int``/``matmul_emul`` (accumulate-level NMED vs the exact
integer matmul across a small K sweep) and ``attention``
(SIMDive-normalized flash attention vs the exact-softmax oracle over
long-context shape buckets and the model configs' GQA head layouts,
clamped for CPU cost; sampled rows, plus one interpret row pinning a
pipelined block so the double-buffered schedule owns a BENCH key) — each
carrying the full
:mod:`repro.metrics` error profile (ARE%/MRED/NMED/PRE%/WCE/error-rate
against the exact result) and a shape-bucketed throughput measurement;
everything flows through the kernel-registry ``get_op`` entry point.
Three row families measure whole subsystems rather than single kernels:
``serve`` (policy-resolved decode tok/s + exact-twin accuracy), ``fault``
(emulated-SEU containment) and ``train`` (exact-vs-approx twin training
divergence — ARE% = final-loss delta %, WCE = worst per-step |loss
delta|, NMED = 1 − min gradient cosine; w8-only, since 16-bit matmul
emulation needs x64 accumulators this driver runs without). The
``suites`` section captures each table/figure module's structured rows.

Schema: ``simdive-bench/v2`` (see :mod:`repro.metrics.trajectory`). A
config that raises mid-sweep is recorded as ``{"status": "failed", ...}``
and the sweep continues — the regression gate (``benchmarks/compare.py``)
can then distinguish a config that *broke* from one that merely wasn't
run. v1 files are migrated in place on the next append; a file that does
not parse at all is renamed aside (never silently discarded).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback

# support plain `python benchmarks/run.py` (repo root not on sys.path then)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ in (None, ""):
    sys.path.insert(0, _REPO_ROOT)

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import SimdiveSpec
from repro.core.approx import quantize_sign_magnitude
from repro.core.simd_pack import pack, unpack
from repro.kernels import get_op
from repro.kernels.registry import (
    export_autotune_cache,
    preload_autotune_cache,
)
from repro.launch.compile_cache import use_compile_cache
from repro.metrics import (
    DIV_FRAC_OUT,
    PACKED_DIV_FRAC_OUT,
    error_stats,
    grid8,
    sample_uints,
    time_callable,
)
from repro.metrics.trajectory import SCHEMA_V2, TrajectoryError, migrate_doc

SUITES = [
    # (name, module, runs-under---quick, what it reproduces)
    ("table2", "benchmarks.table2_sisd", True,
     "Table 2: SISD mul/div ARE%/PRE% vs accurate/trunc/Mitchell/MBM/INZeD"),
    ("table3", "benchmarks.table3_simd", True,
     "Table 3: SIMD packed mul-div cost profile (TPU analogue)"),
    ("table4", "benchmarks.table4_ann", False,
     "Table 4: quantized ANN inference w/ approximate multipliers"),
    ("fig1", "benchmarks.fig1_error_maps", True,
     "Fig 1: error heat maps over the fraction square"),
    ("fig34", "benchmarks.fig34_imaging", False,
     "Fig 3/4: image blending + Gaussian smoothing PSNR"),
    ("roofline", "benchmarks.roofline", False,
     "§Roofline: per (arch x shape) terms from the dry-run sweep"),
]

GRID_SEED = 0         # explicit seed: trajectory numbers must reproduce


# ------------------------------------------------------------------ grid --
def _grid_operands(op: str, width: int, n: int, exhaustive: bool):
    """Seeded operand sets; the divider uses the paper's N/8 format.

    ``b_lo=1`` pins the divisor floor explicitly: the exhaustive path
    excludes zeros via :func:`grid8`, and the sampled paths must match it
    — a single zero divisor makes the exact quotient non-finite and
    poisons the whole config's relative statistics (``error_stats`` now
    also refuses non-finite references outright).
    """
    if exhaustive and width == 8:
        return grid8()
    return sample_uints(width, n, GRID_SEED,
                        b_width=8 if op == "div" else None, b_lo=1)


#: the grid sweeps the paper's 64-region tables only; the config carries it
#: explicitly because it is part of the gate key (a failed record must
#: still know the full identity of what it *tried* to measure)
GRID_INDEX_BITS = 3


def _grid_configs(quick: bool):
    """The (kernel, op, width, coeff_bits, backend) sweep of one run."""
    coeff_sweep = (0, 4, 6) if quick else (0, 2, 4, 6, 8)
    common = dict(index_bits=GRID_INDEX_BITS)
    for width in (8, 16):
        for op in ("mul", "div"):
            for cb in coeff_sweep:
                yield dict(kernel="elemwise", op=op, width=width,
                           coeff_bits=cb, backend="ref", **common)
    # the interpreter path is a correctness artifact, not a speed one:
    # keep it to the paper's headline config so runs stay bounded
    for op in ("mul", "div"):
        yield dict(kernel="elemwise", op=op, width=8, coeff_bits=6,
                   backend="pallas-interpret", **common)
    # packed: all four 8-bit lanes of every word at once, incl. the paper's
    # §3.2 mixed functionality (per-lane mul/div select)
    for op in ("mul", "div", "mixed"):
        for cb in ((6,) if quick else (0, 6)):
            yield dict(kernel="packed", op=op, width=8, coeff_bits=cb,
                       backend="ref", **common)
    yield dict(kernel="packed", op="mul", width=8, coeff_bits=6,
               backend="pallas-interpret", **common)
    # matmul: accumulate-level error vs the exact integer matmul across a
    # small K sweep (NMED is the headline — cancellation makes per-output
    # relative error meaningless near zero sums)
    for k in ((32, 128) if quick else (32, 128, 512)):
        yield dict(kernel="matmul_int", op="matmul", width=8, coeff_bits=6,
                   backend="ref", k=k, **common)
    yield dict(kernel="matmul_emul", op="matmul", width=8, coeff_bits=6,
               backend="ref", k=128, **common)
    yield dict(kernel="matmul_int", op="matmul", width=8, coeff_bits=6,
               backend="pallas-interpret", k=32, **common)
    # attention: SIMDive-normalized flash attention vs the exact-softmax
    # oracle, over long-context Sq buckets and the model configs' GQA head
    # layouts (clamped — see _attention_layout — so the CPU oracle stays
    # bounded). Sq 8192 is full-run-only; all rows are sampled (the gate's
    # 2% rtol class). The divider config is the attention default: width
    # 16, 8 coefficient bits, quotients at 15 fractional bits.
    for sq in ((512, 2048) if quick else (512, 2048, 8192)):
        yield dict(kernel="attention", op="attention", width=16,
                   coeff_bits=8, backend="ref", arch="qwen3-4b", sq=sq,
                   **common)
    # a sliding-window GQA layout (Mixtral) exercises the masked sweep;
    # Sq 1024 keeps its gate key (shape-bucketed) distinct from the
    # qwen3 rows — the window is not part of the key identity
    yield dict(kernel="attention", op="attention", width=16, coeff_bits=8,
               backend="ref", arch="mixtral-8x7b", sq=1024, **common)
    # one interpret row with a pinned pipelined block: the double-buffered
    # kv schedule gets a BENCH key of its own (parity + trend, not speed)
    # simdive-lint: allow(hardcoded-block): deliberately pinned pipelined row
    yield dict(kernel="attention", op="attention", width=16, coeff_bits=8,
               backend="pallas-interpret", arch="qwen3-4b", sq=512,
               block=(256, 256, 2), **common)
    # serving: the end-to-end policy-resolved decode path (launch/serve.py)
    # at smoke smollm-360m shapes. Each row ships a one-entry attention
    # policy (the simdive-policy/v1 resolution serving actually uses),
    # measures the steady-state jitted decode step post-warmup with device
    # sync, and scores tokens/logits against the exact-mode twin — the
    # tok/s-vs-accuracy serving family the gate diffs per PR. Runs under
    # --quick too (the model is smoke-sized).
    for width, cb in ((16, 8), (16, 0), (8, 6)):
        yield dict(kernel="serve", op="serve", width=width, coeff_bits=cb,
                   backend="ref", arch="smollm-360m", batch=4, prompt=32,
                   gen=8, **common)
    # training: the approx-in-the-loop divergence family (repro.train) —
    # a 20-step smollm-360m smoke trains exact and approximate twins on a
    # bitwise-identical batch sequence and gates the divergence summary:
    # ARE% carries the final-loss delta (%), WCE the worst per-step loss
    # delta, NMED the worst gradient *mis*alignment (1 - min grad
    # cosine). 'train-bwd' additionally emulates approximate backward
    # matmuls (ApproxConfig(backward='approx')) — a distinct op name
    # because backward mode is not part of the gate key. All sampled
    # class; width stays at 8-bit lanes (the 16-bit matmul emulation
    # needs x64, which this driver does not enable).
    for op, cb, bwd in (("train", 6, "exact"), ("train", 4, "exact"),
                        ("train-bwd", 6, "approx")):
        yield dict(kernel="train", op=op, width=8, coeff_bits=cb,
                   backend="ref", arch="smollm-360m", batch=8, seq=128,
                   steps=20, backward=bwd, **common)
    # fault: the SEU resilience family (repro.faults.campaign) — per-site
    # error amplification of the elemwise datapath under the deterministic
    # default site set, plus guard/scrub detectability counts. Fully
    # deterministic end to end (fixed operand sets, seeded hash-pattern
    # transient strikes), so the w8 rows gate in the exhaustive class;
    # the gate catches fault *containment* regressing — a datapath change
    # that lets the same upset corrupt more, or corrupt harder
    for op in ("mul", "div"):
        yield dict(kernel="fault", op=op, width=8, coeff_bits=6,
                   backend="ref", **common)
        if not quick:
            yield dict(kernel="fault", op=op, width=16, coeff_bits=8,
                       backend="ref", **common)


def _cfg_geometry(cfg: dict, quick: bool) -> dict:
    """Sweep sizes + *timed operand shapes* of one config.

    Shared by the runners and the per-config failure path: the gate keys
    entries on (config, shape-bucket), so a failed record must land on the
    same key as its healthy baseline twin even though it never timed
    anything — its buckets come from here, not from a measurement.
    """
    from repro.kernels.registry import shape_bucket

    interp = cfg["backend"] == "pallas-interpret"
    if cfg["kernel"] == "elemwise":
        exhaustive = cfg["width"] == 8 and not interp
        # sampled size is the same under --quick and full (the ref sweep is
        # vectorized and cheap): a quick run must land on the committed
        # full baseline's gate keys or the 16-bit sweep is never gated
        n = 4096 if interp else (65025 if exhaustive else 250_000)
        shapes = ((n,), (n,))
        g = {"exhaustive": exhaustive, "n": n}
    elif cfg["kernel"] == "packed":
        # same size under --quick and full: the packed ref sweep is cheap,
        # and identical shapes keep the quick run's gate keys colliding
        # with a full committed baseline
        n = 4096 if interp else 16_384                         # total lanes
        rows = 16 if interp else 64
        words = n // (rows * (32 // cfg["width"]))
        shapes = ((rows, words), (rows, words))
        g = {"n": n, "rows": rows}
    elif cfg["kernel"] == "attention":
        lay = _attention_layout(cfg["arch"], cfg["sq"])
        shapes = ((lay["bh"], cfg["sq"], lay["dh"]),) * 3
        g = {**lay, "sq": cfg["sq"]}
    elif cfg["kernel"] == "serve":
        # the serving row keys on its (batch, prompt) geometry; the timed
        # callable is a closure (no array operands), so the declared
        # buckets are stamped onto the measurement explicitly
        shapes = ((cfg["batch"], cfg["prompt"]),)
        g = {"batch": cfg["batch"], "prompt": cfg["prompt"],
             "gen": cfg["gen"]}
    elif cfg["kernel"] == "train":
        # the twin-run row keys on its (batch, seq) geometry like serve;
        # the timed callable is the jitted approximate train step
        shapes = ((cfg["batch"], cfg["seq"]),)
        g = {"batch": cfg["batch"], "seq": cfg["seq"],
             "steps": cfg["steps"]}
    elif cfg["kernel"] == "fault":
        # same operand sets as the elemwise family: the w8 rows sweep the
        # exhaustive grid, w16 the fixed-seed sample (fault rows never
        # time anything, so the key's buckets are always declared here)
        exhaustive = cfg["width"] == 8
        n = 65025 if exhaustive else 65536
        shapes = ((n,), (n,))
        g = {"exhaustive": exhaustive, "n": n}
    else:                                  # matmul_int / matmul_emul
        m = 32 if interp else 64
        shapes = ((m, cfg["k"]), (cfg["k"], m))
        g = {"m": m}
    g["shape_buckets"] = [list(shape_bucket(s)) for s in shapes]
    return g


def _attention_layout(arch: str, sq: int) -> dict:
    """The model config's GQA head layout, clamped for the CPU oracle.

    Head *counts* are capped (2 kv heads x 2 query groups, d_head 32) —
    the grid measures the divider's composition into attention, not the
    model's full head fan-out, and the exact-softmax reference is
    O(BH * Sq^2). The GQA *structure* (grouped kv, sliding window) is
    preserved; a window wider than the row is clamped to Sq/2 so the
    masked sweep is actually exercised."""
    from repro.configs import get_config

    cfg = get_config(arch)
    kvh = max(1, min(cfg.n_kv_heads, 2))
    groups = max(1, min(cfg.n_heads // max(cfg.n_kv_heads, 1), 2))
    window = min(cfg.sliding_window, sq // 2) if cfg.sliding_window else 0
    return {"bh": kvh * groups, "dh": min(cfg.d_head, 32),
            "kv_heads": kvh, "q_groups": groups, "window": window}


def _measure(call, *args, interp: bool, items: int):
    # 9 iters on the compiled paths: best-of-N is the gated statistic and
    # shared-runner noise needs a few more draws to converge; interpreter
    # wall-clock is a correctness artifact, one sample is plenty
    timed = jax.jit(call) if not interp else call
    return time_callable(timed, *args, iters=1 if interp else 9,
                         items=items)


def _run_elemwise(cfg: dict, quick: bool) -> dict:
    op, width, cb = cfg["op"], cfg["width"], cfg["coeff_bits"]
    spec = SimdiveSpec(width=width, coeff_bits=cb,
                       index_bits=cfg["index_bits"])
    interp = cfg["backend"] == "pallas-interpret"
    geo = _cfg_geometry(cfg, quick)
    exhaustive, n = geo["exhaustive"], geo["n"]
    a_np, b_np = _grid_operands(op, width, n, exhaustive)
    a, b = jnp.asarray(a_np), jnp.asarray(b_np)
    kw = {"op": op} if op == "mul" else {"op": op, "frac_out": DIV_FRAC_OUT}
    # block=None on every backend: dispatch goes through the registry's
    # block picker, so the sweep populates the autotune cache the run
    # record exports (off-TPU that caches the registered default without
    # timing; a TPU host records timed winners)
    bound = get_op("elemwise", spec, cfg["backend"])
    call = (lambda x, y, _b=bound, _kw=kw: _b(x, y, **_kw))
    out = np.asarray(call(a, b)).astype(np.float64)
    if op == "mul":
        true = a_np.astype(np.float64) * b_np.astype(np.float64)
    else:
        out = out / 2.0 ** DIV_FRAC_OUT
        true = a_np.astype(np.float64) / b_np.astype(np.float64)
    err = error_stats(out, true)
    t = _measure(call, a, b, interp=interp, items=int(a.size))
    return {
        "n": int(a.size), "seed": GRID_SEED,
        "exhaustive": bool(exhaustive),
        "frac_out": 0 if op == "mul" else DIV_FRAC_OUT,
        "error": err.as_dict(), "throughput": t.as_dict(),
    }


def _run_packed(cfg: dict, quick: bool) -> dict:
    """All four 8-bit lanes per uint32 word, through the packed kernel."""
    op, width, cb = cfg["op"], cfg["width"], cfg["coeff_bits"]
    spec = SimdiveSpec(width=width, coeff_bits=cb,
                       index_bits=cfg["index_bits"])
    interp = cfg["backend"] == "pallas-interpret"
    lpw = 32 // width
    geo = _cfg_geometry(cfg, quick)
    n, rows = geo["n"], geo["rows"]
    a_np, b_np = sample_uints(width, n, GRID_SEED, b_lo=1)
    a_l = jnp.asarray(a_np.reshape(rows, -1))
    b_l = jnp.asarray(b_np.reshape(rows, -1))
    aw, bw = pack(a_l, width), pack(b_l, width)
    kw: dict = {"op": op}
    mode_np = None
    if op != "mul":
        kw["frac_out"] = PACKED_DIV_FRAC_OUT
    if op == "mixed":
        mode_np = np.random.default_rng(GRID_SEED + 1).integers(
            0, 2, a_l.shape).astype(np.uint32)
        kw["mode"] = pack(jnp.asarray(mode_np), width)
    bound = get_op("packed", spec, cfg["backend"])   # block: registry picks
    call = (lambda x, y, _b=bound, _kw=kw: _b(x, y, **_kw))
    lanes = np.asarray(unpack(jnp.asarray(call(aw, bw)), 2 * width)
                       ).astype(np.float64)
    af = a_np.reshape(rows, -1).astype(np.float64)
    bf = b_np.reshape(rows, -1).astype(np.float64)
    scale = 2.0 ** PACKED_DIV_FRAC_OUT
    if op == "mul":
        out, true = lanes, af * bf
    elif op == "div":
        out, true = lanes / scale, af / bf
    else:   # mixed: product lanes at integer scale, quotients at 2^frac
        sel = mode_np.astype(bool)
        out = np.where(sel, lanes, lanes / scale)
        true = np.where(sel, af * bf, af / bf)
    err = error_stats(out, true)
    t = _measure(call, aw, bw, interp=interp, items=n)
    return {
        "n": n, "seed": GRID_SEED,
        "exhaustive": False, "lanes_per_word": lpw,
        "frac_out": 0 if op == "mul" else PACKED_DIV_FRAC_OUT,
        "error": err.as_dict(), "throughput": t.as_dict(),
    }


def _run_matmul(cfg: dict, quick: bool) -> dict:
    """Accumulate-level error of the matmul kernels vs exact int matmul."""
    kernel, width, cb, k = (cfg["kernel"], cfg["width"], cfg["coeff_bits"],
                            cfg["k"])
    spec = SimdiveSpec(width=width, coeff_bits=cb,
                       index_bits=cfg["index_bits"])
    interp = cfg["backend"] == "pallas-interpret"
    m = n_out = _cfg_geometry(cfg, quick)["m"]
    rng = np.random.default_rng(GRID_SEED + 2)
    bound = get_op(kernel, spec, cfg["backend"])     # block: registry picks
    if kernel == "matmul_int":
        hi = (1 << width) - 1
        x = jnp.asarray(rng.integers(-hi, hi + 1, (m, k), dtype=np.int32))
        w = jnp.asarray(rng.integers(-hi, hi + 1, (k, n_out),
                                     dtype=np.int32))
        call = (lambda xx, ww, _b=bound: _b(xx, ww))
        exact = (np.asarray(x, np.int64) @ np.asarray(w, np.int64))
        args = (x, w)
    else:   # matmul_emul: the model-facing quantized emulation
        xf = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        wf = jnp.asarray(rng.normal(size=(k, n_out)).astype(np.float32))
        qx, sx, _ = quantize_sign_magnitude(xf, width)
        qw, sw, _ = quantize_sign_magnitude(wf, width, axis=0)
        call = (lambda a, b, _b=bound, _s=(sx, sw): _b(a, _s[0], b, _s[1]))
        exact = (np.asarray(qx, np.int64) * np.asarray(sx, np.int64)) @ \
                (np.asarray(qw, np.int64) * np.asarray(sw, np.int64))
        args = (qx, qw)
    appr = np.asarray(call(*args)).astype(np.float64)
    err = error_stats(appr, exact)
    t = _measure(call, *args, interp=interp, items=m * k * n_out)
    return {
        "n": int(exact.size),
        "seed": GRID_SEED, "exhaustive": False,
        "shape": {"m": m, "k": k, "n": n_out}, "frac_out": 0,
        "error": err.as_dict(), "throughput": t.as_dict(),
    }


def _run_attention(cfg: dict, quick: bool) -> dict:
    """SIMDive-normalized attention vs the exact-softmax oracle."""
    from repro.kernels.flash_attention import flash_attention_ref

    spec = SimdiveSpec(width=cfg["width"], coeff_bits=cfg["coeff_bits"],
                       index_bits=cfg["index_bits"])
    interp = cfg["backend"] == "pallas-interpret"
    geo = _cfg_geometry(cfg, quick)
    bh, sq, dh, window = geo["bh"], geo["sq"], geo["dh"], geo["window"]
    rng = np.random.default_rng(GRID_SEED + 3)
    q, k, v = (jnp.asarray(rng.normal(size=(bh, sq, dh)).astype(np.float32))
               for _ in range(3))
    frac_out = 15                 # the attention divider's default format
    bound = get_op("attention", spec, cfg["backend"],
                   block=cfg.get("block"))
    kw = dict(causal=True, window=window, approx_div=True,
              frac_out=frac_out)
    call = (lambda qq, kk, vv, _b=bound, _kw=kw: _b(qq, kk, vv, **_kw))
    out = np.asarray(call(q, k, v)).astype(np.float64)
    exact = np.asarray(flash_attention_ref(
        q, k, v, causal=True, window=window, approx_div=False)
    ).astype(np.float64)
    err = error_stats(out, exact)
    t = _measure(call, q, k, v, interp=interp, items=int(out.size))
    return {
        "n": int(out.size), "seed": GRID_SEED,
        "exhaustive": False,       # sampled class: the gate's 2% rtol
        "shape": {"bh": bh, "sq": sq, "dh": dh, "window": window,
                  "arch": cfg["arch"]},
        "frac_out": frac_out,
        "error": err.as_dict(), "throughput": t.as_dict(),
    }


def _run_serve(cfg: dict, quick: bool) -> dict:
    """The policy-resolved serving path vs its exact twin, measured.

    Builds the smoke LM twice — exact, and with an ``ApproxConfig`` whose
    one-entry attention policy pins this row's (width, coeff_bits,
    frac_out) — then scores the approximate prefill logits against the
    exact ones (sampled class), counts greedy-token agreement across a
    ``gen``-token decode, and times the steady-state jitted decode step on
    a warmed post-prompt cache (the per-token latency a scheduler sees).
    """
    from repro.configs import get_config
    from repro.core.approx import ApproxConfig
    from repro.launch.serve import generate, make_decode_step, merge_cache
    from repro.models import build
    from repro.tuning.select import PolicyEntry, TuningPolicy

    geo = _cfg_geometry(cfg, quick)
    B, P, G = geo["batch"], geo["prompt"], geo["gen"]
    frac_out = cfg["width"] - 1          # quotient in [0,1]: width-1 bits
    policy = TuningPolicy(
        entries=(PolicyEntry(op="attention", width=cfg["width"],
                             coeff_bits=cfg["coeff_bits"],
                             index_bits=cfg["index_bits"],
                             backend=cfg["backend"], frac_out=frac_out),),
        meta=(("source", "bench-serve-row"),))
    base = get_config(cfg["arch"], smoke=True)
    lm_e = build(base)
    lm_a = build(base.with_approx(ApproxConfig(
        mode="simdive", use_in_softmax=True, policy=policy)))
    params = lm_e.init(jax.random.PRNGKey(GRID_SEED))
    rng = np.random.default_rng(GRID_SEED + 4)
    prompts = jnp.asarray(rng.integers(0, base.vocab_size, (B, P),
                                       dtype=np.int32))
    max_seq = P + G
    logits_e, _ = lm_e.prefill(params, {"tokens": prompts})
    logits_a, cache = lm_a.prefill(params, {"tokens": prompts})
    err = error_stats(np.asarray(logits_a, np.float64),
                      np.asarray(logits_e, np.float64))
    tok_e = np.asarray(generate(lm_e, params, prompts, max_seq, G))
    tok_a = np.asarray(generate(lm_a, params, prompts, max_seq, G))
    # steady-state decode step at the first post-prompt position; the
    # non-donating wrapper keeps the timed buffer re-runnable
    step = make_decode_step(lm_a, donate=False)
    cache = merge_cache(lm_a.empty_cache(B, max_seq), cache)
    tok = jnp.argmax(logits_a, -1).astype(jnp.int32)
    call = (lambda: step(params, cache, tok, jnp.int32(P)))
    t = time_callable(call, iters=9, items=B)
    tp = t.as_dict()
    tp["shape_buckets"] = geo["shape_buckets"]
    return {
        "n": int(np.asarray(logits_e).size), "seed": GRID_SEED,
        "exhaustive": False,             # sampled class: the gate's 2% rtol
        "shape": {"arch": cfg["arch"], "batch": B, "prompt": P, "gen": G},
        "frac_out": frac_out,
        "token_match": float((tok_e == tok_a).mean()),
        "error": err.as_dict(), "throughput": tp,
    }


def _run_fault(cfg: dict, quick: bool) -> dict:
    """SEU resilience row: the deterministic fault-site sweep of one
    (op, width, coeff_bits) through :mod:`repro.faults.campaign`.

    The gated ``error`` object carries per-field maxima across the site
    set — the worst faulted ARE%, worst-case error, changed-output rate —
    so the gate flags a change that weakens the datapath's fault
    containment (the same upset suddenly corrupting more outputs, or
    corrupting them harder). Detectability (guard trips + scrub hits) is
    recorded per site; the tier-1 campaign smoke asserts it, the BENCH
    row makes it auditable.
    """
    from repro.faults.campaign import default_sites, measure_site

    op, width, cb = cfg["op"], cfg["width"], cfg["coeff_bits"]
    geo = _cfg_geometry(cfg, quick)
    results = [measure_site(s, op, width=width, coeff_bits=cb,
                            n=geo["n"], seed=GRID_SEED)
               for s in default_sites(op, width)]
    return {
        "n": geo["n"], "seed": GRID_SEED,
        "exhaustive": geo["exhaustive"],
        "shape_buckets": geo["shape_buckets"],
        "frac_out": 0 if op == "mul" else DIV_FRAC_OUT,
        "sites": [r.as_dict() for r in results],
        "n_sites": len(results),
        "detected_sites": sum(r.detected for r in results),
        "error": {
            "are_pct": max(r.are_fault_pct for r in results),
            "wce": max(r.wce_fault for r in results),
            "error_rate": max(r.changed_rate for r in results),
        },
    }


def _run_train(cfg: dict, quick: bool) -> dict:
    """Approx-in-the-loop training row: exact-vs-approx twins, gated.

    Runs :func:`repro.train.train_twin` for ``steps`` steps on the smoke
    model — both twins consume the same (seed, step)-deterministic batch
    sequence, so the recorded divergence isolates the arithmetic — then
    times the jitted *approximate* train step on a warmed state (the
    per-sequence step latency a trainer sees). The ``error`` mapping
    reuses the gate's field vocabulary for the divergence summary:
    ``are_pct`` = final loss delta %, ``wce`` = max per-step |loss
    delta|, ``nmed`` = 1 - min gradient cosine. The full
    ``simdive-train-divergence/v1`` summary rides along un-gated.
    """
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.approx import ApproxConfig
    from repro.data import make_source
    from repro.launch.train import make_train_step
    from repro.models import build
    from repro.optim import adamw, cosine_schedule
    from repro.train import train_twin

    geo = _cfg_geometry(cfg, quick)
    B, S, steps = geo["batch"], geo["seq"], geo["steps"]
    base = get_config(cfg["arch"], smoke=True)
    shape = ShapeConfig("bench-train", S, B, "train")
    acfg = ApproxConfig(mode="simdive", width=cfg["width"],
                        coeff_bits=cfg["coeff_bits"],
                        index_bits=cfg["index_bits"],
                        backward=cfg["backward"])
    lr = 1e-3
    params, trace = train_twin(base, shape, steps=steps, approx=acfg,
                               seed=GRID_SEED, lr=lr)
    s = trace.summary()
    # steady-state approximate train step on the post-run state; the
    # non-donating jit keeps the timed buffers re-runnable
    lm_a = build(base.with_approx(acfg))
    opt = adamw(cosine_schedule(lr, warmup=min(100, steps // 10 + 1),
                                total=steps))
    opt_state = jax.jit(opt.init)(params)
    src = make_source(base, shape, seed=GRID_SEED)
    batch = {k: jnp.asarray(v) for k, v in src.batch(0).items()}
    step = jax.jit(make_train_step(lm_a, opt))
    call = (lambda: step(params, opt_state, batch))
    t = time_callable(call, iters=5, items=B)
    tp = t.as_dict()
    tp["shape_buckets"] = geo["shape_buckets"]
    return {
        "n": steps, "seed": GRID_SEED,
        "exhaustive": False,             # sampled class: the gate's 2% rtol
        "shape": {"arch": cfg["arch"], "batch": B, "seq": S,
                  "steps": steps},
        "backward": cfg["backward"],
        "divergence": s,
        "error": {
            "are_pct": s["final_loss_delta_pct"],
            "wce": s["max_abs_loss_delta"],
            "nmed": 1.0 - s["min_grad_cosine"],
        },
        "throughput": tp,
    }


_GRID_RUNNERS = {
    "elemwise": _run_elemwise,
    "packed": _run_packed,
    "matmul_int": _run_matmul,
    "matmul_emul": _run_matmul,
    "attention": _run_attention,
    "serve": _run_serve,
    "fault": _run_fault,
    "train": _run_train,
}


def _cfg_label(cfg: dict) -> str:
    label = (f"{cfg['kernel']}/{cfg['op']}/{cfg['width']}b/"
             f"cb{cfg['coeff_bits']}/{cfg['backend']}")
    if "k" in cfg:
        label += f"/K{cfg['k']}"
    if "sq" in cfg:
        label += f"/{cfg['arch']}/Sq{cfg['sq']}"
    if "prompt" in cfg:
        label += f"/{cfg['arch']}/B{cfg['batch']}xP{cfg['prompt']}"
    if "seq" in cfg:
        label += f"/{cfg['arch']}/B{cfg['batch']}xS{cfg['seq']}/{cfg['backward']}-bwd"
    if cfg.get("block") is not None and len(cfg["block"]) > 2:
        label += f"/pipelined-d{cfg['block'][2]}"
    return label


def run_grid(report, quick: bool, records: list[dict],
             kernels: tuple | None = None) -> int:
    """Sweep every grid config, appending records into ``records``.

    One config failing must not lose the rest of the sweep (nor the
    records already computed — the caller owns the list, so even an
    escaping exception keeps them): failures append a
    ``{"status": "failed", ...}`` record and the gate downstream treats
    them as regressions, distinct from configs that were never run.
    ``kernels`` restricts the sweep to those grid kernels (``--only
    attention``). Returns the number of failed configs.
    """
    failures = 0
    report("# === grid: (kernel, op, width, coeff_bits, backend) error + "
           "throughput trajectory")
    for cfg in _grid_configs(quick):
        if kernels is not None and cfg["kernel"] not in kernels:
            continue
        base = {
            "kernel": cfg["kernel"], "op": cfg["op"], "width": cfg["width"],
            "coeff_bits": cfg["coeff_bits"],
            "index_bits": cfg["index_bits"], "backend": cfg["backend"],
        }
        try:
            rec = {**base, "status": "ok",
                   **_GRID_RUNNERS[cfg["kernel"]](cfg, quick)}
            if cfg["kernel"] == "fault" and "n_sites" in rec:
                # fault rows time nothing; their headline is containment
                report(f"grid,{_cfg_label(cfg)},"
                       f"worstARE%={rec['error']['are_pct']:.4f},"
                       f"changed={rec['error'].get('error_rate', 0.0):.3f},"
                       f"detected={rec['detected_sites']}/"
                       f"{rec['n_sites']}")
            elif cfg["kernel"] == "train":
                # divergence vocabulary, not per-lane error stats
                err, tp = rec["error"], rec["throughput"]
                report(f"grid,{_cfg_label(cfg)},"
                       f"lossDelta%={err['are_pct']:.4f},"
                       f"1-gcos={err['nmed']:.4f},"
                       f"mean_us={tp['mean_us']:.0f}")
            else:
                err, tp = rec["error"], rec["throughput"]
                report(f"grid,{_cfg_label(cfg)},ARE%={err['are_pct']:.4f},"
                       f"NMED={err['nmed']:.3e},PRE%={err['pre_pct']:.3f},"
                       f"mean_us={tp['mean_us']:.0f}")
        # simdive-lint: allow(swallowed-exception): becomes a gated "failed" record, not silence
        except Exception as e:  # noqa: BLE001 — keep the sweep going
            failures += 1
            rec = {**base, "status": "failed",
                   "error_msg": f"{type(e).__name__}: {e}"}
            try:
                # declared buckets land the failure on the same gate key
                # as its healthy baseline twin (it never timed anything)
                rec["shape_buckets"] = _cfg_geometry(cfg, quick)[
                    "shape_buckets"]
            # simdive-lint: allow(swallowed-exception): geometry must never mask the recorded failure
            except Exception:  # noqa: BLE001 — geometry must never mask
                pass           # the original failure
            report(f"# !!! grid config {_cfg_label(cfg)} FAILED: "
                   f"{type(e).__name__}: {e}")
            traceback.print_exc()
        records.append(rec)
    return failures


# ----------------------------------------------------------------- suites --
def _jsonify(x):
    """Structured suite rows -> plain JSON (dataclasses via .as_dict())."""
    if hasattr(x, "as_dict"):
        return _jsonify(x.as_dict())
    if isinstance(x, dict):
        return {str(k): _jsonify(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonify(v) for v in x]
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


def run_suites(report, wanted, quick: bool):
    suites, failures = {}, 0
    for name, module, quick_ok, desc in SUITES:
        if wanted is not None:
            if name not in wanted:
                continue
        elif quick and not quick_ok:
            continue
        report(f"# === {name}: {desc}")
        t0 = time.time()  # simdive-lint: allow(timing-outside-harness): suite wall-clock, not kernel timing
        try:
            mod = __import__(module, fromlist=["main"])
            kw = {"report": report}
            if "quick" in inspect.signature(mod.main).parameters:
                kw["quick"] = quick
            rows = mod.main(**kw)
            dt = time.time() - t0  # simdive-lint: allow(timing-outside-harness): suite wall-clock, not kernel timing
            suites[name] = {"status": "ok", "seconds": round(dt, 2),
                            "rows": _jsonify(rows)}
            report(f"# --- {name} done in {dt:.1f}s")
        # simdive-lint: allow(swallowed-exception): recorded as a failed suite, counted against exit status
        except Exception as e:  # noqa: BLE001 — keep the harness sweeping
            failures += 1
            suites[name] = {"status": "failed",
                            "error": f"{type(e).__name__}: {e}"}
            report(f"# !!! {name} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    return suites, failures


# -------------------------------------------------------------- autotune --
def reuse_autotune(path: str) -> tuple[int, str]:
    """Preload the registry autotune cache from recorded winners.

    Merges ``autotune`` records *per key* across the trajectory's run
    history, newest run first (the latest winner for a key always takes
    precedence, but a key only recorded by an older run still loads —
    a newer run with a missing or corrupt ``autotune`` field no longer
    silently discards every older winner). ``path`` (the ``--bench-out``
    trajectory) is merged first; the committed repo baseline fills in
    keys it lacks, so a local ``run.py --reuse-autotune --bench-out
    new.json`` still reuses the committed winners exactly like CI's
    copy-then-run flow.

    Every anomaly is *loud* (stderr): an unreadable trajectory, a run
    whose ``autotune`` field is not a list, malformed records inside one,
    and winners the registry rejected (retired blocks / unknown ops).
    Loading remains best-effort — the cache is an optimization, never a
    correctness input — but a silent no-op is itself a perf bug, which is
    why this warns instead of just falling through. Returns
    ``(entries loaded, source description)``.
    """
    def warn(msg):
        print(f"# !!! reuse-autotune: {msg}", file=sys.stderr)

    committed = os.path.join(_REPO_ROOT, "BENCH_simdive.json")
    merged: dict[str, dict] = {}       # json key -> newest record seen
    sources = []
    for src in dict.fromkeys([path, committed]):   # de-duped, order kept
        try:
            with open(src) as f:
                doc = migrate_doc(json.load(f))
        except FileNotFoundError:
            continue                   # scratch --bench-out: expected
        # simdive-lint: allow(swallowed-exception): warned + next source; autotune preload is best-effort
        except Exception as e:  # noqa: BLE001 — corrupt: warn, fall back
            warn(f"{src} is not a readable trajectory "
                 f"({type(e).__name__}: {e}); trying the next source")
            continue
        found = 0
        for ri in range(len(doc.get("runs", [])) - 1, -1, -1):
            run = doc["runs"][ri]
            recs = run.get("autotune")
            if recs is None:
                continue
            if not isinstance(recs, list):
                warn(f"{os.path.basename(src)} run[{ri}] has a corrupt "
                     f"autotune field ({type(recs).__name__}, expected "
                     "list); skipping that run, older runs still load")
                continue
            malformed = 0
            for rec in recs:
                try:
                    key = json.dumps(rec["key"], sort_keys=True)
                except (TypeError, KeyError):
                    malformed += 1
                    continue
                merged.setdefault(key, rec)   # newest-first: first wins
                found += 1
            if malformed:
                warn(f"{os.path.basename(src)} run[{ri}]: {malformed} "
                     "malformed autotune record(s) dropped")
        if found:
            sources.append(os.path.basename(src))
    loaded = preload_autotune_cache(list(merged.values()))
    rejected = len(merged) - loaded
    if rejected:
        warn(f"{rejected} recorded winner(s) rejected by the registry "
             "(retired block candidates or unregistered ops); they will "
             "be re-tuned")
    if not loaded:
        warn("no usable autotune records found anywhere; every block "
             "choice will be re-tuned this run")
    return loaded, "+".join(sources) if sources else path


# ------------------------------------------------------------- trajectory --
def append_trajectory(path: str, run_record: dict) -> None:
    """Append one run to the BENCH file (schema: simdive-bench/v2).

    v1 documents are migrated in place (the rewrite persists them as v2).
    A file that cannot be interpreted as a trajectory at all is renamed
    aside to ``<path>.corrupt-<runid>`` — the accumulated history is the
    very thing the regression gate diffs against, so it is *never*
    silently discarded — and the run starts a fresh document.

    Crash- and race-safe: the whole read-modify-write cycle holds an
    exclusive ``flock`` on ``<path>.lock`` (two overlapping runs
    serialize; neither append is lost) and the rewrite lands via
    write-to-temp + ``os.replace``, so a crash mid-write leaves the
    previous history intact instead of a truncated JSON document.
    """
    import tempfile
    try:
        import fcntl
    except ImportError:        # non-POSIX host: atomic replace still holds
        fcntl = None
    path = os.path.abspath(path)
    lock = open(path + ".lock", "w")
    try:
        if fcntl is not None:
            fcntl.flock(lock, fcntl.LOCK_EX)
        doc = {"schema": SCHEMA_V2, "runs": []}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    prev = json.load(f)
                doc = migrate_doc(prev)
            except (json.JSONDecodeError, OSError, TrajectoryError) as e:
                runid = run_record.get("created_unix", "unknown")
                aside = f"{path}.corrupt-{runid}"
                os.replace(path, aside)
                print(f"# !!! {path} is not a readable trajectory "
                      f"({type(e).__name__}: {e}); kept it at {aside} and "
                      "started a fresh history", file=sys.stderr)
        doc["runs"].append(run_record)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=os.path.basename(path) + ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1)
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        # simdive-lint: allow(swallowed-exception): cleanup only — re-raised below
        except BaseException:
            try:
                os.unlink(tmp)   # never leave temp droppings behind
            except OSError:
                pass
            raise
    finally:
        lock.close()


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names, may include 'grid' "
                         "(default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run: reduced grid sweep, fast suites only")
    ap.add_argument("--out", default=os.path.join(_REPO_ROOT, "results",
                                                  "bench.csv"))
    ap.add_argument("--bench-out",
                    default=os.path.join(_REPO_ROOT, "BENCH_simdive.json"))
    ap.add_argument("--reuse-autotune", action="store_true",
                    help="preload the kernel-registry autotune cache from "
                         "recorded winners (merged per key across the "
                         "trajectory history, newest first)")
    ap.add_argument("--policy", default=None, metavar="PATH",
                    help="a repro.tuning policy JSON (benchmarks/tune.py "
                         "policy --save ...): validated, echoed, and "
                         "recorded verbatim in this run's BENCH record so "
                         "the deployed accuracy settings are auditable "
                         "next to the measurements")
    args = ap.parse_args()
    policy_record = None
    if args.policy:
        from repro.tuning import TuningPolicy
        # a bad policy file must fail the run up front, not after the
        # sweep: loading validates schema + entry shape
        policy = TuningPolicy.load(args.policy)
        policy_record = {"path": os.path.basename(args.policy),
                         **policy.as_dict()}
    wanted = set(args.only.split(",")) if args.only else None
    # 'attention' / 'serve' / 'fault' are the grid restricted to those
    # kernels — handy when iterating on one path without re-sweeping
    # every op
    grid_kernels = {"attention", "serve", "fault", "train"}
    valid = {name for name, _, _, _ in SUITES} | {"grid"} | grid_kernels
    if wanted is not None and not wanted <= valid:
        # a typo'd suite name must not append an empty trajectory record
        ap.error(f"unknown --only names {sorted(wanted - valid)}; "
                 f"valid: {sorted(valid)}")

    # abspath first: a bare --out filename has an empty dirname, and
    # os.makedirs('') raises
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    lines: list[str] = []

    def report(msg):
        print(msg, flush=True)
        lines.append(str(msg))

    t_start = time.time()  # simdive-lint: allow(timing-outside-harness): sweep wall-clock, not kernel timing
    if policy_record is not None:
        report(f"# policy: {policy_record['path']} "
               f"({len(policy_record['entries'])} entries)")
    if args.reuse_autotune:
        n, src = reuse_autotune(args.bench_out)
        report(f"# reuse-autotune: preloaded {n} cached block choice(s) "
               f"from {os.path.basename(src)}")
    grid_records: list[dict] = []
    grid_failures = 0
    if wanted is None or wanted & ({"grid"} | grid_kernels):
        kernels = None
        if wanted is not None and "grid" not in wanted:
            kernels = tuple(sorted(wanted & grid_kernels))
        try:
            grid_failures = run_grid(
                report, args.quick, grid_records, kernels=kernels)
        # simdive-lint: allow(swallowed-exception): harness breakage is counted as a failure and fails the run
        except Exception as e:  # noqa: BLE001 — per-config capture is in
            # run_grid; this catches harness-level breakage, and the
            # records accumulated so far survive in grid_records
            grid_failures += 1
            report(f"# !!! grid harness FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    suites, failures = run_suites(
        report,
        None if wanted is None else wanted - ({"grid"} | grid_kernels),
        args.quick)
    failures += grid_failures

    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")

    append_trajectory(args.bench_out, {
        # simdive-lint: allow(timing-outside-harness): trajectory metadata
        "created_unix": int(time.time()),
        "quick": bool(args.quick),
        "only": sorted(wanted) if wanted else None,
        # simdive-lint: allow(timing-outside-harness): trajectory metadata
        "seconds": round(time.time() - t_start, 2),
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "failures": failures,
        "grid": grid_records,
        # the block/k_unroll choices in effect for this run — tuned this
        # run or preloaded via --reuse-autotune (schema-tolerant extra
        # field: v2 readers ignore unknown keys). Preloading validates
        # every block against the op's current candidate set, so retired
        # choices age out instead of riding the trajectory forever.
        "autotune": export_autotune_cache(),
        # the tuning policy in effect for this deployment/run, verbatim
        # (schema-tolerant extra field; None when no --policy was given)
        "policy": policy_record,
        "suites": suites,
    })
    print(f"# wrote {args.out} and {args.bench_out}; failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
