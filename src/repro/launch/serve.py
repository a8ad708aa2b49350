"""Serving driver: policy-driven batched prefill + greedy decode.

A deployment ships a ``simdive-policy/v1`` JSON (benchmarks/tune.py
policy --save ...); ``--policy`` threads it through
``ApproxConfig(policy=...)`` so every layer's matmul / divider / attention
dispatch config — width, coeff_bits, index_bits, backend, and the
attention divider's ``frac_out`` — is resolved *at load time* and printed
as a serving plan before the first token. Layer-scoped entries
(``layer='L3'``) split the scan-over-layers into per-segment scans (see
:func:`repro.core.approx.serving_segments`).

Serving modes:
  * ``--approx simdive``  — divider-softmax + (small models) bit-exact
    approximate linears,
  * ``--quantize``        — int8 weights (QuantizedWeight pytree swap), the
    memory-roofline deployment path (2x HBM bytes vs bf16, 4x vs f32);
    composes with ``--approx --emulate``: the int8 magnitudes feed the
    emulated SIMDive matmul directly.
  * ``--scheduler``       — the continuous-batching load-shed drill
    (:mod:`repro.launch.scheduler`).

Throughput is measured, not guessed: the decode step is jitted (cache
donated off-CPU), warmed once, and timed with device sync via
:func:`repro.metrics.timing.time_callable` — compile time and async
dispatch can never leak into the reported tok/s.

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --prompt-len 32 --gen 16 --batch 4 --policy policy.json
"""
from __future__ import annotations

import argparse
import sys
from dataclasses import dataclass
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.approx import ApproxConfig, serving_segments
from repro.kernels.registry import resolve_backend
from repro.launch.compile_cache import use_compile_cache
from repro.metrics.timing import time_callable
from repro.models import build
from repro.models.layers import quantize_weight


# matmul-weight leaf names (stacked (L,K,N) / MoE (L,E,K,N) / flat (K,N));
# norms, embeddings (gather tables), convs and per-head vectors stay float.
_MATMUL_WEIGHTS = frozenset(
    "wq wk wv wo w1 w2 w3 head router wr wg wz wx wdt cm_wk cm_wr cm_wv "
    "out_proj".split())


def quantize_params(params):
    """Swap every linear weight for an int8 QuantizedWeight (per-out-channel
    scale). Works on stacked per-layer weights: the leading L (and expert)
    axes survive quantization, so the scan-over-layers still slices them."""
    def q(path, leaf):
        name = path[-1] if path else ""
        if "moe" in path:
            return leaf        # expert einsums take float weights (for now)
        if (name in _MATMUL_WEIGHTS and leaf.ndim >= 2
                and leaf.shape[-1] >= 64 and leaf.shape[-2] >= 64
                and leaf.dtype in (jnp.float32, jnp.bfloat16)):
            return quantize_weight(leaf)
        return leaf

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        return q(path, tree)

    return walk(params)


# ---------------------------------------------------------------- caches --
def merge_cache(full, cache):
    """Embed a prompt-length prefill cache into a max_seq serving cache.

    Equal-shape leaves pass through; longer-seq destination leaves take
    the prefill slab at the front (dynamic_update_slice on axis 2, the
    stacked caches' seq axis). Anything else raises with the leaf path —
    a cache-layout drift must fail loudly, not silently serve an *empty*
    cache and generate garbage.
    """
    def merge(path, dst, src):
        if src.shape == dst.shape:
            return src.astype(dst.dtype)
        if (dst.ndim >= 3 and src.ndim == dst.ndim
                and dst.shape[:2] == src.shape[:2]
                and dst.shape[2] >= src.shape[2]
                and dst.shape[3:] == src.shape[3:]):
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=2)
        raise ValueError(
            f"unmergeable cache leaf {jax.tree_util.keystr(path)}: prefill "
            f"{src.shape} does not embed into serving cache {dst.shape} "
            "(cache layout drift between prefill and empty_cache?)")

    return jax.tree_util.tree_map_with_path(merge, full, cache)


# ------------------------------------------------------------ decode step --
@lru_cache(maxsize=64)
def make_decode_step(lm, donate: bool | None = None):
    """A jitted decode step bound to ``lm``, with the cache buffer donated
    so each token's KV write is in place (one token of HBM traffic, not
    one cache). ``donate=None`` donates wherever the backend implements it
    (TPU/GPU; CPU ignores donation and would warn on every compile).

    Memoized per (lm, donate): LM is a frozen dataclass, so repeated
    ``generate`` calls reuse one jitted wrapper (and its compiled
    executables) instead of retracing per call.

    Falls back to the model's own jitted ``decode_step`` if the raw
    function is not reachable (then without donation).
    """
    raw = getattr(type(lm).decode_step, "__wrapped__", None)
    if raw is None:
        return lm.decode_step
    if donate is None:
        donate = jax.default_backend() != "cpu"
    step = lambda params, cache, tok, pos: raw(lm, params, cache, tok, pos)
    return jax.jit(step, donate_argnums=(1,) if donate else ())


def generate(lm, params, prompts, max_seq: int, gen: int, *,
             decode_fn=None):
    """prompts: (B, P) int32. Greedy decode ``gen`` tokens. Returns (B,gen).

    The per-token loop runs a single jitted step function
    (:func:`make_decode_step` unless ``decode_fn`` overrides it) against
    the merged serving cache; the step's cache argument is donated
    off-CPU, so the loop re-dispatches one executable, not one trace.
    """
    B, P = prompts.shape
    logits, cache = lm.prefill(params, {"tokens": prompts})
    cache = merge_cache(lm.empty_cache(B, max_seq), cache)
    step = decode_fn if decode_fn is not None else make_decode_step(lm)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    for i in range(gen - 1):
        logits, cache = step(params, cache, tok, jnp.int32(P + i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, axis=1)


def measure_generate(lm, params, prompts, max_seq: int, gen: int, *,
                     iters: int = 3):
    """Measured serving numbers: (tokens, end-to-end stats, step stats).

    One warm pass compiles prefill + the decode step, then the full
    ``generate`` is timed ``iters`` times with device sync
    (:func:`repro.metrics.timing.time_callable` discipline), and the
    steady-state decode step is timed separately against the post-prompt
    cache — end-to-end tok/s amortizes prefill, the step timing is the
    per-token latency a scheduler sees.
    """
    B, P = prompts.shape
    step = make_decode_step(lm)
    run = lambda: generate(lm, params, prompts, max_seq, gen,
                           decode_fn=step)
    tokens = jax.block_until_ready(run())          # warm: compile everything
    e2e = time_callable(run, iters=iters, items=B * gen)
    # steady-state single step on a warmed cache (non-donating: the timed
    # callable must be re-runnable on the same operands)
    plain = make_decode_step(lm, donate=False)
    logits, cache = lm.prefill(params, {"tokens": prompts})
    cache = merge_cache(lm.empty_cache(B, max_seq), cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    step_t = time_callable(plain, params, cache, tok, jnp.int32(P),
                           iters=max(iters, 5), items=B)
    return tokens, e2e, step_t


# ------------------------------------------------------------ serving plan --
_PLAN_OPS = ("matmul", "div", "attention")


@dataclass(frozen=True)
class ResolvedOp:
    """One row of the load-time serving plan: the concrete dispatch config
    serving logical ``op`` on layers ``[layer_lo, layer_hi)``."""
    op: str
    layer_lo: int
    layer_hi: int
    width: int
    coeff_bits: int
    index_bits: int
    backend: str                 # resolved: 'ref' | 'pallas-interpret' | ...
    frac_out: int | None
    source: str                  # 'policy' entry or the config's own knobs

    def label(self) -> str:
        layers = f"L{self.layer_lo}" if self.layer_hi == self.layer_lo + 1 \
            else f"L{self.layer_lo}..L{self.layer_hi - 1}"
        frac = f"/q{self.frac_out}" if self.frac_out is not None else ""
        return (f"{layers:>8} {self.op:<9} {self.width}b/cb{self.coeff_bits}"
                f"/ib{self.index_bits}{frac} {self.backend} [{self.source}]")


def resolve_serving_plan(cfg) -> tuple[ResolvedOp, ...]:
    """Resolve every layer's per-op dispatch config at load time.

    One row per (policy-resolved layer segment, logical op): the widths /
    coeff_bits / index_bits / backend the registry will actually serve on
    this host (``auto``/``pallas`` resolved to a concrete lowering),
    including the attention divider's ``frac_out``. Exact-mode configs
    yield an empty plan (nothing approximate dispatches).
    """
    approx = cfg.approx
    if not approx.enabled:
        return ()
    rows = []
    for lo, hi, acfg in serving_segments(approx, cfg.n_layers):
        for op in _PLAN_OPS:
            if op == "attention":
                spec, backend, frac = acfg.resolve_attention()
            else:
                spec, backend = acfg.resolve(
                    op, acfg.div_width if op == "div" else None)
                frac = acfg.frac_out if op == "div" else None
            entry = approx.policy.lookup(op, acfg.layer) \
                if approx.policy is not None else None
            rows.append(ResolvedOp(
                op=op, layer_lo=lo, layer_hi=hi, width=spec.width,
                coeff_bits=spec.coeff_bits, index_bits=spec.index_bits,
                backend=resolve_backend(backend), frac_out=frac,
                source="policy" if entry is not None else "config"))
    return tuple(rows)


def serving_config(cfg, mode: str, *, emulate: bool = False, policy=None,
                   backend: str = "auto"):
    """``cfg`` carrying the serving-time approximation for ``mode``.

    Big-model serving default: divider-softmax only (linears stay MXU
    int8); ``emulate`` opts into bit-exact approximate linears. A policy
    implies approximate serving. The backend is ``auto`` — the compiled
    Pallas kernels on a TPU, the bit-exact reference elsewhere — and a
    policy entry that names a backend still pins it for its op.
    """
    if policy is not None and mode == "exact":
        mode = "simdive"       # shipping a policy means approximate serving
    if mode == "exact":
        return cfg
    return cfg.with_approx(ApproxConfig(
        mode=mode, emulate=emulate, use_in_softmax=True, policy=policy,
        backend=backend))


def render_plan(plan, cfg) -> str:
    if not plan:
        return "# serving plan: exact (no approximate dispatch)"
    segs = serving_segments(cfg.approx, cfg.n_layers)
    lines = [f"# serving plan: {len(segs)} layer segment(s), "
             f"{len(plan)} resolved op config(s)"]
    lines += [f"#   {row.label()}" for row in plan]
    return "\n".join(lines)


# --------------------------------------------------------------------- cli --
def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--approx", default="exact",
                    choices=["exact", "mitchell", "simdive"])
    ap.add_argument("--emulate", action="store_true",
                    help="bit-exact approximate linears (small models / "
                         "accuracy studies); composes with --quantize")
    ap.add_argument("--quantize", action="store_true")
    ap.add_argument("--policy", default=None, metavar="PATH",
                    help="a simdive-policy/v1 JSON; resolves per-layer/"
                         "per-op dispatch configs at load time (implies "
                         "--approx simdive unless set)")
    ap.add_argument("--scheduler", action="store_true",
                    help="run the continuous-batching load-shed drill "
                         "instead of a single batched generate")
    ap.add_argument("--chaos", action="store_true",
                    help="scheduler drill under a seeded persistent "
                         "correction-table fault: the watchdog must "
                         "quarantine, retry on the recovery rung, and "
                         "complete every admitted request (exit 1 on "
                         "any violation); implies --scheduler")
    ap.add_argument("--requests", type=int, default=12,
                    help="scheduler drill: how many requests to flood")
    ap.add_argument("--shed-depth", type=int, default=4)
    ap.add_argument("--recover-depth", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    policy = None
    if args.policy:
        from repro.tuning import TuningPolicy
        policy = TuningPolicy.load(args.policy)
        print(f"# policy: {args.policy} ({len(policy.entries)} entries, "
              f"{len(policy.distinct_configs())} distinct dispatch "
              "config(s))")
    cfg = serving_config(get_config(args.arch, smoke=args.smoke),
                         args.approx, emulate=args.emulate, policy=policy)
    plan = resolve_serving_plan(cfg)
    print(render_plan(plan, cfg))

    lm = build(cfg)
    rng = np.random.default_rng(args.seed)
    params = lm.init(jax.random.PRNGKey(args.seed))
    if args.quantize:
        params = quantize_params(params)
    max_seq = args.prompt_len + args.gen

    if args.scheduler or args.chaos:
        from repro.launch.scheduler import Scheduler, default_ladder
        sched = Scheduler(
            cfg, params=params, levels=default_ladder(cfg.approx),
            batch=args.batch, prompt_len=args.prompt_len, max_seq=max_seq,
            shed_depth=args.shed_depth, recover_depth=args.recover_depth,
            scrub_every=1 if args.chaos else 0)
        compiled = sched.warmup()
        print(f"# scheduler: precompiled {compiled} executable(s) across "
              f"{len(sched.levels)} policy level(s)")
        for _ in range(args.requests):
            sched.submit(rng.integers(0, cfg.vocab_size, args.prompt_len,
                                      dtype=np.int32), max_new=args.gen)
        if args.chaos:
            # strike every div correction table the ladder can read —
            # the attention softmax divider runs on every decode tick,
            # so undetected corruption would poison every completion.
            # Armed mid-flight (after the first admission tick) so the
            # scrub catches requests already in their decode loop.
            from repro.faults.inject import FaultSpec, set_faults
            sched.step()
            spec = FaultSpec(site="table", bit=20, kind="stuck1", op="div")
            set_faults([spec])
            print(f"# chaos: armed {spec} at tick {sched.tick_no}")
            try:
                stats = sched.run()
            finally:
                set_faults([])
        else:
            stats = sched.run()
        step_t = sched.measure_decode()
        print(f"# drill: {stats['completed']} request(s) in "
              f"{stats['ticks']} tick(s); tokens/level="
              f"{stats['tokens_per_level']}; sheds={stats['sheds']} "
              f"recovers={stats['recovers']}")
        if sched.self_heal:
            print(f"# watchdog: guard_trips={stats['guard_trips']} "
                  f"quarantines={stats['quarantines']} "
                  f"retries={stats['retries']} "
                  f"timeouts={stats['timeouts']} failed={stats['failed']}")
        step_msg = (f"decode step {step_t.best_s * 1e6:.0f}us best "
                    f"({step_t.items_per_s:.1f} tok/s steady-state, "
                    f"iters={step_t.iters}, synced)")
        print(step_msg)
        if args.chaos:
            violations = []
            if stats["completed"] != args.requests:
                violations.append(
                    f"completed {stats['completed']}/{args.requests}")
            if stats["failed"]:
                violations.append(f"{stats['failed']} request(s) failed")
            if stats["quarantines"] < 1:
                violations.append("watchdog never quarantined — the "
                                  "armed fault went unnoticed")
            rec = stats["tokens_per_level"].get("recovery", 0)
            if rec < 1:
                violations.append("no tokens attributed to the recovery "
                                  "rung")
            if violations:
                print("# chaos: FAIL — " + "; ".join(violations))
                sys.exit(1)
            print(f"# chaos: PASS — every admitted request completed; "
                  f"{rec} token(s) re-served on the recovery rung")
        return

    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len),
        dtype=np.int32))
    toks, e2e, step_t = measure_generate(lm, params, prompts, max_seq,
                                         args.gen)
    print(f"generated {toks.shape}: "
          f"{args.batch * args.gen / e2e.best_s:.1f} tok/s end-to-end "
          f"(best of {e2e.iters} post-warmup, synced); "
          f"decode step {step_t.best_s * 1e6:.0f}us "
          f"({step_t.items_per_s:.1f} tok/s steady-state)")
    print(np.asarray(toks)[:2])


if __name__ == "__main__":
    main()
