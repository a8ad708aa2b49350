"""Serving-path tests: policy-resolved dispatch, the continuous-batching
scheduler's load-shed drill, and the regressions PR 7 fixed.

The regression pair this file pins:
  * ``merge_cache`` used to *silently* return the empty destination leaf
    on a shape/rank mismatch — a serving cache of zeros, garbage tokens,
    no error. It must raise, naming the leaf path.
  * the decode loop re-dispatched an unjitted step and read
    ``time.time()`` without a device sync — ``make_decode_step`` is now a
    memoized jitted wrapper and every reported number goes through
    :func:`repro.metrics.timing.time_callable` (warmup + block_until_ready).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.approx import ApproxConfig, serving_segments
from repro.launch.serve import (
    generate,
    make_decode_step,
    measure_generate,
    merge_cache,
    quantize_params,
    resolve_serving_plan,
    serving_config,
)
from repro.models import build
from repro.models.layers import QuantizedWeight
from repro.tuning.select import PolicyEntry, TuningPolicy

ARCH = "smollm-360m"
B, P, GEN = 2, 16, 6


def _lm_and_params(approx=None, seed=0):
    cfg = get_config(ARCH, smoke=True)
    if approx is not None:
        cfg = cfg.with_approx(approx)
    lm = build(cfg)
    return cfg, lm, lm.init(jax.random.PRNGKey(seed))


def _prompts(cfg, seed=0, batch=B, plen=P):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, plen),
                                    dtype=np.int32))


def _policy(entries=None, **meta):
    entries = entries or (
        PolicyEntry(op="matmul", width=16, coeff_bits=8, kernel="matmul"),
        PolicyEntry(op="div", width=16, coeff_bits=8),
        PolicyEntry(op="attention", width=16, coeff_bits=8, frac_out=15),
    )
    return TuningPolicy(entries=tuple(entries),
                        meta=tuple(sorted(meta.items())))


# ------------------------------------------------------------ smoke path --
def test_generate_smoke():
    cfg, lm, params = _lm_and_params()
    toks = generate(lm, params, _prompts(cfg), P + GEN, GEN)
    assert toks.shape == (B, GEN)
    assert toks.dtype == jnp.int32
    # greedy decode of a deterministic model is itself deterministic
    again = generate(lm, params, _prompts(cfg), P + GEN, GEN)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(again))


def test_measured_numbers_are_synced_and_warm():
    """Regression: reported tok/s must come from the timing harness
    (warmup >= 1, positive best-of-iters wall-clock, device-synced), not
    from a bare time.time() around an async dispatch."""
    cfg, lm, params = _lm_and_params()
    toks, e2e, step_t = measure_generate(lm, params, _prompts(cfg),
                                         P + GEN, GEN, iters=2)
    assert toks.shape == (B, GEN)
    for t in (e2e, step_t):
        assert t.warmup >= 1
        assert t.iters >= 2
        assert 0 < t.best_s <= t.mean_s
    assert step_t.items_per_s > 0


def test_decode_step_wrapper_is_memoized():
    """Regression: one jitted wrapper per (lm, donate) — a fresh wrapper
    per generate() call would retrace/recompile every token loop."""
    _, lm, _ = _lm_and_params()
    assert make_decode_step(lm, donate=False) is \
        make_decode_step(lm, donate=False)


# ------------------------------------------------------------ merge_cache --
def test_merge_cache_embeds_prefix():
    cfg, lm, params = _lm_and_params()
    _, pre = lm.prefill(params, {"tokens": _prompts(cfg)})
    full = merge_cache(lm.empty_cache(B, P + GEN), pre)
    k_pre = jax.tree.leaves(pre)[0]
    k_full = jax.tree.leaves(full)[0]
    assert k_full.shape[2] == P + GEN
    np.testing.assert_allclose(np.asarray(k_full[:, :, :P]),
                               np.asarray(k_pre), rtol=1e-6, atol=1e-6)


def test_merge_cache_mismatch_raises_with_leaf_path():
    """Regression: a rank/shape drift used to silently return the *empty*
    destination leaf — the server then decoded against a zero cache."""
    dst = {"layers": {"k": jnp.zeros((2, B, 32, 4, 8))}}
    src = {"layers": {"k": jnp.zeros((2, B, 16, 4))}}        # rank drift
    with pytest.raises(ValueError, match=r"\['layers'\]\['k'\]"):
        merge_cache(dst, src)
    src = {"layers": {"k": jnp.zeros((2, B + 1, 16, 4, 8))}}  # batch drift
    with pytest.raises(ValueError, match="does not embed"):
        merge_cache(dst, src)


# ----------------------------------------------------------------- policy --
def test_policy_roundtrip_into_serving_plan(tmp_path):
    """A saved policy file resolves into the load-time serving plan: every
    op row sourced from the policy, attention frac_out included."""
    pol = _policy(source="test")
    path = tmp_path / "policy.json"
    pol.save(str(path))
    loaded = TuningPolicy.load(str(path))
    assert loaded == pol
    assert len(loaded.distinct_configs()) == 3
    cfg = get_config(ARCH, smoke=True).with_approx(
        ApproxConfig(mode="simdive", use_in_softmax=True, policy=loaded))
    plan = resolve_serving_plan(cfg)
    assert len(plan) == 3                      # one segment x three ops
    assert all(row.source == "policy" for row in plan)
    att = next(r for r in plan if r.op == "attention")
    assert (att.width, att.coeff_bits, att.frac_out) == (16, 8, 15)


@pytest.mark.parametrize("backend,want", [("auto", "ref"),
                                          ("pallas", "pallas-interpret")])
def test_serving_config_plan_names_the_resolved_backend(backend, want):
    """The serve CLI's config resolves 'auto' (compiled kernels on a TPU,
    the reference elsewhere — this host has no TPU); the printed plan
    names the concrete lowering, never the unresolved request."""
    cfg = serving_config(get_config(ARCH, smoke=True), "simdive",
                         emulate=True, backend=backend)
    plan = resolve_serving_plan(cfg)
    assert cfg.approx.backend == backend
    assert [r.backend for r in plan] == [want] * 3


def test_serving_config_exact_and_policy_modes():
    base = get_config(ARCH, smoke=True)
    assert serving_config(base, "exact") is base
    pol = _policy()
    cfg = serving_config(base, "exact", policy=pol)     # policy => approx
    assert cfg.approx.mode == "simdive" and cfg.approx.policy is pol
    assert cfg.approx.backend == "auto"


def test_policy_matching_defaults_token_parity():
    """A policy pinning exactly the config's own defaults must serve the
    same tokens as the policy-free config — resolution, not behavior."""
    base = ApproxConfig(mode="simdive", use_in_softmax=True)
    spec_a, _, frac = base.resolve_attention()
    pol = TuningPolicy(entries=(
        PolicyEntry(op="attention", width=spec_a.width,
                    coeff_bits=spec_a.coeff_bits,
                    index_bits=spec_a.index_bits, frac_out=frac),))
    cfg, lm0, params = _lm_and_params(base)
    lm1 = build(cfg.with_approx(
        ApproxConfig(mode="simdive", use_in_softmax=True, policy=pol)))
    prompts = _prompts(cfg)
    t0 = generate(lm0, params, prompts, P + GEN, GEN)
    t1 = generate(lm1, params, prompts, P + GEN, GEN)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))


def test_layer_scoped_policy_splits_segments_and_serves():
    """A layer-scoped entry splits the scan into per-segment scans; the
    split model still prefills + decodes (and differs from uniform)."""
    pol = _policy(entries=(
        PolicyEntry(op="attention", width=16, coeff_bits=8, frac_out=15),
        PolicyEntry(op="attention", width=16, coeff_bits=0, frac_out=12,
                    layer="L1"),
    ))
    approx = ApproxConfig(mode="simdive", use_in_softmax=True, policy=pol)
    cfg, lm, params = _lm_and_params(approx)
    segs = serving_segments(approx, cfg.n_layers)
    assert len(segs) == 2
    assert [(lo, hi) for lo, hi, _ in segs] == [(0, 1), (1, cfg.n_layers)]
    toks = generate(lm, params, _prompts(cfg), P + GEN, GEN)
    assert toks.shape == (B, GEN)


# --------------------------------------------------------------- quantize --
def test_quantize_survives_policy_resolved_dispatch():
    """Regression target: --quantize x --approx simdive --emulate used to
    be an untested composition. The int8 QuantizedWeight must survive the
    policy-resolved emulated matmul (finite logits, plausible decode)."""
    pol = _policy()
    approx = ApproxConfig(mode="simdive", emulate=True,
                          use_in_softmax=True, policy=pol)
    cfg, lm, params = _lm_and_params(approx)
    qparams = quantize_params(params)
    assert any(isinstance(l, QuantizedWeight)
               for l in jax.tree.leaves(
                   qparams, is_leaf=lambda x: isinstance(x, QuantizedWeight)))
    prompts = _prompts(cfg)
    logits, _ = lm.prefill(qparams, {"tokens": prompts})
    assert bool(jnp.isfinite(logits).all())
    toks = generate(lm, qparams, prompts, P + GEN, GEN)
    assert toks.shape == (B, GEN)
    # and the quantized approximate path tracks the quantized exact path
    lm_exact = build(get_config(ARCH, smoke=True))
    logits_e, _ = lm_exact.prefill(qparams, {"tokens": prompts})
    rel = float(jnp.abs(logits - logits_e).mean()
                / (jnp.abs(logits_e).mean() + 1e-9))
    assert rel < 0.2


def test_quantize_refuses_narrow_lane_loudly():
    """A policy whose matmul lane cannot hold int8 magnitudes must raise,
    not silently truncate the weights."""
    pol = _policy(entries=(
        PolicyEntry(op="matmul", width=4, coeff_bits=2, kernel="matmul"),))
    approx = ApproxConfig(mode="simdive", emulate=True, policy=pol)
    cfg, lm, params = _lm_and_params(approx)
    qparams = quantize_params(params)
    with pytest.raises(ValueError, match="cannot hold int8"):
        jax.block_until_ready(
            lm.prefill(qparams, {"tokens": _prompts(cfg)}))


# -------------------------------------------------------------- scheduler --
def _scheduler(batch=2, requests=0, shed_depth=3, recover_depth=1, gen=4,
               **kw):
    from repro.launch.scheduler import Scheduler, default_ladder

    approx = ApproxConfig(mode="simdive", use_in_softmax=True,
                          policy=_policy())
    cfg = get_config(ARCH, smoke=True).with_approx(approx)
    sched = Scheduler(cfg, levels=default_ladder(approx), batch=batch,
                      prompt_len=P, max_seq=P + gen + 2,
                      shed_depth=shed_depth, recover_depth=recover_depth,
                      seed=0, **kw)
    rng = np.random.default_rng(7)
    for _ in range(requests):
        sched.submit(rng.integers(0, cfg.vocab_size, P, dtype=np.int32),
                     max_new=gen)
    return cfg, sched


def test_scheduler_single_request_matches_generate():
    """One request through the scheduler == the plain batched generate
    (same level, same greedy tokens) — continuous batching must not
    change what is computed, only when."""
    cfg, sched = _scheduler(batch=2, requests=0)
    lm = sched.lms[0]
    params = sched.params
    prompt = np.asarray(_prompts(cfg, batch=1))[0]
    req = sched.submit(prompt, max_new=GEN)
    sched.warmup()
    stats = sched.run()
    assert stats["completed"] == 1
    assert stats["sheds"] == 0                 # queue never got deep
    want = np.asarray(generate(lm, params, jnp.asarray(prompt)[None],
                               sched.max_seq, GEN))[0]
    np.testing.assert_array_equal(np.asarray(req.tokens), want)


def test_scheduler_load_shed_drill():
    """The drill the issue asks for: flood the queue past shed_depth,
    watch the scheduler hot-swap to the coarser precompiled level, drain,
    and recover — with every request completing."""
    _, sched = _scheduler(batch=2, requests=8, shed_depth=3,
                          recover_depth=1)
    compiled = sched.warmup()
    assert compiled == 2 * len(sched.levels)
    stats = sched.run()
    assert stats["completed"] == 8
    assert stats["sheds"] >= 1
    assert stats["recovers"] >= 1
    kinds = [k for _, k, _ in stats["events"]]
    assert kinds.index("shed") < kinds.index("recover")
    # both rungs actually served tokens
    assert stats["tokens_per_level"]["fine"] > 0
    assert stats["tokens_per_level"]["shed"] > 0
    # every token is attributed to the rung that produced it
    total = sum(len(r.tokens) for r in sched.done)
    assert sum(stats["tokens_per_level"].values()) == total


def test_scheduler_validates_geometry():
    cfg, sched = _scheduler()
    with pytest.raises(ValueError, match="prompt length"):
        sched.submit(np.zeros(P + 1, np.int32), max_new=2)
    with pytest.raises(ValueError, match="max_seq"):
        sched.submit(np.zeros(P, np.int32), max_new=10_000)
    from repro.launch.scheduler import Scheduler
    with pytest.raises(ValueError, match="recover_depth"):
        Scheduler(cfg, levels=sched.levels, batch=2, prompt_len=P,
                  max_seq=64, shed_depth=2, recover_depth=2)


def test_scheduler_refuses_zero_length_prompt_loudly():
    cfg, sched = _scheduler()
    from repro.launch.scheduler import Scheduler
    with pytest.raises(ValueError, match="prompt_len must be positive"):
        Scheduler(cfg, levels=sched.levels, batch=2, prompt_len=0,
                  max_seq=64, shed_depth=3, recover_depth=1)
    with pytest.raises(ValueError, match="max_retries"):
        Scheduler(cfg, levels=sched.levels, batch=2, prompt_len=P,
                  max_seq=64, shed_depth=3, recover_depth=1,
                  max_retries=-1)


def test_scheduler_retire_during_active_shed():
    """A request retiring while the shed rung is active must free its
    slot for the next queued request at the *current* (shed) level, with
    every token attributed to the rung that actually produced it."""
    _, sched = _scheduler(batch=2, requests=8, shed_depth=2,
                          recover_depth=1, gen=3)
    sched.warmup()
    stats = sched.run()
    assert stats["completed"] == 8
    shed_tick = next(t for t, k, _ in stats["events"] if k == "shed")
    recover_tick = next(t for t, k, _ in stats["events"] if k == "recover")
    retire_ticks = [t for t, k, _ in stats["events"] if k == "retire"]
    # at least one retirement landed while the shed rung was active ...
    assert any(shed_tick <= t < recover_tick for t in retire_ticks)
    # ... and the shed rung produced tokens for it
    assert stats["tokens_per_level"]["shed"] > 0
    total = sum(len(r.tokens) for r in sched.done)
    assert sum(stats["tokens_per_level"].values()) == total


def test_scheduler_all_slots_busy_queue_accounting():
    """With every slot occupied, admission must leave the queue intact —
    depth only drains as slots free — and nothing is double-admitted."""
    _, sched = _scheduler(batch=2, requests=6, shed_depth=100, gen=4)
    sched.warmup()
    sched.step()                       # admits exactly `batch` requests
    assert sum(r is not None for r in sched.slots) == 2
    assert len(sched.queue) == 4
    depth_before = len(sched.queue)
    sched.step()                       # slots busy: no admission possible
    assert len(sched.queue) == depth_before
    admits = [v for _, k, v in sched.events if k == "admit"]
    assert len(admits) == len(set(admits)) == 2
    stats = sched.run()
    assert stats["completed"] == 6
    assert len(set(r.rid for r in sched.done)) == 6


def test_scheduler_hysteresis_does_not_flap():
    """A queue sitting strictly between recover_depth and shed_depth
    must not move the level at all — and a shed is never immediately
    re-shed/recovered tick-over-tick (the recover_depth < shed_depth
    gap is the anti-flapping contract)."""
    _, sched = _scheduler(batch=2, requests=5, shed_depth=6,
                          recover_depth=1, gen=4)
    sched.warmup()
    stats = sched.run()
    assert stats["completed"] == 5
    # depth peaks at 5 and drains through the (1, 6) hysteresis band
    # without ever crossing it -> the ladder never moved
    assert stats["sheds"] == 0 and stats["recovers"] == 0
    # and a drill that does shed never alternates on adjacent ticks
    _, sched2 = _scheduler(batch=2, requests=10, shed_depth=3,
                           recover_depth=1, gen=3)
    sched2.warmup()
    stats2 = sched2.run()
    moves = [(t, k) for t, k, _ in stats2["events"]
             if k in ("shed", "recover")]
    for (t1, k1), (t2, k2) in zip(moves, moves[1:]):
        if k1 != k2:
            assert t2 > t1 + 1, f"level flapped {k1}->{k2} on adjacent ticks"


# ------------------------------------------------------- watchdog / chaos --
def test_scheduler_chaos_drill_self_heals():
    """The ISSUE's acceptance drill: a persistent correction-table fault
    lands mid-flight; the scrub quarantines poisoned work, retries it on
    the exact recovery rung, and every admitted request completes with
    finite outputs — none silently served, none lost."""
    from repro.faults.inject import FaultSpec, set_faults

    _, sched = _scheduler(batch=2, requests=6, shed_depth=100, gen=4,
                          scrub_every=1)
    assert sched.levels[-1].name == "recovery"
    sched.warmup()
    sched.step()                     # first admission is in flight
    set_faults([FaultSpec(site="table", bit=20, kind="stuck1", op="div")])
    try:
        stats = sched.run()
    finally:
        set_faults([])
    assert stats["completed"] == 6 and stats["failed"] == 0
    assert stats["quarantines"] >= 1 and stats["retries"] >= 1
    assert stats["tokens_per_level"]["recovery"] > 0
    # quarantined requests were re-served from scratch on the exact rung
    for req in sched.done:
        assert len(req.tokens) == req.max_new
        if req.retries:
            assert set(req.levels) == {"recovery"}
    # the scrub saw the corruption and said which table
    dirty = [v for _, k, v in stats["events"] if k == "scrub-dirty"]
    assert dirty and "div" in dirty[0]


def test_scheduler_scrub_clears_after_repair():
    """Disarming the fault (config memory repaired) must lift the
    recovery pin: the scrub logs a clean pass and later admissions run
    the ladder again."""
    from repro.faults.inject import FaultSpec, set_faults

    _, sched = _scheduler(batch=2, requests=2, shed_depth=100, gen=4,
                          scrub_every=1)
    sched.warmup()
    sched.step()
    set_faults([FaultSpec(site="table", bit=20, kind="stuck1", op="div")])
    try:
        sched.step()                 # scrub-dirty + quarantine
        assert sched._poisoned
    finally:
        set_faults([])
    stats = sched.run()
    assert not stats["poisoned"]
    kinds = [k for _, k, _ in stats["events"]]
    assert "scrub-dirty" in kinds and "scrub-clean" in kinds
    assert kinds.index("scrub-dirty") < kinds.index("scrub-clean")
    assert stats["completed"] == 2 and stats["failed"] == 0


def test_scheduler_tick_budget_times_out_and_retries():
    """A request overstaying tick_budget is quarantined (counted as a
    timeout), backed off, and re-served — not left occupying a slot."""
    _, sched = _scheduler(batch=2, requests=2, shed_depth=100, gen=4,
                          tick_budget=1)   # gen=4 needs ~4 ticks: must trip
    sched.warmup()
    stats = sched.run()
    assert stats["timeouts"] >= 1
    assert stats["retries"] >= 1
    # retried requests still only ever fail loudly, never hang the drain
    assert stats["completed"] + stats["failed"] == 2
    for req in sched.failed:
        assert req.failed and "budget" in req.fail_reason


def test_scheduler_exhausted_retries_fail_loudly():
    """max_retries=0: the first quarantine fails the request outright —
    it lands in stats['failed'] with a reason, never in done."""
    from repro.faults.inject import FaultSpec, set_faults

    _, sched = _scheduler(batch=2, requests=2, shed_depth=100, gen=4,
                          scrub_every=1, max_retries=0)
    sched.warmup()
    sched.step()
    set_faults([FaultSpec(site="table", bit=20, kind="stuck1", op="div")])
    try:
        stats = sched.run()
    finally:
        set_faults([])
    assert stats["failed"] == 2 and stats["completed"] == 0
    assert stats["quarantines"] == 2 and stats["retries"] == 0
    for req in sched.failed:
        assert req.failed and req.fail_reason
        assert req.tokens == []      # partial poisoned work was discarded
    kinds = [k for _, k, _ in stats["events"]]
    assert kinds.count("fail") == 2


def test_scheduler_self_heal_off_keeps_legacy_shape():
    """self_heal=False: no recovery rung, no watchdog — the ladder is
    exactly what the caller passed (the pre-watchdog contract)."""
    _, sched = _scheduler(batch=2, requests=2, gen=3, self_heal=False)
    assert [lv.name for lv in sched.levels] == ["fine", "shed"]
    sched.warmup()
    stats = sched.run()
    assert stats["completed"] == 2
    assert stats["quarantines"] == 0 and stats["guard_trips"] == 0


# ---------------------------------------------------------- compile cache --
def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache sits at the checkout's fixed .jax_cache/."""
    from repro.launch import compile_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.use_compile_cache() == str(cc.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.REPO_CACHE_DIR)
        assert cc.REPO_CACHE_DIR.name == ".jax_cache"
        assert (cc.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
