"""Training driver: sharded step, checkpoint/restart, deterministic data.

Fault-tolerance contract (tested in tests/test_checkpoint.py):
  * checkpoints are atomic (tmp-dir + rename) and written async,
  * ``--resume auto`` restarts from the newest complete checkpoint,
  * data order is a pure function of (seed, step) — a restart replays the
    exact batch sequence, so loss curves are bitwise continuous,
  * restore re-lays-out onto the *current* mesh (elastic: a job checkpointed
    on N devices resumes on M).

Usage (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ck --save-every 10
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.core.approx import ApproxConfig
from repro.data import SyntheticLM, make_source
from repro.launch import sharding as shardlib
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import (
    as_shardings,
    batch_axes_for,
    opt_specs,
    param_specs,
    sanitize_specs,
)
from repro.models import build
from repro.optim import adamw, cosine_schedule


def make_train_step(lm, opt, microbatch: int = 1,
                    grad_compress: bool = False,
                    compress_axis: str | None = None):
    """``microbatch`` > 1: gradient accumulation (same math, ~microbatch-fold
    lower activation peak — see dryrun §Perf Cell 1 it. 6).

    ``grad_compress``: int8 + error-feedback wire quantization of the
    gradients (optim/grad_compress.py). The step signature grows a
    residual tree: ``step(params, opt_state, res, batch) -> (params,
    opt_state, res, metrics)``. ``compress_axis`` names the mesh axis to
    psum over (requires shard_map); ``None`` uses the single-host
    identity-all-reduce twin, same quantization, same residual.
    """
    def compute(params, batch):
        if microbatch == 1:
            return jax.value_and_grad(lm.train_loss)(params, batch)
        def split(x):
            return x.reshape((microbatch, x.shape[0] // microbatch)
                             + x.shape[1:])

        def mb(carry, b):
            g_acc, l_acc = carry
            loss, grads = jax.value_and_grad(lm.train_loss)(params, b)
            return (jax.tree.map(jnp.add, g_acc, grads),
                    l_acc + loss), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (grads, loss), _ = jax.lax.scan(
            mb, (zeros, jnp.zeros((), jnp.float32)),
            jax.tree.map(split, batch))
        grads = jax.tree.map(lambda g: g / microbatch, grads)
        return loss / microbatch, grads

    if not grad_compress:
        def step(params, opt_state, batch):
            loss, grads = compute(params, batch)
            params, opt_state, metrics = opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss, **metrics}
        return step

    from repro.optim.grad_compress import compress_local, compress_psum

    def step(params, opt_state, res, batch):
        loss, grads = compute(params, batch)
        if compress_axis is not None:
            grads, res = compress_psum(grads, res, compress_axis)
        else:
            grads, res = compress_local(grads, res)
        params, opt_state, metrics = opt.update(grads, opt_state, params)
        return params, opt_state, res, {"loss": loss, **metrics}
    return step


def train(cfg, shape: ShapeConfig, *, steps: int, ckpt_dir: str | None,
          save_every: int = 50, resume: str = "auto", seed: int = 0,
          lr: float = 3e-4, tp: int = 1, log_every: int = 10,
          keep: int = 3, stop_after: int | None = None,
          microbatch: int = 1, schedule=None, grad_compress: bool = False,
          devices=None):
    """``stop_after``: simulate preemption — exit after that many steps
    WITHOUT the final checkpoint (only periodic commits survive), exactly
    like a killed worker. The lr schedule is always pinned to ``steps`` so
    a resumed run follows the same schedule.

    ``schedule`` (a :class:`repro.train.PrecisionSchedule`) switches the
    approximation policy at rung boundaries: each step runs under
    ``schedule.config_at(step, cfg.approx)``, one jit executable per
    rung. Because the rung is a pure function of the step — like the
    data order — a resumed run replays the same precision sequence and
    the loss curve stays bitwise continuous across a kill/resume that
    straddles a rung boundary.

    ``grad_compress``: int8 error-feedback gradient compression; the
    residual tree joins the checkpoint so resume carries the feedback
    state too.

    ``devices``: where to train (default: every device). More than one
    shards the step over a ("data", "model") mesh with ``tp`` model
    ways; one trains unsharded on that device.
    """
    lm = build(cfg)
    opt = adamw(cosine_schedule(lr, warmup=min(100, steps // 10 + 1),
                                total=steps))
    devices = jax.devices() if devices is None else list(devices)
    mesh = make_host_mesh(model=tp, devices=devices) \
        if len(devices) > 1 else None
    source = make_source(cfg, shape, seed=seed)

    key = jax.random.PRNGKey(seed)
    start_step = 0
    params = opt_state = res = None
    if ckpt_dir and resume == "auto" and ckpt.latest_step(ckpt_dir) is not None:
        params_like = jax.eval_shape(lm.init, key)
        opt_like = jax.eval_shape(opt.init, params_like)
        like = {"params": params_like, "opt": opt_like}
        if grad_compress:
            like["res"] = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                params_like)
        start_step, tree = ckpt.restore(ckpt_dir, like=like)
        params, opt_state = tree["params"], tree["opt"]
        res = tree.get("res")
        print(f"[resume] step {start_step} from {ckpt_dir}")

    # One jitted step per ApproxConfig: a schedule rung boundary swaps in
    # a model rebuilt under that rung's policy (compile-cached here, so a
    # schedule that revisits a rung reuses its executable). Key ``None``
    # is the unscheduled path — exactly ``cfg`` as handed in.
    jitted_cache: dict = {}
    donate = (0, 1, 2) if grad_compress else (0, 1)

    def jitted_for(acfg):
        fn = jitted_cache.get(acfg)
        if fn is None:
            lm_s = lm if acfg is None else build(cfg.with_approx(acfg))
            fn = jax.jit(make_train_step(lm_s, opt, microbatch=microbatch,
                                         grad_compress=grad_compress),
                         donate_argnums=donate)
            jitted_cache[acfg] = fn
        return fn

    from contextlib import ExitStack
    with ExitStack() as stack:
        if mesh is not None:
            stack.enter_context(mesh)
            stack.enter_context(
                shardlib.use_rules(mesh, {"batch": batch_axes_for(mesh)}))
        else:
            stack.enter_context(jax.default_device(devices[0]))
        if params is None:
            params = jax.jit(lm.init)(key)
            opt_state = jax.jit(opt.init)(params)
        if grad_compress and res is None:
            from repro.optim import zero_residual
            res = zero_residual(params)
        if mesh is not None:
            pspecs = sanitize_specs(param_specs(params), params, mesh)
            pshard = as_shardings(mesh, pspecs)
            params = jax.device_put(params, pshard)

        def ckpt_tree():
            tree = {"params": params, "opt": opt_state}
            if grad_compress:
                tree["res"] = res
            return tree

        losses = []
        t0 = time.time()  # simdive-lint: allow(timing-outside-harness): step wall-clock for throughput logging
        for step in range(start_step, steps):
            acfg = schedule.config_at(step, cfg.approx) \
                if schedule is not None else None
            jitted = jitted_for(acfg)
            batch = {k: jnp.asarray(v) for k, v in source.batch(step).items()}
            if grad_compress:
                params, opt_state, res, metrics = jitted(
                    params, opt_state, res, batch)
            else:
                params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0  # simdive-lint: allow(timing-outside-harness): step wall-clock for throughput logging
                rung = ""
                if schedule is not None:
                    r = schedule.rung_at(step)
                    rung = f" rung={r.label or r.start_step}"
                print(f"[step {step:5d}] loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e}{rung} ({dt:.1f}s)",
                      flush=True)
            if ckpt_dir and save_every and (step + 1) % save_every == 0:
                ckpt.save_async(ckpt_dir, step + 1, ckpt_tree())
                ckpt.gc_keep_last(ckpt_dir, keep=keep)
            if stop_after is not None and step + 1 >= stop_after:
                ckpt.wait_pending()   # flush committed periodic saves only
                return params, losses
        if ckpt_dir:
            ckpt.wait_pending()
            ckpt.save(ckpt_dir, steps, ckpt_tree())
    return params, losses


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--approx", default="exact",
                    choices=["exact", "mitchell", "simdive"])
    ap.add_argument("--policy", default=None, metavar="JSON",
                    help="tuning policy (simdive-policy/v1) for the "
                         "approximate arithmetic")
    ap.add_argument("--schedule", default=None, metavar="JSON",
                    help="precision schedule (simdive-schedule/v1): "
                         "per-rung policies switched at step boundaries")
    ap.add_argument("--backward", default="exact",
                    choices=["exact", "approx"],
                    help="'approx' emulates approximate backward matmuls "
                         "too (default: exact grads via custom_vjp)")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 + error-feedback gradient compression")
    ap.add_argument("--twin", action="store_true",
                    help="train exact + approx twins on identical batches "
                         "and report loss divergence instead of a single "
                         "run (no checkpoints)")
    ap.add_argument("--divergence-out", default=None, metavar="JSON",
                    help="with --twin: write the DivergenceTrace report")
    ap.add_argument("--assert-final-delta-pct", type=float, default=None,
                    help="with --twin: exit 1 if |final loss delta| "
                         "exceeds this percentage of the exact loss")
    ap.add_argument("--assert-grad-cosine", type=float, default=None,
                    help="with --twin: exit 1 if any step's gradient "
                         "cosine similarity falls below this")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    policy = None
    if args.policy:
        from repro.tuning import TuningPolicy
        policy = TuningPolicy.load(args.policy)
    schedule = None
    if args.schedule:
        from repro.train import PrecisionSchedule
        schedule = PrecisionSchedule.load(args.schedule)

    if args.twin:
        import json
        import sys

        from repro.train import train_twin
        mode = args.approx if args.approx != "exact" else "simdive"
        base = ApproxConfig(mode=mode, policy=policy,
                            backward=args.backward)
        _, trace = train_twin(
            cfg, shape, steps=args.steps, approx=base, schedule=schedule,
            seed=args.seed, lr=args.lr, grad_compress=args.grad_compress,
            log_every=max(args.steps // 10, 1))
        print(trace.render())
        if args.divergence_out:
            trace.save(args.divergence_out)
            print(f"[twin] wrote {args.divergence_out}")
        failures = []
        delta = trace.final_loss_delta_pct()
        if args.assert_final_delta_pct is not None \
                and delta > args.assert_final_delta_pct:
            failures.append(
                f"final loss delta {delta:.3f}% > "
                f"{args.assert_final_delta_pct}%")
        gcos = trace.min_grad_cosine()
        if args.assert_grad_cosine is not None and gcos is not None \
                and gcos < args.assert_grad_cosine:
            failures.append(
                f"min grad cosine {gcos:.4f} < {args.assert_grad_cosine}")
        if failures:
            print("[twin] DIVERGED: " + "; ".join(failures))
            sys.exit(1)
        print(json.dumps(trace.summary(), sort_keys=True))
        return

    if args.approx != "exact" or policy is not None:
        mode = args.approx if args.approx != "exact" else "simdive"
        cfg = cfg.with_approx(ApproxConfig(mode=mode, policy=policy,
                                           backward=args.backward))
    train(cfg, shape, steps=args.steps, ckpt_dir=args.ckpt_dir,
          save_every=args.save_every, resume=args.resume, seed=args.seed,
          lr=args.lr, tp=args.tp, microbatch=args.microbatch,
          schedule=schedule, grad_compress=args.grad_compress)


if __name__ == "__main__":
    main()
