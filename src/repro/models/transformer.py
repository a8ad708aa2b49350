"""Decoder assembly: blocks, scan-over-layers, KV caches, hybrid interleave.

One code path serves all ten architectures:
  * dense / moe / vlm / audio — attention blocks (GQA, SWA, partial/M-RoPE,
    qk-norm, biases) + MLP or MoE, homogeneous stack -> ``lax.scan`` over
    stacked per-layer params (keeps HLO size O(1) in depth — essential for
    48-layer models compiling against 512 virtual devices).
  * ssm (rwkv6) — RWKV blocks scanned the same way.
  * hybrid (zamba2) — Mamba2 backbone scanned in groups of ``hybrid_period``
    with one *shared* attention+MLP block (single weight copy + small
    per-invocation LoRA) applied between groups.

Caches for decode are pytrees of stacked (L, ...) arrays so the decode step
is also a layer scan. Sliding-window archs get ring caches (window-sized).
"""
from __future__ import annotations

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx import serving_segments
from repro.launch.sharding import logical_axis_size, shard
from .layers import (
    apply_norm,
    apply_rope,
    decode_attention_append,
    dense,
    flash_attention,
    mlp,
    rope_tables,
)
from .moe import init_moe, moe_ffn
from .ssm import (
    init_mamba2,
    init_rwkv6,
    mamba2_block,
    mamba2_empty_carry,
    rwkv6_block,
    rwkv6_empty_carry,
)

# ------------------------------------------------------------------- init --


def _uniform(key, shape, dtype, fan_in):
    lim = fan_in ** -0.5
    return jax.random.uniform(key, shape, dtype, -lim, lim)


def _init_norm(cfg, dtype, d=None):
    d = d or cfg.d_model
    p = {"w": jnp.ones((d,), dtype)}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((d,), dtype)
    return p


def init_attn_layer(key, cfg: ModelConfig, dtype):
    H, KV, dh, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "ln_attn": _init_norm(cfg, dtype),
        "wq": _uniform(ks[0], (D, H * dh), dtype, D),
        "wk": _uniform(ks[1], (D, KV * dh), dtype, D),
        "wv": _uniform(ks[2], (D, KV * dh), dtype, D),
        "wo": _uniform(ks[3], (H * dh, D), dtype, H * dh),
        "ln_mlp": _init_norm(cfg, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"w": jnp.ones((dh,), dtype)}
        p["k_norm"] = {"w": jnp.ones((dh,), dtype)}
    if cfg.n_experts and cfg.family in ("moe",):
        p["moe"] = init_moe(ks[4], D, cfg.d_ff, cfg.n_experts,
                            cfg.n_shared_experts, dtype)
    else:
        p["mlp"] = {
            "w1": _uniform(ks[5], (D, cfg.d_ff), dtype, D),
            "w2": _uniform(ks[6], (cfg.d_ff, D), dtype, cfg.d_ff),
        }
        if cfg.act == "swiglu":
            p["mlp"]["w3"] = _uniform(ks[7], (D, cfg.d_ff), dtype, D)
    return p


# -------------------------------------------------------------- attention --


def _rope_for(cfg: ModelConfig, positions):
    rot = int(cfg.d_head * cfg.partial_rotary)
    rot -= rot % 2
    if cfg.pos_emb != "rope" or rot == 0:
        return None, 0
    cos, sin = rope_tables(positions, rot, cfg.rope_theta,
                           cfg.mrope_sections if cfg.mrope else None)
    return (cos, sin), rot


def _qkv(p, h, cfg: ModelConfig, rope, rot):
    B, S, D = h.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = dense(h, p["wq"], cfg.approx)
    k = dense(h, p["wk"], cfg.approx)
    v = dense(h, p["wv"], cfg.approx)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    if cfg.qk_norm:
        from .layers import rmsnorm
        q = rmsnorm(q, p["q_norm"]["w"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"]["w"], cfg.norm_eps)
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin, rot)
        k = apply_rope(k, cos, sin, rot)
    return q, k, v


def attn_block_train(p, x, cfg: ModelConfig, positions):
    """Full-sequence block (train / prefill). Returns (x', (k, v), aux).

    Attention TP layout: when the KV-head count divides the tensor-parallel
    axis, K/V shard by head (classic TP attention, zero collectives inside
    the block). Otherwise GSPMD would pad KV over the axis and reshard the
    score chunks every step (measured: tens of GiB of all-gathers per layer
    in the backward) — instead we flatten GQA to *query* heads and
    replicate K/V across the axis (Megatron-style KV replication): one
    (B,S,KV,dh) broadcast per layer instead of score-chunk gathers.
    """
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    G = H // KV
    rope, rot = _rope_for(cfg, positions)
    h = apply_norm(x, p["ln_attn"], cfg.norm, cfg.norm_eps, cfg.approx)
    q, k, v = _qkv(p, h, cfg, rope, rot)
    tp = logical_axis_size("kv")
    if KV % tp == 0:
        qs = shard(q.reshape(B, S, KV, G, dh), "batch", None, "kv", None,
                   None)
        ks = shard(k, "batch", None, "kv", None)
        vs = shard(v, "batch", None, "kv", None)
    else:
        # flatten to H query heads; replicate K/V over the model axis
        qs = shard(q.reshape(B, S, H, 1, dh), "batch", None, "heads", None,
                   None)
        ks = shard(jnp.repeat(k, G, axis=2), "batch", None, "heads", None)
        vs = shard(jnp.repeat(v, G, axis=2), "batch", None, "heads", None)
    o = flash_attention(
        qs, ks, vs, causal=True, window=cfg.sliding_window,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
        approx=cfg.approx, unroll=cfg.unroll_scans,
    ).reshape(B, S, H * dh)
    x = x + dense(o, p["wo"], cfg.approx)
    # residual stream carries the "seq" logical axis: binding it to the
    # model axis (sequence parallelism) turns the TP all-reduces into
    # reduce-scatter + all-gather pairs and shards the norm compute
    x = shard(x, "batch", "seq", None)
    h = apply_norm(x, p["ln_mlp"], cfg.norm, cfg.norm_eps, cfg.approx)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        y, aux = moe_ffn(h, p["moe"], top_k=cfg.n_experts_active,
                         capacity_factor=cfg.moe_capacity_factor,
                         approx=cfg.approx)
    else:
        y = mlp(h, p["mlp"], cfg.act, cfg.approx)
    x = x + y
    return shard(x, "batch", "seq", None), (k, v), aux


def decode_slot(cfg: ModelConfig, Smax: int, pos):
    """Cache slot for the token at ``pos`` (ring for sliding-window)."""
    if cfg.sliding_window and Smax <= cfg.sliding_window:
        return pos % Smax
    return pos


def attn_block_decode(p, x, cfg: ModelConfig, cache, pos, positions):
    """Single-token block against a *read-only* cache.

    x: (B,1,D); cache {k,v}: (B,Smax,KV,dh). Returns (x', (k_new, v_new))
    where k_new/v_new are the (B,1,KV,dh) slabs the caller writes into the
    stacked cache buffer (in place via donation) — a decode step's cache
    write is one token, not one cache.
    """
    B, _, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    G = H // KV
    Smax = cache["k"].shape[1]
    rope, rot = _rope_for(cfg, positions)
    h = apply_norm(x, p["ln_attn"], cfg.norm, cfg.norm_eps, cfg.approx)
    q, k, v = _qkv(p, h, cfg, rope, rot)
    ring_full = bool(cfg.sliding_window and Smax <= cfg.sliding_window)
    slot = decode_slot(cfg, Smax, pos)
    o = decode_attention_append(
        q.reshape(B, KV, G, dh), cache["k"], cache["v"], k, v, pos, slot,
        ring_full=ring_full, window=0 if ring_full else cfg.sliding_window,
        approx=cfg.approx,
    ).reshape(B, 1, H * dh)
    x = x + dense(o, p["wo"], cfg.approx)
    h = apply_norm(x, p["ln_mlp"], cfg.norm, cfg.norm_eps, cfg.approx)
    if "moe" in p:
        y, _ = moe_ffn(h, p["moe"], top_k=cfg.n_experts_active,
                       capacity_factor=4.0, approx=cfg.approx)
    else:
        y = mlp(h, p["mlp"], cfg.act, cfg.approx)
    return x + y, (k.astype(cache["k"].dtype), v.astype(cache["v"].dtype))


# ------------------------------------------------------------ layer stack --


def _approx_segments(cfg: ModelConfig):
    """Policy-resolved layer segments for the attention stacks.

    ``((lo, hi, seg_cfg), ...)``: contiguous layer runs whose
    ``ApproxConfig`` resolves identically under ``cfg.approx.policy``
    (see :func:`repro.core.approx.serving_segments`), each paired with a
    ``ModelConfig`` carrying that run's layer-labelled approx config. A
    homogeneous (or absent) policy yields one segment with the original
    ``cfg`` — the scan-over-layers is exactly the pre-policy trace.
    """
    segs = serving_segments(cfg.approx, cfg.n_layers)
    if len(segs) == 1 and segs[0][2] == cfg.approx:
        # no policy (or disabled): the original unlabelled cfg, one scan
        return ((0, cfg.n_layers, cfg),)
    # keep the layer-labelled config even for a single segment: a uniform
    # layer-scoped policy (e.g. a ramp's final rung, or a policy_only
    # assignment covering every layer) still needs cfg.approx.layer set
    # for lookup to resolve its entries
    return tuple((lo, hi, replace(cfg, approx=acfg))
                 for lo, hi, acfg in segs)


@jax.named_scope("model.kv_cache")
def _write_token(buf, i, slot, new):
    """Write one decoded token's (B,1,KV,dh) slab into the stacked
    (L,B,Smax,KV,dh) cache at layer ``i``, seq slot ``slot``.

    Scalar ``slot`` keeps the historical dynamic_update_slice (one
    contiguous in-place write on donated buffers); a (B,) ``slot`` —
    continuous batching, per-row positions — scatters each row at its own
    depth.
    """
    slot = jnp.asarray(slot, jnp.int32)
    i = jnp.asarray(i, jnp.int32)
    if slot.ndim:
        rows = jnp.arange(new.shape[0])
        return buf.at[i, rows, slot].set(new[:, 0])
    zero = jnp.zeros((), jnp.int32)
    at = (i, zero, slot, zero, zero)
    return jax.lax.dynamic_update_slice(buf, new[None], at)


def init_stack(key, cfg: ModelConfig, dtype):
    """Stacked per-layer params (leading L axis) + shared block (hybrid)."""
    L = cfg.n_layers
    if L == 0:                      # analysis variant: embed/head only
        return {"layers": {}}
    keys = jax.random.split(key, L)
    if cfg.family == "ssm":        # rwkv6
        init_one = lambda k: init_rwkv6(k, cfg.d_model,
                                        cfg.d_model // cfg.d_head, cfg.d_ff,
                                        dtype)
    elif cfg.family == "hybrid":   # zamba2: mamba2 backbone
        init_one = lambda k: init_mamba2(k, cfg.d_model, cfg.ssm_state,
                                         cfg.ssm_head_dim, dtype)
    else:
        init_one = lambda k: init_attn_layer(k, cfg, dtype)
    stacked = jax.vmap(init_one)(keys)
    out = {"layers": stacked}
    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(jax.random.fold_in(key, 17))
        out["shared"] = init_attn_layer(k1, cfg, dtype)
        n_inv = cfg.n_layers // cfg.hybrid_period
        r = cfg.hybrid_lora_rank
        D, H, dh = cfg.d_model, cfg.n_heads, cfg.d_head
        ks = jax.random.split(k2, 2 * n_inv)
        out["lora_a"] = jnp.stack(
            [_uniform(ks[2 * i], (D, r), dtype, D) for i in range(n_inv)])
        out["lora_b"] = jnp.stack(
            [jnp.zeros((r, H * dh), dtype) for _ in range(n_inv)])
    return out


def _hybrid_shared(p, x, cfg, positions, i, cache=None, pos=None):
    """Shared attention block with per-invocation LoRA on the q projection.

    Decode mode returns (y, (k_new, v_new)) token slabs like
    :func:`attn_block_decode`."""
    sp = dict(p["shared"])
    la = p["lora_a"][i].astype(x.dtype)
    lb = p["lora_b"][i].astype(x.dtype)
    sp = {**sp, "wq": sp["wq"] + la @ lb if not hasattr(sp["wq"], "q")
          else sp["wq"]}
    if cache is None:
        y, _, aux = attn_block_train(sp, x, cfg, positions)
        return y, aux
    y, new_kv = attn_block_decode(sp, x, cfg, cache, pos, positions)
    return y, new_kv


def stack_train(params, x, cfg: ModelConfig, positions):
    """Run the full layer stack over (B,S,D). Returns (x, aux_losses)."""
    remat = jax.checkpoint if cfg.remat else (lambda f, **kw: f)
    unroll = cfg.unroll_scans

    if cfg.n_layers == 0:
        return x, jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        B = x.shape[0]
        carry0 = rwkv6_empty_carry(B, cfg.d_model,
                                   cfg.d_model // cfg.d_head, x.dtype)

        def body(xc, pl):
            y, _ = remat(rwkv6_block, static_argnums=(3, 4, 5, 6),
                         prevent_cse=False)(pl, xc, carry0,
                                            cfg.d_model // cfg.d_head,
                                            cfg.ssm_chunk, unroll,
                                            cfg.approx)
            return y, None

        x, _ = jax.lax.scan(body, x, params["layers"], unroll=unroll)
        return x, jnp.zeros((), jnp.float32)

    if cfg.family == "hybrid":
        B = x.shape[0]
        carry0 = mamba2_empty_carry(B, cfg.d_model, cfg.ssm_state,
                                    cfg.ssm_head_dim, x.dtype)
        n_groups = cfg.n_layers // cfg.hybrid_period
        aux = jnp.zeros((), jnp.float32)

        def body(xc, pl):
            y, _ = remat(mamba2_block, static_argnums=(3, 4, 5, 6, 7),
                         prevent_cse=False)(pl, xc, carry0, cfg.ssm_state,
                                            cfg.ssm_head_dim, cfg.ssm_chunk,
                                            unroll, cfg.approx)
            return y, None

        for g in range(n_groups):
            group = jax.tree.map(
                lambda a: a[g * cfg.hybrid_period:(g + 1) * cfg.hybrid_period],
                params["layers"])
            x, _ = jax.lax.scan(body, x, group, unroll=unroll)
            x, a = _hybrid_shared(params, x, cfg, positions, g)
            aux = aux + a
        return x, aux

    # attention stacks (dense / moe / vlm / audio): one scan per
    # policy-resolved layer segment (a single scan when the policy is
    # homogeneous or absent)
    def body_for(seg_cfg):
        def body(carry, pl):
            xc, aux = carry
            y, _, a = remat(attn_block_train, static_argnums=(2,),
                            prevent_cse=False)(pl, xc, seg_cfg, positions)
            return (y, aux + a), None
        return body

    carry = (x, jnp.zeros((), jnp.float32))
    for lo, hi, seg_cfg in _approx_segments(cfg):
        part = params["layers"] if (lo, hi) == (0, cfg.n_layers) \
            else jax.tree.map(lambda a: a[lo:hi], params["layers"])
        carry, _ = jax.lax.scan(body_for(seg_cfg), carry, part,
                                unroll=unroll)
    x, aux = carry
    return x, aux


def stack_prefill(params, x, cfg: ModelConfig, positions):
    """Full-sequence forward that also returns the decode cache.

    Attention archs: per-layer K/V stacked (L,B,S,KV,dh). SSM/hybrid: final
    recurrent states per layer. Cache seq length == S (launch/serve.py pads
    into a larger ring/linear cache as needed).
    """
    unroll = cfg.unroll_scans
    if cfg.n_layers == 0:
        # L0 analysis variant: structurally-correct zero-layer cache
        return x, empty_cache(cfg, x.shape[0], x.shape[1], x.dtype)
    if cfg.family == "ssm":
        B = x.shape[0]
        carry0 = rwkv6_empty_carry(B, cfg.d_model,
                                   cfg.d_model // cfg.d_head, x.dtype)

        def body(xc, pl):
            y, c = rwkv6_block(pl, xc, carry0, cfg.d_model // cfg.d_head,
                               cfg.ssm_chunk, unroll, cfg.approx)
            return y, c

        x, states = jax.lax.scan(body, x, params["layers"], unroll=unroll)
        return x, {"ssm": states}

    if cfg.family == "hybrid":
        B = x.shape[0]
        carry0 = mamba2_empty_carry(B, cfg.d_model, cfg.ssm_state,
                                    cfg.ssm_head_dim, x.dtype)
        n_groups = cfg.n_layers // cfg.hybrid_period

        def body(xc, pl):
            y, c = mamba2_block(pl, xc, carry0, cfg.ssm_state,
                                cfg.ssm_head_dim, cfg.ssm_chunk, unroll,
                                cfg.approx)
            return y, c

        ssm_parts, kparts, vparts = [], [], []
        for g in range(n_groups):
            sl = slice(g * cfg.hybrid_period, (g + 1) * cfg.hybrid_period)
            group = jax.tree.map(lambda a: a[sl], params["layers"])
            x, states = jax.lax.scan(body, x, group, unroll=unroll)
            ssm_parts.append(states)
            sp = dict(params["shared"])
            la = params["lora_a"][g].astype(x.dtype)
            lb = params["lora_b"][g].astype(x.dtype)
            if not isinstance(sp["wq"], dict):
                sp = {**sp, "wq": sp["wq"] + la @ lb}
            x, (k, v), _ = attn_block_train(sp, x, cfg, positions)
            kparts.append(k)
            vparts.append(v)
        return x, {
            "ssm": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                *ssm_parts),
            "k": jnp.stack(kparts).astype(x.dtype),
            "v": jnp.stack(vparts).astype(x.dtype),
        }

    def body_for(seg_cfg):
        def body(xc, pl):
            y, kv, _ = attn_block_train(pl, xc, seg_cfg, positions)
            return y, kv
        return body

    kparts, vparts = [], []
    for lo, hi, seg_cfg in _approx_segments(cfg):
        part = params["layers"] if (lo, hi) == (0, cfg.n_layers) \
            else jax.tree.map(lambda a: a[lo:hi], params["layers"])
        x, (ks, vs) = jax.lax.scan(body_for(seg_cfg), x, part,
                                   unroll=unroll)
        kparts.append(ks)
        vparts.append(vs)
    ks = kparts[0] if len(kparts) == 1 else jnp.concatenate(kparts, 0)
    vs = vparts[0] if len(vparts) == 1 else jnp.concatenate(vparts, 0)
    return x, {"k": ks.astype(x.dtype), "v": vs.astype(x.dtype)}


# ----------------------------------------------------------------- caches --


def empty_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    """Decode cache pytree (stacked over layers)."""
    KV, dh, L = cfg.n_kv_heads, cfg.d_head, cfg.n_layers
    if cfg.family == "ssm":
        c = rwkv6_empty_carry(batch, cfg.d_model, cfg.d_model // cfg.d_head,
                              dtype)
        return {"ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), c)}
    S = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    kv = {
        "k": jnp.zeros((L, batch, S, KV, dh), dtype),
        "v": jnp.zeros((L, batch, S, KV, dh), dtype),
    }
    if cfg.family == "hybrid":
        c = mamba2_empty_carry(batch, cfg.d_model, cfg.ssm_state,
                               cfg.ssm_head_dim, dtype)
        n_inv = cfg.n_layers // cfg.hybrid_period
        return {
            "ssm": jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), c),
            "k": jnp.zeros((n_inv, batch, S, KV, dh), dtype),
            "v": jnp.zeros((n_inv, batch, S, KV, dh), dtype),
        }
    return kv


def stack_decode(params, x, cfg: ModelConfig, cache, pos, positions):
    """One-token decode through the stack. x: (B,1,D)."""
    unroll = cfg.unroll_scans
    if cfg.n_layers == 0:
        return x, cache
    if cfg.family == "ssm":
        def body(xc, pl_cache):
            pl, c = pl_cache
            y, c2 = rwkv6_block(pl, xc, c, cfg.d_model // cfg.d_head, 1,
                                approx=cfg.approx)
            return y, c2

        x, new_ssm = jax.lax.scan(body, x, (params["layers"], cache["ssm"]),
                                  unroll=unroll)
        return x, {"ssm": new_ssm}

    if cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.hybrid_period
        Smax = cache["k"].shape[2]
        slot = decode_slot(cfg, Smax, pos)

        def body(xc, pl_cache):
            pl, c = pl_cache
            y, c2 = mamba2_block(pl, xc, c, cfg.ssm_state, cfg.ssm_head_dim,
                                 1, approx=cfg.approx)
            return y, c2

        kc, vc = cache["k"], cache["v"]
        new_ssm_parts = []
        for g in range(n_groups):
            sl = slice(g * cfg.hybrid_period, (g + 1) * cfg.hybrid_period)
            group = jax.tree.map(lambda a: a[sl], params["layers"])
            cgroup = jax.tree.map(lambda a: a[sl], cache["ssm"])
            x, c2 = jax.lax.scan(body, x, (group, cgroup), unroll=unroll)
            new_ssm_parts.append(c2)
            with jax.named_scope("model.kv_cache"):
                kv = {"k": kc[g], "v": vc[g]}
            x, (k_new, v_new) = _hybrid_shared(params, x, cfg, positions, g,
                                               cache=kv, pos=pos)
            kc = _write_token(kc, g, slot, k_new)
            vc = _write_token(vc, g, slot, v_new)
        new_cache = {
            "ssm": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                *new_ssm_parts),
            "k": kc,
            "v": vc,
        }
        return x, new_cache

    # attention archs: carry the stacked cache and write one token per
    # layer in place (donated buffer) — the scan's xs are only the params.
    # One scan per policy-resolved layer segment (single scan when the
    # policy is homogeneous or absent); each segment scans its own slice
    # of the stacked cache so layer indices stay segment-local.
    Smax = cache["k"].shape[2]
    slot = decode_slot(cfg, Smax, pos)

    def body_for(seg_cfg):
        def body(carry, pl_i):
            xc, kc, vc = carry
            pl, i = pl_i
            with jax.named_scope("model.kv_cache"):
                layer_cache = {
                    "k": jax.lax.dynamic_index_in_dim(kc, i, 0,
                                                      keepdims=False),
                    "v": jax.lax.dynamic_index_in_dim(vc, i, 0,
                                                      keepdims=False),
                }
            y, (k_new, v_new) = attn_block_decode(pl, xc, seg_cfg,
                                                  layer_cache, pos, positions)
            kc = _write_token(kc, i, slot, k_new)
            vc = _write_token(vc, i, slot, v_new)
            return (y, kc, vc), None
        return body

    segs = _approx_segments(cfg)
    if len(segs) == 1:
        (x, kc, vc), _ = jax.lax.scan(
            body_for(segs[0][2]), (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)), unroll=unroll)
        return x, {"k": kc, "v": vc}
    kparts, vparts = [], []
    for lo, hi, seg_cfg in segs:
        part = jax.tree.map(lambda a: a[lo:hi], params["layers"])
        (x, kc, vc), _ = jax.lax.scan(
            body_for(seg_cfg), (x, cache["k"][lo:hi], cache["v"][lo:hi]),
            (part, jnp.arange(hi - lo)), unroll=unroll)
        kparts.append(kc)
        vparts.append(vc)
    return x, {"k": jnp.concatenate(kparts, 0),
               "v": jnp.concatenate(vparts, 0)}
