"""Public model API: build(cfg) -> LM with init / loss / prefill / decode.

Batch dict convention (all optional fields present only when used):
  tokens      (B,S) int32            [(B,S,C) for musicgen codebooks]
  labels      same shape as tokens
  positions   (B,S) int32 or (B,S,3) for M-RoPE; defaults to arange
  patch_embeds (B,P,D) bf16          vlm stub: precomputed patch embeddings
  patch_mask  (B,S) bool             True where the sequence slot is a patch
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard
from .layers import apply_norm, dense
from .loss import mean_xent
from .transformer import (
    empty_cache,
    init_stack,
    stack_decode,
    stack_prefill,
    stack_train,
)


def _dt(name):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


@dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ------------------------------------------------------------- params --
    def init(self, key) -> dict:
        cfg = self.cfg
        pdt = _dt(cfg.param_dtype)
        k_emb, k_stack, k_head, k_fin = jax.random.split(key, 4)
        params: dict[str, Any] = {}
        lim = cfg.d_model ** -0.5
        n_emb = max(cfg.n_codebooks, 1)
        params["embed"] = jax.random.normal(
            k_emb, (n_emb, cfg.vocab_size, cfg.d_model), pdt) * lim
        params["stack"] = init_stack(k_stack, cfg, pdt)
        params["final_norm"] = {"w": jnp.ones((cfg.d_model,), pdt)}
        if cfg.norm == "layernorm":
            params["final_norm"]["b"] = jnp.zeros((cfg.d_model,), pdt)
        if not cfg.tie_embeddings:
            params["head"] = jax.random.uniform(
                k_head, (n_emb, cfg.d_model, cfg.vocab_size), pdt, -lim, lim)
        return params

    # -------------------------------------------------------------- embed --
    @jax.named_scope("model.embed")
    def _embed(self, params, batch):
        cfg = self.cfg
        adt = _dt(cfg.dtype)
        tokens = batch["tokens"]
        if cfg.n_codebooks:
            # musicgen: sum the codebook embeddings
            x = sum(
                params["embed"][c].astype(adt)[tokens[..., c]]
                for c in range(cfg.n_codebooks)
            )
        else:
            x = params["embed"][0].astype(adt)[tokens]
        if cfg.vision_stub and "patch_embeds" in batch:
            # merge precomputed patch embeddings at masked positions
            B, S, D = x.shape
            pe = batch["patch_embeds"].astype(adt)
            n_p = pe.shape[1]
            pad = jnp.zeros((B, S - n_p, D), adt)
            pe_full = jnp.concatenate([pe, pad], axis=1)
            x = jnp.where(batch["patch_mask"][..., None], pe_full, x)
        if cfg.pos_emb == "sin":
            S = x.shape[1]
            pos = batch.get("positions")
            pos = jnp.arange(S)[None] if pos is None else pos
            half = cfg.d_model // 2
            inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
            ang = pos.astype(jnp.float32)[..., None] * inv
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
            x = x + pe.astype(adt)
        return shard(x, "batch", "seq", None)

    def _positions(self, batch, S, offset=0):
        pos = batch.get("positions")
        if pos is None:
            B = batch["tokens"].shape[0]
            pos = jnp.broadcast_to(jnp.arange(S)[None] + offset, (B, S))
        return pos

    @jax.named_scope("model.head")
    def _head(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            w = params["embed"].transpose(0, 2, 1)
        else:
            w = params["head"]
        outs = [dense(x, w[c]) for c in range(max(cfg.n_codebooks, 1))]
        logits = jnp.stack(outs, axis=-2) if cfg.n_codebooks else outs[0]
        return shard(logits, "batch", None, "vocab") if not cfg.n_codebooks \
            else shard(logits, "batch", None, None, "vocab")

    # --------------------------------------------------------------- loss --
    def train_loss(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch, x.shape[1])
        x, aux = stack_train(params["stack"], x, cfg, positions)
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = self._head(params, x)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if cfg.n_codebooks:
            loss = sum(
                mean_xent(logits[..., c, :], labels[..., c], mask)
                for c in range(cfg.n_codebooks)
            ) / cfg.n_codebooks
        else:
            loss = mean_xent(logits, labels, mask)
        return loss + 0.01 * aux

    def logits(self, params, batch):
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch, x.shape[1])
        x, _ = stack_train(params["stack"], x, cfg, positions)
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        return self._head(params, x)

    # -------------------------------------------------------------- serve --
    def empty_cache(self, batch_size: int, max_seq: int):
        return empty_cache(self.cfg, batch_size, max_seq, _dt(self.cfg.dtype))

    @partial(jax.jit, static_argnums=(0,))
    def prefill(self, params, batch):
        """Prompt forward pass; returns (last-token logits, decode cache).

        The cache covers exactly the prompt length S; launch/serve.py embeds
        it into a larger linear/ring cache before decoding continues.
        """
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = self._positions(batch, x.shape[1])
        x, cache = stack_prefill(params["stack"], x, cfg, positions)
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = self._head(params, x[:, -1:])
        return logits[:, 0], cache

    @partial(jax.jit, static_argnums=(0,))
    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B,) [(B,C) musicgen] int32; pos: int32 (0-based) —
        scalar, or (B,) for per-row positions (continuous batching: every
        cache slot decodes at its own depth; attention-family archs only).

        Returns (logits (B,V) [(B,C,V)], new_cache).
        """
        cfg = self.cfg
        tok = tokens[:, None] if tokens.ndim == 1 else tokens[:, None, :]
        B = tok.shape[0]
        pos_arr = jnp.asarray(pos, jnp.int32)
        positions = pos_arr[:, None] if pos_arr.ndim else \
            jnp.broadcast_to(pos_arr[None, None], (B, 1))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (B, 1, 3))
        batch = {"tokens": tok, "positions": positions}
        x = self._embed(params, batch)
        x, new_cache = stack_decode(params["stack"], x, cfg, cache, pos,
                                    positions)
        x = apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = self._head(params, x)
        return logits[:, 0], new_cache


def build(cfg: ModelConfig) -> LM:
    return LM(cfg)
