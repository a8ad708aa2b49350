"""Plain reference forward of a dense decoder (Llama / SmolLM / Qwen3 family)
with SIMDive arithmetic in its linears and in the softmax divide.

Teacher-forced and uncached: one causal pass over whole sequences, layer by
layer, in straightforward ``jnp`` with float32 matmuls at ``highest``
precision. It follows the published architecture (RMSNorm, rotary position
embedding on the two halves of each head, grouped-query attention, optional
per-head RMSNorm of q and k, SwiGLU MLP, tied or separate output head) and
the configuration's arithmetic:

* every projection of the decoder layers is an emulated SIMDive matmul: the
  bf16 activation and the f32 weight are quantized to ``width``-bit
  sign-magnitude codes (one scale per activation row, one per weight column),
  the code products come from :mod:`bench.references.simdive`, and the int32
  sum is scaled back;
* the softmax normalization ``acc / l`` goes through the SIMDive divider: each
  row of ``|acc|`` and its ``l`` share a power-of-two scale that puts the
  larger into the top bits of the divider lane, and the quotient keeps
  ``frac_out`` fraction bits;
* the output head is an exact float32 matmul.

Activations are rounded to bfloat16 wherever the configuration's dtype puts
them. The activation scale: the program scales each activation by the
maximum over the whole batch of its call, so a request's arithmetic depends
on the requests that shared its calls. :func:`cohort_gaps` follows that
where the rows of every call are known (a cohort admitted, decoded and
retired together): one scale over the cohort's prompt rows, one over the
rows of each decode position. :func:`served_gaps` gives each token row its
own scale, for traffic whose calls mix requests at different depths (and
the admissions' padding rows), which a teacher-forced pass cannot replay.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from .simdive import Lane, divide, emulated_matmul

BF16 = jnp.bfloat16
F32 = jnp.float32


@dataclass(frozen=True)
class Shape:
    """The sizes the reference needs, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    qk_norm: bool
    tied: bool

    @classmethod
    def from_hf(cls, c: dict) -> "Shape":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   d_head=c.get("head_dim",
                                c["hidden_size"] // c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   qk_norm=bool(c.get("qk_norm", False)),
                   tied=bool(c["tie_word_embeddings"]))


@dataclass(frozen=True)
class Arithmetic:
    """The lanes of the linears and of the softmax divider."""
    mul: Lane
    div: Lane
    frac_out: int

    @classmethod
    def from_config(cls, a: dict) -> "Arithmetic":
        cb = a["coeff_bits"]
        return cls(mul=Lane(a["width"], cb, a["index_bits"]),
                   div=Lane(a["div_width"], cb, a["index_bits"]),
                   frac_out=a["frac_out"])


def _rmsnorm(x, w, eps):
    xf = x.astype(F32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * inv * w.astype(F32)).astype(BF16)


def _linear(x, w, lane: Lane, groups=None, n_groups: int = 0):
    """x (M, K) bf16 @ w (K, N) f32 through the emulated SIMDive matmul.
    One activation scale per row, or per group of rows (``groups`` (M,)
    ids below ``n_groups``)."""
    qmax = float(2 ** lane.width - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)           # bf16
    if groups is not None:
        amax = jax.ops.segment_max(amax[:, 0], groups,
                                   num_segments=n_groups)[groups][:, None]
    sx = jnp.maximum(amax, 1e-30) / qmax                          # bf16
    qx = jnp.clip(jnp.round(jnp.abs(x) / sx), 0, qmax).astype(jnp.int32)
    wmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    sw = jnp.maximum(wmax, 1e-30) / qmax
    qw = jnp.clip(jnp.round(jnp.abs(w) / sw), 0, qmax).astype(jnp.int32)
    acc = emulated_matmul(qx, jnp.where(x < 0, -1, 1).astype(jnp.int32),
                          qw, jnp.where(w < 0, -1, 1).astype(jnp.int32), lane)
    return (acc.astype(F32) * (sx.astype(F32) * sw)).astype(BF16)


def _rope(x, pos, theta):
    """Rotate the two halves of each head of x (B, S, H, dh)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None] * inv                         # (B,S,half)
    c = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    s = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _softmax_divide(acc, l, ar: Arithmetic):
    """acc (..., dh) f32 over l (...,) through the SIMDive divider."""
    w = ar.div.width
    num = jnp.abs(acc)
    den = jnp.maximum(l, 1e-30)[..., None]
    top = jnp.maximum(jnp.max(num, axis=-1, keepdims=True), den)
    scale = jnp.exp2((w - 2) - jnp.floor(jnp.log2(top)))
    lim = float(2 ** w - 1)
    qn = jnp.clip(jnp.round(num * scale), 0, lim).astype(jnp.int32)
    qd = jnp.clip(jnp.round(den * scale), 1, lim).astype(jnp.int32)
    q = divide(qn, jnp.broadcast_to(qd, qn.shape), ar.div, ar.frac_out)
    out = q.astype(F32) * (2.0 ** -ar.frac_out)
    return jnp.where(acc < 0, -out, out)


def _layer(x, p, pos, groups, shape: Shape, ar: Arithmetic, n_groups: int):
    B, S, D = x.shape
    H, KV, dh = shape.heads, shape.kv_heads, shape.d_head
    g_rows = None if groups is None else groups.reshape(B * S)
    lin = lambda h, w: _linear(h.reshape(B * S, -1), w, ar.mul, g_rows,
                               n_groups).reshape(B, S, -1)
    h = _rmsnorm(x, p["ln_attn"]["w"], shape.eps)
    q = lin(h, p["wq"]).reshape(B, S, H, dh)
    k = lin(h, p["wk"]).reshape(B, S, KV, dh)
    v = lin(h, p["wv"]).reshape(B, S, KV, dh)
    if shape.qk_norm:
        q = _rmsnorm(q, p["q_norm"]["w"], shape.eps)
        k = _rmsnorm(k, p["k_norm"]["w"], shape.eps)
    q = _rope(q, pos, shape.rope_theta)
    k = _rope(k, pos, shape.rope_theta)
    g = H // KV
    kr = jnp.repeat(k, g, axis=2).astype(F32)
    vr = jnp.repeat(v, g, axis=2).astype(F32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), kr) * dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    acc = jnp.einsum("bhqk,bkhd->bhqd", e, vr)
    o = _softmax_divide(acc, jnp.sum(e, axis=-1), ar)
    o = o.astype(BF16).transpose(0, 2, 1, 3).reshape(B, S, H * dh)
    x = x + lin(o, p["wo"])
    h = _rmsnorm(x, p["ln_mlp"]["w"], shape.eps)
    m = p["mlp"]
    y = jax.nn.silu(lin(h, m["w1"])) * lin(h, m["w3"])
    return x + lin(y, m["w2"])


def forward(weights, tokens, shape: Shape, ar: Arithmetic, groups=None):
    """Logits (B, S, vocab) in float32 for token ids (B, S), every position.
    ``groups`` (B, S): the activation-scale group of each token row (None:
    a scale per row).

    Layer by layer, one compiled layer reused: only one layer's
    intermediates are alive at a time.
    """
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        n_groups = 0 if groups is None else int(np.max(groups)) + 1
        x = _embed(weights["embed"], tokens)
        layers = weights["stack"]["layers"]
        for i in range(shape.layers):
            p = jax.tree.map(lambda a: a[i], layers)
            x = _layer_jit(x, p, pos, groups, shape, ar, n_groups)
        return _head_jit(x, weights, shape)


@jax.jit
def _embed(table, tokens):
    return table[0][tokens].astype(BF16)


_layer_jit = jax.jit(_layer, static_argnums=(4, 5, 6))


def _head(x, weights, shape: Shape):
    x = _rmsnorm(x, weights["final_norm"]["w"], shape.eps).astype(F32)
    w = (weights["embed"][0].T if shape.tied else weights["head"][0])
    return x @ w.astype(F32)


_head_jit = jax.jit(_head, static_argnums=(2,))


def served_gaps(weights, seqs, prompt_lens, shape: Shape, ar: Arithmetic,
                block_len: int = 128, max_rows: int = 2048):
    """For each sequence (prompt + served tokens), the gap by which each
    served token's reference logit lies below the reference's best at that
    position. Returns a list of 1-D numpy arrays, one per sequence.

    Sequences are right-padded to a multiple of ``block_len`` (causal
    attention keeps padding out of every real position) and run in batches
    of one padded length and at most ``max_rows`` token rows.
    """
    by_len: dict[int, list[int]] = {}
    for i, s in enumerate(seqs):
        L = -(-len(s) // block_len) * block_len
        by_len.setdefault(L, []).append(i)
    batches = []
    for L, idx in sorted(by_len.items()):
        per = max(1, max_rows // L)
        batches += [(L, idx[j:j + per]) for j in range(0, len(idx), per)]
    out: list = [None] * len(seqs)
    for L, idx in batches:
        _gaps_into(out, weights, seqs, prompt_lens, idx, L, shape, ar)
    return out


def cohort_gaps(weights, seqs, prompt_lens, cohorts, shape: Shape,
                ar: Arithmetic, block_len: int = 128):
    """As :func:`served_gaps`, with the program's activation scale: each of
    ``cohorts`` (lists of indices into ``seqs``, sequences of one prompt
    length and one length, admitted and decoded together) runs as one
    batch, with one scale over all its prompt rows (the admission's
    prefill) and one over the rows of each later position (a decode step)."""
    out: list = [None] * len(seqs)
    for idx in cohorts:
        P, n = prompt_lens[idx[0]], len(seqs[idx[0]])
        if any(prompt_lens[i] != P or len(seqs[i]) != n for i in idx):
            raise ValueError("a cohort's sequences differ in length")
        L = -(-n // block_len) * block_len
        t = np.arange(L)
        groups = np.broadcast_to(np.where(t < P, 0, 1 + t - P),
                                 (len(idx), L)).astype(np.int32)
        _gaps_into(out, weights, seqs, prompt_lens, idx, L, shape, ar,
                   jnp.asarray(groups))
    return out


def _gaps_into(out, weights, seqs, prompt_lens, idx, L, shape, ar,
               groups=None):
    toks = np.zeros((len(idx), L), np.int32)
    for r, i in enumerate(idx):
        toks[r, :len(seqs[i])] = seqs[i]
    logits = np.asarray(forward(weights, jnp.asarray(toks), shape, ar,
                                groups))
    for r, i in enumerate(idx):
        s, p = np.asarray(seqs[i]), prompt_lens[i]
        # the token at position t was served from the logits at t - 1
        lg = logits[r, p - 1:len(s) - 1]
        out[i] = lg.max(-1) - lg[np.arange(len(lg)), s[p:]]
