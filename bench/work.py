"""Operations and bytes of the model's calls, computed from their shapes.

The yardstick for ``step.mfu`` and ``kernels_roofline``. Each call counts at
the shapes it was given, without any kernel's tile padding:

* an emulated SIMDive matmul of (M, K) x (K, N) is M*K*N products, 2 ops
  each at the int8 peak (an exact int8 matmul of those shapes is the least
  the chip could take); its operands cross HBM as 1-byte codes, its output
  as bf16;
* attention is 4 * heads * d_head flops per (query, key) pair it needs
  (causal: key <= query), at the bf16 peak; q, k, v and the output cross
  HBM in bf16;
* the output head is an exact bf16 matmul of the last position per row.
"""
from __future__ import annotations


def sizes(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    dh = c.get("head_dim", D // H)
    return dict(L=c["num_hidden_layers"], D=D, H=H,
                KV=c["num_key_value_heads"], dh=dh,
                F=c["intermediate_size"], V=c["vocab_size"])


def _linears(s):
    """(K, N) of each emulated matmul of one layer."""
    D, H, KV, dh, F = s["D"], s["H"], s["KV"], s["dh"], s["F"]
    return [(D, H * dh), (D, KV * dh), (D, KV * dh), (H * dh, D),
            (D, F), (D, F), (F, D)]


def layer_params(s) -> int:
    return sum(k * n for k, n in _linears(s))


def _linear_cost(s, M):
    prods = sum(M * k * n for k, n in _linears(s)) * s["L"]
    byts = sum(M * k + k * n + 2 * M * n for k, n in _linears(s)) * s["L"]
    return prods, byts


def _head_cost(s, rows):
    return 2 * rows * s["D"] * s["V"], 2 * (s["D"] * s["V"]
                                            + rows * (s["D"] + s["V"]))


def prefill(s, rows: int, P: int) -> dict:
    """One prefill of ``rows`` prompts of length P."""
    prods, byts = _linear_cost(s, rows * P)
    pairs = rows * P * (P + 1) // 2
    attn = 4 * s["H"] * s["dh"] * pairs * s["L"]
    qkvo = 2 * rows * P * s["dh"] * (2 * s["H"] + 2 * s["KV"]) * s["L"]
    hf, hb = _head_cost(s, rows)
    return dict(products=prods, flops=attn + hf, bytes=byts + qkvo + hb)


def decode(s, rows: int, ctx: list) -> dict:
    """One decode step of ``rows`` rows; ``ctx`` are the keys each live row
    attends to (a row without a request attends to one)."""
    prods, byts = _linear_cost(s, rows)
    keys = sum(ctx) + (rows - len(ctx))
    attn = 4 * s["H"] * s["dh"] * keys * s["L"]
    kv = 2 * keys * 2 * s["KV"] * s["dh"] * s["L"]
    hf, hb = _head_cost(s, rows)
    return dict(products=prods, flops=attn + hf, bytes=byts + kv + hb)


def least_seconds(cost: dict, peak: dict) -> float:
    compute = (2 * cost["products"] / peak["int8_ops_s"]
               + cost["flops"] / peak["bf16_flops_s"])
    return max(compute, cost["bytes"] / peak["hbm_bytes_s"])


def window_least_seconds(s, window, mix, peak) -> float:
    """Sum over the window's ticks of the least time of each call."""
    total = 0.0
    for t in window.ticks:
        if t.admitted:
            total += least_seconds(prefill(s, mix.batch, mix.prompt_len),
                                   peak)
        total += least_seconds(decode(s, mix.batch, t.decode_ctx), peak)
    return total


def linear_least_seconds(s, window, mix, peak) -> float:
    """Sum over the window's ticks of the least time of each call's
    emulated matmuls alone (what the logmatmul kernel is given)."""
    total = 0.0
    for t in window.ticks:
        rows = [mix.batch] + ([mix.batch * mix.prompt_len] if t.admitted
                              else [])
        for M in rows:
            prods, byts = _linear_cost(s, M)
            total += least_seconds({"products": prods, "flops": 0,
                                    "bytes": byts}, peak)
    return total


def useful_flops(s, window, mix) -> float:
    """Model flops of the window's useful work: each admitted prompt (its
    tokens through every layer, the head once) and each decoded token; the
    batch's padding rows are not counted."""
    lin = 2 * layer_params(s) * s["L"]
    head = 2 * s["D"] * s["V"]
    per_pair = 4 * s["H"] * s["dh"] * s["L"]
    P = mix.prompt_len
    total = 0.0
    for t in window.ticks:
        total += t.admitted * (P * lin + head
                               + per_pair * P * (P + 1) // 2)
        total += sum(lin + head + per_pair * c for c in t.decode_ctx)
    return total
