"""Model-facing approximate math: SIMDive inside linear / softmax / norm.

This is the layer that carries the paper's arithmetic into real networks:

* ``quantize_sign_magnitude`` — the 8-bit fixed-point quantization of the
  paper's ANN experiment (§4.3), sign-magnitude because the log datapath is
  unsigned (signs are XORed outside, as in every log-domain multiplier).
* ``approx_matmul`` — matmul whose scalar products are SIMDive products,
  K-chunked so the (M, Kc, N) product tensor stays small; exact-float
  gradients via ``custom_vjp`` (straight-through), so QAT and the paper's
  "train float / infer approx" flow both work.
* ``approx_softmax`` — softmax whose normalization uses the SIMDive
  *divider* (the paper's division use-case: TPUs have no fast divide).
* ``approx_rmsnorm`` — beyond-paper: log-domain rsqrt (L >> 1) feeding the
  divider for the RMSNorm denominator.

Every approximate op here dispatches through the kernel registry
(:func:`repro.kernels.registry.get_op`) — the same entry point the
benchmarks and examples use — so a model forward pass can be served by the
bit-exact reference (``backend='ref'``, the default: identical numerics to
the historical in-module emulation) or by the Pallas kernels
(``backend='pallas'``/``'auto'``) without touching model code. Caveat for
the kernel backends: the emulated matmul's Pallas path accumulates in
int32 (exact for width 8 with K < 2^15; tested bit-equal to ref in that
range) — the int64 ``ref`` path remains the accuracy-study oracle for
wider lanes / deeper reductions.

``ApproxConfig.mode``:
  'exact'    — plain float ops (baseline),
  'mitchell' — uncorrected log arithmetic (paper's Mitchell baseline),
  'simdive'  — corrected + rounded (the paper's contribution).

``ApproxConfig.policy`` / ``.layer`` plug the accuracy-budget autotuner
in: a :class:`repro.tuning.TuningPolicy` (any hashable ``.lookup(op,
layer)`` provider) resolves the concrete ``(width, coeff_bits,
index_bits, backend)`` per logical op — 'matmul' for the linears, 'div'
for softmax/rmsnorm denominators — at dispatch time via
:meth:`ApproxConfig.resolve`, layer-scoped entries first. No policy (or
no matching entry) falls back to the config's own knobs, so existing
call sites are untouched.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.registry import get_op
from .mitchell import lane_max_float, work_dtype
from .simdive import SimdiveSpec

__all__ = [
    "ApproxConfig",
    "quantize_sign_magnitude",
    "approx_matmul",
    "approx_matmul_int8",
    "approx_softmax",
    "approx_rmsnorm",
    "attention_div",
    "layer_label",
    "serving_segments",
]


@dataclass(frozen=True)
class ApproxConfig:
    mode: str = "exact"            # exact | mitchell | simdive
    width: int = 8                 # multiplier lane width
    div_width: int = 16            # divider lane width (32 needs jax x64)
    coeff_bits: int = 6
    index_bits: int = 3
    frac_out: int = 15             # divider fixed-point output bits
    k_chunk: int = 128             # matmul K-chunk (bounds the 3D product)
    emulate: bool = True           # bit-exact SIMDive emulation in linears
    backend: str = "ref"           # kernel backend: 'ref' (bit-exact seed
    #                                semantics) | 'pallas' | 'auto' | ...
    use_in_linear: bool = True
    use_in_softmax: bool = True
    use_in_norm: bool = False
    # an optional repro.tuning.TuningPolicy (any hashable object with
    # .lookup(op, layer) returning width/coeff_bits/index_bits/backend
    # attributes): per-op dispatch configs resolved at call time, so a
    # budget-selected policy drives every knob without model-code edits
    policy: object | None = None
    layer: str | None = None       # layer label for policy lookup
    # policy_only: approximate ONLY where the policy carries a matching
    # entry (layer-scoped or op default); call sites whose lookup misses
    # run exact instead of falling back to this config's own knobs. This
    # is how a per-layer sensitivity assignment leaves unprofiled layers
    # untouched (see repro.tuning.sensitivity.train_run_metric).
    policy_only: bool = False
    # backward: 'exact' keeps the straight-through custom_vjp (grads flow
    # through the exact einsum while the forward runs SIMDive — the QAT
    # default); 'approx' emulates approximate *backward* matmuls too: both
    # grad GEMMs (dL/dx, dL/dw) run the same quantize + SIMDive emulated
    # matmul as the forward (see repro/train/).
    backward: str = "exact"
    # guarded dispatch: every get_op below validates concrete outputs and
    # raises registry.GuardTripped on violation (see kernels/README.md
    # "Robustness"). Off by default: guards read outputs back to host, so
    # they are for eager/campaign paths — jitted serving uses the
    # scheduler watchdog instead.
    guard: bool = False

    def __post_init__(self):
        if self.backward not in ("exact", "approx"):
            raise ValueError(f"backward must be 'exact' or 'approx', "
                             f"got {self.backward!r}")

    @property
    def enabled(self) -> bool:
        return self.mode != "exact"

    def active_for(self, op: str) -> bool:
        """Whether approximation applies to logical ``op`` at this layer.

        Always true when enabled, unless ``policy_only`` is set — then
        only where the policy resolves a matching entry (layer-scoped
        first, then the op default). Dispatch sites consult this before
        quantizing, so a ``policy_only`` config runs every unassigned
        layer bit-exact rather than on the config's fallback knobs.
        """
        if not self.enabled:
            return False
        if not self.policy_only:
            return True
        return (self.policy is not None
                and self.policy.lookup(op, self.layer) is not None)

    def spec(self, width: int | None = None) -> SimdiveSpec:
        w = self.width if width is None else width
        if self.mode == "mitchell":
            return SimdiveSpec(width=w, coeff_bits=0, index_bits=self.index_bits,
                               round_output=False)
        return SimdiveSpec(width=w, coeff_bits=self.coeff_bits,
                           index_bits=self.index_bits, round_output=True)

    def resolve(self, op: str, width: int | None = None
                ) -> tuple[SimdiveSpec, str]:
        """(spec, backend) serving logical ``op`` on this config's layer.

        A matching policy entry — layer-scoped first, then the op's
        default — overrides the config's own knobs wholesale (width,
        coeff_bits, index_bits, backend); without one (or without a
        policy) the config's fields stand, exactly the pre-policy
        behavior. ``width`` only steers the fallback (e.g. ``div_width``
        for divider call sites).
        """
        entry = self.policy.lookup(op, self.layer) \
            if self.policy is not None else None
        if entry is None:
            return self.spec(width), self.backend
        spec = SimdiveSpec(width=entry.width, coeff_bits=entry.coeff_bits,
                           index_bits=entry.index_bits)
        return spec, (getattr(entry, "backend", None) or self.backend)

    def resolve_attention(self) -> tuple[SimdiveSpec, str, int]:
        """(spec, backend, frac_out) serving the attention softmax divider.

        Like :meth:`resolve` for the logical ``'attention'`` op, plus the
        divider's fixed-point output bits: a policy entry carrying
        ``frac_out`` overrides the config's ``frac_out`` knob, so a
        ``simdive-policy/v1`` JSON pins the whole attention divider — width,
        coeff_bits, index_bits, backend *and* frac_out — per layer.
        """
        spec, backend = self.resolve("attention", self.div_width)
        entry = self.policy.lookup("attention", self.layer) \
            if self.policy is not None else None
        frac = self.frac_out
        if entry is not None and getattr(entry, "frac_out", None):
            frac = int(entry.frac_out)
        return spec, backend, frac


EXACT = ApproxConfig()


def layer_label(i: int) -> str:
    """Canonical policy label of transformer layer ``i`` (``'L0'``...).

    The serving stack resolves layer-scoped policy entries against these
    labels, so a ``simdive-policy/v1`` file targets a decoder layer with
    ``layer='L3'`` the same way the ANN path targets ``layer='fc0'``.
    """
    return f"L{i}"


def _resolution_sig(cfg: ApproxConfig) -> tuple:
    """Everything policy resolution can change for one layer, hashable."""
    spec_a, backend_a, frac = cfg.resolve_attention()
    return (cfg.resolve("matmul"), cfg.resolve("div", cfg.div_width),
            spec_a, backend_a, frac,
            # policy_only flips per-layer *enablement*, not just the spec
            tuple(cfg.active_for(op)
                  for op in ("matmul", "div", "attention")))


def serving_segments(approx: ApproxConfig, n_layers: int
                     ) -> tuple[tuple[int, int, ApproxConfig], ...]:
    """Contiguous layer runs with identical policy resolution.

    Returns ``((lo, hi, cfg), ...)`` covering ``[0, n_layers)``; each
    ``cfg`` carries ``layer=layer_label(lo)`` so every dispatch inside the
    run resolves to that run's policy entries. Without a policy (or with
    one whose entries are all op-defaults) this collapses to a single
    segment carrying the original config — the scan-over-layers stays one
    scan, exactly the pre-policy trace. The segment tuple is static under
    jit (ApproxConfig is hashable), so a heterogeneous policy costs one
    scan per *distinct-config run*, not one per layer.
    """
    if n_layers <= 0:
        return ((0, max(n_layers, 0), approx),)
    if approx.policy is None or not approx.enabled:
        # exact mode ignores every resolved entry — one segment, one scan
        return ((0, n_layers, approx),)
    cfgs = [replace(approx, layer=layer_label(i)) for i in range(n_layers)]
    sigs = [_resolution_sig(c) for c in cfgs]
    segments, lo = [], 0
    for i in range(1, n_layers):
        if sigs[i] != sigs[i - 1]:
            segments.append((lo, i, cfgs[lo]))
            lo = i
    segments.append((lo, n_layers, cfgs[lo]))
    return tuple(segments)


def quantize_sign_magnitude(x: jax.Array, width: int, axis=None):
    """Symmetric sign-magnitude quantization to ``width``-bit magnitudes.

    Returns (mag uint32 in [0, 2^width-1], sign int32 in {-1,+1}, scale).
    ``axis`` selects per-axis (e.g. per-output-channel) scales; None = global.
    """
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    qmax = float(2 ** width - 1)
    scale = jnp.maximum(amax, 1e-30) / qmax
    mag = jnp.clip(jnp.round(jnp.abs(x) / scale), 0, qmax).astype(jnp.uint32)
    sign = jnp.where(x < 0, -1, 1).astype(jnp.int32)
    return mag, sign, scale


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def approx_matmul(x: jax.Array, w: jax.Array, cfg: ApproxConfig) -> jax.Array:
    """Float-in/out matmul with SIMDive products; exact grads (STE)."""
    return _approx_matmul_fwd_impl(x, w, cfg)


def _approx_matmul_fwd_impl(x, w, cfg):
    if not cfg.enabled or not cfg.use_in_linear \
            or not cfg.active_for("matmul"):
        return x @ w
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    spec, backend = cfg.resolve("matmul")
    # named scopes put the device time of the dispatch around the kernel
    # (operand quantize, output rescale) down to its own name in a trace;
    # XLA names a fusion by its root, so the cast back to x.dtype, which
    # the rescale fuses into, stays inside the scope
    with jax.named_scope("approx.quantize"):
        qx, sx, scx = quantize_sign_magnitude(x2, spec.width)
        qw, sw, scw = quantize_sign_magnitude(w, spec.width, axis=0)
    mm = get_op("matmul_emul", spec, backend=backend, guard=cfg.guard)
    acc = mm(qx, sx, qw, sw, k_chunk=cfg.k_chunk)
    with jax.named_scope("approx.rescale"):
        out = acc.astype(jnp.float32) * (scx * scw)
        return out.reshape(*lead, w.shape[1]).astype(x.dtype)


def _approx_matmul_fwd(x, w, cfg):
    return _approx_matmul_fwd_impl(x, w, cfg), (x, w)


def _approx_matmul_bwd(cfg, res, g):
    x, w = res
    if cfg.backward == "approx" and cfg.enabled and cfg.use_in_linear \
            and cfg.active_for("matmul"):
        # emulate approximate *backward* matmuls: both grad GEMMs run the
        # same quantize + SIMDive emulated matmul as the forward. This is
        # the opt-in training mode (repro/train/) — the default below is
        # the straight-through exact einsum (QAT semantics).
        gf = g.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        gx = _approx_matmul_fwd_impl(gf, wf.T, cfg)
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        g2 = gf.reshape(-1, gf.shape[-1])
        gw = _approx_matmul_fwd_impl(x2.T, g2, cfg)
        return gx.astype(x.dtype), gw.astype(w.dtype)
    gx = jnp.einsum("...n,kn->...k", g, w).astype(x.dtype)
    gw = jnp.einsum("...k,...n->kn", x, g).astype(w.dtype)
    return gx, gw


approx_matmul.defvjp(_approx_matmul_fwd, _approx_matmul_bwd)


def approx_matmul_int8(x: jax.Array, q: jax.Array, scale: jax.Array,
                       cfg: ApproxConfig) -> jax.Array:
    """SIMDive matmul against *pre-quantized* int8 weights.

    The ``--quantize`` serving path swaps linear weights for
    ``QuantizedWeight`` pytrees (int8 magnitudes <= 127, per-out-channel
    scale); composing that with ``--approx`` used to silently fall back to
    the exact dequantized matmul. Here the stored int8 magnitudes feed the
    emulated SIMDive matmul directly — no requantization, the weight's own
    scale rides through — so int8 deployment and approximate arithmetic
    compose bit-faithfully. Inference-path only (no custom VJP: int8
    weights are not differentiated through).

    Raises when the resolved lane is narrower than the stored 8-bit
    magnitudes: serving would silently truncate every weight, which is
    exactly the mis-serve this path exists to refuse.
    """
    if not cfg.active_for("matmul"):
        # policy_only with no matmul entry at this layer: exact dequant
        wf = q.astype(jnp.float32) * scale.astype(jnp.float32)
        return (x.astype(jnp.float32) @ wf).astype(x.dtype)
    spec, backend = cfg.resolve("matmul")
    if spec.width < 8:
        raise ValueError(
            f"approx+quantize: resolved matmul lane width {spec.width} "
            "cannot hold int8 weight magnitudes (<=127 needs width >= 8); "
            "widen the policy's matmul entry or serve unquantized")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    qx, sx, scx = quantize_sign_magnitude(x2, spec.width)
    qi = q.astype(jnp.int32)
    qw = jnp.abs(qi).astype(jnp.uint32)
    sw = jnp.where(qi < 0, -1, 1).astype(jnp.int32)
    mm = get_op("matmul_emul", spec, backend=backend, guard=cfg.guard)
    acc = mm(qx, sx, qw, sw, k_chunk=cfg.k_chunk)
    out = acc.astype(jnp.float32) * (scx * scale.astype(jnp.float32))
    return out.reshape(*lead, q.shape[-1]).astype(x.dtype)


def _fixed_point_div(num: jax.Array, den: jax.Array, cfg: ApproxConfig):
    """Approximate num/den (both float >= 0, den > 0) via the SIMDive divider.

    Operands are block-scaled into the ``div_width``-bit lane (a shared
    power-of-two exponent, like the FPGA datapath's fixed-point input
    format); the scale cancels in the quotient. The default 16-bit lane
    runs in uint32 everywhere; a 32-bit lane needs jax x64 mode.
    """
    spec, backend = cfg.resolve("div", cfg.div_width)
    w = spec.width
    if w > 16:
        # clip both sides to the *lane* maximum, not the carrier dtype's:
        # the old 2^63 bound admitted operands far past 2^width - 1, which
        # the log datapath's LOD maps outside the F-bit fraction field.
        # Found by repro.analysis.widthcheck (lane-domain, w32).
        SC = jnp.float32(2 ** 16)
        lim = jnp.float32(lane_max_float(w))
        qn = jnp.clip(jnp.round(num * SC), 0, lim).astype(work_dtype(w))
        qd = jnp.clip(jnp.round(den * SC), 1, lim).astype(work_dtype(w))
    else:
        # shared per-call exponent so the larger side fills the lane
        top = jnp.maximum(jnp.max(num), jnp.max(den))
        ex = jnp.floor(jnp.log2(jnp.maximum(top, 1e-30)))
        SC = jnp.exp2(jnp.float32(w - 1) - ex - 1)
        lim = jnp.float32(lane_max_float(w))
        qn = jnp.clip(jnp.round(num * SC), 0, lim).astype(jnp.uint32)
        qd = jnp.clip(jnp.round(den * SC), 1, lim).astype(jnp.uint32)
    div = get_op("elemwise", spec, backend=backend, guard=cfg.guard)
    q = div(qn, qd, op="div", frac_out=cfg.frac_out)
    return q.astype(jnp.float32) / jnp.float32(2 ** cfg.frac_out)


def attention_div(acc: jax.Array, l: jax.Array, cfg: ApproxConfig):
    """Softmax normalization ``acc / l[..., None]`` on the SIMDive divider,
    resolved as the logical ``'attention'`` op (policy-tunable per layer).

    Same per-row shared-exponent quantization as the flash kernel's
    in-kernel finalize (:func:`repro.kernels.flash_attention.softmax_div`):
    ``top = max(rowmax|acc|, l)`` anchors each row's scale, so identical
    rows produce identical divider inputs whether attention is served by
    the jnp online-softmax path or the Pallas kernel — and the result is
    independent of how the rows were chunked. ``acc`` is signed float
    (..., dh); ``l`` is (...,) > 0. The default 16-bit lane runs in uint32
    everywhere; a 32-bit lane needs jax x64 mode.
    """
    if not cfg.active_for("attention"):
        # policy_only with no attention entry at this layer: exact divide
        return acc / jnp.maximum(l, 1e-30)[..., None]
    spec, backend, frac_out = cfg.resolve_attention()
    w = spec.width
    num = jnp.abs(acc)
    den = jnp.maximum(l, 1e-30)[..., None]
    top = jnp.maximum(jnp.max(num, axis=-1, keepdims=True), den)
    ex = jnp.floor(jnp.log2(jnp.maximum(top, 1e-30)))
    sc = jnp.exp2(jnp.float32(w - 2) - ex)
    # float32(2^32 - 1) rounds UP to 2^32, so at w=32 the old
    # `2 ** w - 1` limit let a clipped operand land one past the lane
    # maximum. Found by repro.analysis.widthcheck (lane-domain, w32).
    lim = jnp.float32(lane_max_float(w))
    dt = work_dtype(w)
    qn = jnp.clip(jnp.round(num * sc), 0, lim).astype(dt)
    qd = jnp.clip(jnp.round(den * sc), 1, lim).astype(dt)
    div = get_op("elemwise", spec, backend=backend, guard=cfg.guard)
    quot = div(qn, jnp.broadcast_to(qd, qn.shape), op="div",
               frac_out=frac_out)
    out = quot.astype(jnp.float32) * jnp.float32(2.0 ** -frac_out)
    return jnp.where(acc < 0, -out, out)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def approx_softmax(x: jax.Array, axis: int, cfg: ApproxConfig) -> jax.Array:
    """Softmax whose normalization division is a SIMDive divider."""
    return _approx_softmax_impl(x, axis, cfg)


def _approx_softmax_impl(x, axis, cfg):
    if not cfg.enabled or not cfg.use_in_softmax \
            or not cfg.active_for("div"):
        return jax.nn.softmax(x, axis=axis)
    m = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    e = jnp.exp((x - m).astype(jnp.float32))
    s = jnp.sum(e, axis=axis, keepdims=True)
    p = _fixed_point_div(e, jnp.broadcast_to(s, e.shape), cfg)
    return p.astype(x.dtype)


def _approx_softmax_fwd(x, axis, cfg):
    p = _approx_softmax_impl(x, axis, cfg)
    return p, p


def _approx_softmax_bwd(axis, cfg, p, g):
    # exact softmax jacobian at the approximate output (STE)
    pg = p.astype(jnp.float32) * g.astype(jnp.float32)
    gx = pg - p * jnp.sum(pg, axis=axis, keepdims=True)
    return (gx.astype(g.dtype),)


approx_softmax.defvjp(_approx_softmax_fwd, _approx_softmax_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def approx_rmsnorm(x: jax.Array, gamma: jax.Array, eps: float,
                   cfg: ApproxConfig) -> jax.Array:
    """RMSNorm with a log-domain rsqrt+divide denominator (beyond-paper)."""
    return _approx_rmsnorm_impl(x, gamma, eps, cfg)


def _approx_rmsnorm_impl(x, gamma, eps, cfg):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    if not cfg.enabled or not cfg.use_in_norm \
            or not cfg.active_for("div"):
        inv = jax.lax.rsqrt(ms + eps)
    else:
        # rsqrt in the log domain: sqrt is L >> 1, then one SIMDive divide.
        #   qm = m * 2^32           (uint64 lane)
        #   r  = sqrt(qm)           = sqrt(m) * 2^16
        #   q  = (2^31 / r) * 2^16  = rsqrt(m) * 2^31
        spec, backend = cfg.resolve("div", cfg.div_width)
        # qm feeds lod_log(., width) directly, so it must stay inside the
        # spec.width-bit lane; ms >= 1 would otherwise push qm past 2^32 - 1
        # (and float32 cannot even represent that limit — it rounds up to
        # 2^32). Found by repro.analysis.widthcheck (lane-domain, w32).
        qm = jnp.clip(jnp.round((ms + eps) * jnp.float32(2.0 ** 32)),
                      1.0, jnp.float32(lane_max_float(spec.width)))
        qm = qm.astype(jnp.uint64)
        # sqrt has no Pallas impl yet — 'auto' serves it from ref on any host
        sqrt_op = get_op(
            "sqrt", spec, guard=cfg.guard,
            backend=backend if backend == "ref" else "auto")
        r = jnp.maximum(sqrt_op(qm), 1)
        one = jnp.full_like(r, jnp.uint64(1) << jnp.uint64(31))
        div = get_op("elemwise", spec, backend=backend, guard=cfg.guard)
        q = div(one, r, op="div", frac_out=16)
        inv = q.astype(jnp.float32) * jnp.float32(2.0 ** -31)
    return (x.astype(jnp.float32) * inv * gamma.astype(jnp.float32)).astype(x.dtype)


def _approx_rmsnorm_fwd(x, gamma, eps, cfg):
    return _approx_rmsnorm_impl(x, gamma, eps, cfg), (x, gamma)


def _approx_rmsnorm_bwd(eps, cfg, res, g):
    x, gamma = res
    # exact RMSNorm gradient (STE through the approximate denominator)
    f32 = jnp.float32
    xf, gf, gg = x.astype(f32), g.astype(f32), gamma.astype(f32)
    d = x.shape[-1]
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xn = xf * inv
    gxn = gf * gg
    gx = inv * (gxn - xn * jnp.mean(gxn * xn, axis=-1, keepdims=True))
    ggamma = jnp.sum((gf * xn).reshape(-1, d), axis=0)
    return gx.astype(x.dtype), ggamma.astype(gamma.dtype)


approx_rmsnorm.defvjp(_approx_rmsnorm_fwd, _approx_rmsnorm_bwd)
