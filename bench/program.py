"""The system under test, built the way ``serve.py --scheduler`` builds it.

The only module of the benchmark that imports the program. It turns a
configuration file into the program's ``ModelConfig`` (checking every width
against the program's own file of that model), makes the weights from the
seed, and builds the ``Scheduler`` with its ladder pinned to the one
configured rung.
"""
from __future__ import annotations

from dataclasses import replace
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.scheduler import Scheduler, ServeLevel, coarse_step
from repro.launch.serve import resolve_serving_plan, serving_config

#: published key -> program field, for every size the program must match
_WIDTHS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
}


def model_config(conf: dict, *, rehearse: bool, backend: str,
                 control: str | None = None):
    """The served ``ModelConfig``: the program's file of this model with the
    configuration's depth, norm epsilon and head tying, and the
    configuration's arithmetic. ``control='mitchell'`` serves the program's
    own coarser rung (uncorrected Mitchell) in its place."""
    base = get_config(conf["program_config"], smoke=rehearse)
    if not rehearse:
        for key, field in _WIDTHS.items():
            want = conf.get(key, conf["hidden_size"] //
                            conf["num_attention_heads"]) \
                if key == "head_dim" else conf.get(key, False)
            if getattr(base, field) != want:
                raise SystemExit(
                    f"{conf['program_config']}: program has {field}="
                    f"{getattr(base, field)!r}, configuration file has "
                    f"{key}={want!r}")
        base = replace(base, n_layers=conf["num_hidden_layers"],
                       norm_eps=float(conf["rms_norm_eps"]),
                       tie_embeddings=bool(conf["tie_word_embeddings"]))
    a = conf["arithmetic"]
    if base.dtype != a["dtype"] or base.param_dtype != a["param_dtype"]:
        raise SystemExit(f"program dtypes {base.dtype}/{base.param_dtype} "
                         f"differ from the configuration's {a['dtype']}/"
                         f"{a['param_dtype']}")
    cfg = serving_config(base, a["mode"], emulate=a["emulate"],
                         backend=backend)
    ap = cfg.approx
    got = (ap.width, ap.div_width, ap.coeff_bits, ap.index_bits, ap.frac_out)
    want = (a["width"], a["div_width"], a["coeff_bits"], a["index_bits"],
            a["frac_out"])
    if got != want:
        raise SystemExit(f"program arithmetic {got} differs from the "
                         f"configuration's {want}")
    if control == "mitchell":
        cfg = cfg.with_approx(coarse_step(cfg.approx))
    elif control is not None:
        raise SystemExit(f"unknown control {control!r}")
    return cfg


def reference_sizes(conf: dict, cfg) -> dict:
    """The sizes the reference is built from: the configuration file, or in
    a rehearsal the program's smoke preset in the same published keys."""
    if cfg.n_layers == conf["num_hidden_layers"] and \
            cfg.d_model == conf["hidden_size"]:
        return conf
    return dict(conf, num_hidden_layers=cfg.n_layers,
                hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
                num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.d_head,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                qk_norm=cfg.qk_norm,
                tie_word_embeddings=cfg.tie_embeddings)


def make_weights(sizes: dict, seed: int):
    """Seeded float32 weights in the program's parameter layout, made on the
    device in one compiled call, as the configuration's ``weights`` says."""
    key = jax.random.PRNGKey(int(np.random.SeedSequence(seed)
                                 .generate_state(1)[0]))
    c = sizes
    dims = (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c.get("head_dim", c["hidden_size"] // c["num_attention_heads"]),
            c["intermediate_size"], c["vocab_size"],
            bool(c.get("qk_norm", False)), bool(c["tie_word_embeddings"]),
            float(c["weights"]["embed_scale"]))
    return _init(key, dims)


@partial(jax.jit, static_argnums=(1,))
def _init(key, dims):
    L, D, H, KV, dh, F, V, qk_norm, tied, embed_scale = dims
    ks = iter(jax.random.split(key, 16))

    def uni(shape, fan_in):
        lim = fan_in ** -0.5
        return jax.random.uniform(next(ks), shape, jnp.float32, -lim, lim)

    def norm(shape):
        return {"w": jax.random.uniform(next(ks), shape, jnp.float32,
                                        0.8, 1.2)}

    layers = {
        "ln_attn": norm((L, D)), "ln_mlp": norm((L, D)),
        "wq": uni((L, D, H * dh), D), "wk": uni((L, D, KV * dh), D),
        "wv": uni((L, D, KV * dh), D), "wo": uni((L, H * dh, D), H * dh),
        "mlp": {"w1": uni((L, D, F), D), "w2": uni((L, F, D), F),
                "w3": uni((L, D, F), D)},
    }
    if qk_norm:
        layers["q_norm"] = norm((L, dh))
        layers["k_norm"] = norm((L, dh))
    w = {"embed": jax.random.normal(next(ks), (1, V, D), jnp.float32)
         * (embed_scale * D ** -0.5),
         "stack": {"layers": layers}, "final_norm": norm((D,))}
    if not tied:
        w["head"] = uni((1, D, V), D)
    return w


def scheduler(cfg, weights, mix, *, kernel_backend: str):
    """The Scheduler as users run it: watchdog on, no scrub, and a ladder of
    the one configured rung. Refuses to build one that would serve any
    approximate op off ``kernel_backend`` or could shed to another rung."""
    plan = resolve_serving_plan(cfg)
    wrong = [r.label() for r in plan if r.backend != kernel_backend]
    if not plan or wrong:
        raise SystemExit(f"serving plan does not put every approximate op "
                         f"on {kernel_backend}: {wrong or 'empty plan'}")
    sched = Scheduler(cfg, params=weights,
                      levels=(ServeLevel("fine", cfg.approx),),
                      batch=mix.batch, prompt_len=mix.prompt_len,
                      max_seq=mix.max_seq)
    rungs = [lv for lv in sched.levels if lv.name != "recovery"]
    if len(rungs) != 1 or rungs[0].approx != cfg.approx:
        raise SystemExit(f"load-shed ladder holds {len(rungs)} rungs; the "
                         "cell times one configured rung")
    return sched, plan
