"""Plain reference of a served llama-style model, in ``jax.numpy``.

Written from the published description (arXiv:2403.04652, a llama
decoder): token embedding; per layer RMSNorm, grouped-query causal
attention with rotary embeddings (first and second halves of each head
rotated as a pair), a residual add, RMSNorm, a SwiGLU MLP and a residual
add; a final RMSNorm and the head (the embedding where it is tied).  It
imports nothing of the program and reads only the benchmark's weights
and the configuration.

The scorer runs one whole sequence and returns, at every position, the
reference's largest logit and the logit of the next token of the
sequence, and with a control the reference's logit of the token that the
control puts first.  The reference runs in float32 with every matmul at
``HIGHEST``.  The ``int8`` control is the same forward pass with every
matmul weight (attention, MLP, head) rounded to int8 with one symmetric
scale per output channel, the weights a weight-only int8 deployment
serves; the arithmetic stays float32.
"""

from __future__ import annotations

import functools


def _forward(cfg, params, tokens, dtype, precision):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n_len = tokens.shape[0]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g = h // kv
    eps = cfg["rms_norm_eps"]

    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                          precision=precision, preferred_element_type=f32)

    def rms(x, scale):
        x32 = x.astype(f32)
        y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (y * scale).astype(dtype)

    half = dh // 2
    inv = cfg["rope_theta"] ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(n_len, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # (L, heads, dh)
        x1, x2 = x[..., :half].astype(f32), x[..., half:].astype(f32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(dtype)

    causal = jnp.tril(jnp.ones((n_len, n_len), bool))

    def layer(x, p):
        a = p["attn"]
        hn = rms(x, p["ln_attn"]["scale"])
        q = rope(mm("ld,dhk->lhk", hn, a["wq"]).astype(dtype))
        k = rope(mm("ld,dhk->lhk", hn, a["wk"]).astype(dtype))
        v = mm("ld,dhk->lhk", hn, a["wv"]).astype(dtype)
        qg = q.reshape(n_len, kv, g, dh)
        s = mm("legk,sek->egls", qg, k) / jnp.sqrt(f32(dh))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = mm("egls,sek->legk", w, v).astype(dtype).reshape(n_len, h, dh)
        x = (x + mm("lhk,hkd->ld", o, a["wo"]).astype(dtype)).astype(dtype)
        m = p["mlp"]
        hn = rms(x, p["ln_mlp"]["scale"])
        gate = jax.nn.silu(mm("ld,df->lf", hn, m["w_gate"]))
        up = mm("ld,df->lf", hn, m["w_up"])
        hid = (gate * up).astype(dtype)
        return (x + mm("lf,fd->ld", hid, m["w_down"]).astype(dtype)
                ).astype(dtype), None

    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(dtype)
    x, _ = jax.lax.scan(layer, x, params["stack"]["reps"][0])
    x = rms(x, params["final_norm"]["scale"])
    head = params["lm_head"]["table"] if "lm_head" in params else table
    return mm("ld,vd->lv", x, head)  # (L, V) float32


def _int8(w, axes):
    """``w`` rounded to int8 steps, one symmetric scale per output
    channel (the maximum |w| over the input ``axes``)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


def int8_weights(params):
    """The params with every matmul weight rounded by ``_int8``; the
    head becomes an ``lm_head`` of its own, so a tied embedding table
    stays exact where it is looked up."""
    layer = params["stack"]["reps"][0]
    inputs = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
              "w_gate": (1,), "w_up": (1,), "w_down": (1,)}
    q = {g: {k: _int8(v, inputs[k]) for k, v in layer[g].items()}
         for g in ("attn", "mlp")}
    head = params.get("lm_head", params["embed"])["table"]
    return dict(params, stack={"reps": (dict(layer, **q),), "tail": []},
                lm_head={"table": _int8(head, (1,))})


@functools.lru_cache(maxsize=None)
def _scorer(frozen_cfg, control):
    import jax
    import jax.numpy as jnp

    cfg = dict(frozen_cfg)
    hi = jax.lax.Precision.HIGHEST

    def score(params, tokens):
        ref = _forward(cfg, params, tokens, jnp.float32, hi)
        nxt = jnp.roll(tokens, -1)
        out = {"best": jnp.max(ref, axis=-1),
               "next": jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]}
        if control == "int8":
            low = _forward(cfg, int8_weights(params), tokens, jnp.float32, hi)
            first = jnp.argmax(low, axis=-1)
            out["control"] = jnp.take_along_axis(ref, first[:, None],
                                                 axis=-1)[:, 0]
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        return out

    return jax.jit(score)


def widest_gaps(cfg: dict, params, sequences, length: int,
                control=None) -> dict:
    """For each ``(prompt, served)`` pair, pad prompt + served tokens to
    ``length`` and score it once.  A served token's gap is how far its
    reference logit lies below the reference's best at that position
    (0 where the reference puts it first).  Returns, over all served
    tokens, the widest gap, the mean gap and the number of tokens the
    reference would not have put first; with a ``control`` the same,
    under ``control_`` names, for the token the control puts first at
    each of those positions."""
    import jax.numpy as jnp
    import numpy as np

    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    fn = _scorer(tuple(sorted((k, cfg[k]) for k in keys)), control)
    gaps, ctrl = [], []
    for prompt, served in sequences:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served, np.int32)])
        if seq.shape[0] > length:
            raise ValueError(f"sequence of {seq.shape[0]} > {length}")
        pad = np.zeros(length, np.int32)
        pad[: seq.shape[0]] = seq
        out = {k: np.asarray(v) for k, v in fn(params, jnp.asarray(pad)).items()}
        # served token j sits at position P + j and was chosen from the
        # logits at position P + j - 1
        lo, hi = len(prompt) - 1, seq.shape[0] - 1
        gaps.append(out["best"][lo:hi] - out["next"][lo:hi])
        if control:
            ctrl.append(out["best"][lo:hi] - out["control"][lo:hi])
    res = _summary(gaps, "")
    if control:
        res.update(_summary(ctrl, "control_"))
    return res


def _summary(gaps: list, prefix: str) -> dict:
    import numpy as np

    gaps = np.concatenate(gaps).astype(np.float64) if gaps else np.zeros(0)
    n = int(gaps.shape[0])
    return {prefix + "widest_logit_gap": float(gaps.max()) if n else 0.0,
            prefix + "mean_logit_gap": float(gaps.mean()) if n else 0.0,
            prefix + "tokens_not_first": int(np.sum(gaps > 0)),
            prefix + "tokens": n}
