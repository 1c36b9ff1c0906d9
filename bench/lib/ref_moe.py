"""Plain reference of a served Mellum2 model, in ``jax.numpy``.

Written from the published ``config.json`` (JetBrains/
Mellum2-12B-A2.5B-Instruct): token embedding; per layer RMSNorm,
grouped-query causal attention with rotary embeddings (first and second
halves of each head rotated as a pair), a residual add, RMSNorm, a routed
mixture of SwiGLU experts and a residual add; a final RMSNorm and an
untied head.  ``layer_types`` says which layers attend within a sliding
window of ``sliding_window`` positions (a key ``k`` is seen from query
``q`` when ``q - window < k <= q``) and which attend to every earlier
position; ``rope_parameters`` gives each kind its rotary embedding: the
default one at ``rope_theta``, or YaRN (arXiv:2309.00071) with its
frequency ramp and its attention factor on cos and sin.  The router
takes a softmax over all ``expert_share.router_experts`` experts, keeps
the ``num_experts_per_tok`` largest and renormalises their weights
(``norm_topk_prob``); only the experts this chip holds
(``expert_share.first`` and the ``num_experts`` after it) add their
terms, every token routed to them, none dropped.  It imports nothing of
the program and reads only the benchmark's weights and the
configuration.

The scorer runs one whole sequence and returns, at every position, the
reference's largest logit and the logit of the next token of the
sequence, and with a control the reference's logit of the token that the
control puts first.  The reference runs in float32 with every matmul at
``HIGHEST``.  The ``int8`` control is the same forward pass with every
matmul weight (attention, router, experts, head) rounded to int8 with
one symmetric scale per output channel; the arithmetic stays float32.
"""

from __future__ import annotations

import functools
import math

from . import ref_lm


def rope_inv_freq(d: int, params: dict):
    """(d/2,) inverse frequencies and the cos/sin factor of one
    ``rope_parameters`` entry."""
    import numpy as np

    theta = float(params["rope_theta"])
    half = d // 2
    pos_freqs = theta ** (np.arange(half, dtype=np.float64) * 2.0 / d)
    if params.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {params['rope_type']!r}")
    factor = float(params["factor"])
    orig = float(params["original_max_position_embeddings"])

    def dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(params["beta_fast"])), 0)
    high = min(math.ceil(dim(params["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    inv = ramp / (factor * pos_freqs) + (1.0 - ramp) / pos_freqs
    return inv.astype(np.float32), float(params.get("attention_factor", 1.0))


def _forward(cfg, params, tokens, precision):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n_len = tokens.shape[0]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g = h // kv
    eps = cfg["rms_norm_eps"]
    share = cfg["expert_share"]
    first, held = share["first"], cfg["num_experts"]
    top_k = cfg["num_experts_per_tok"]

    def mm(eq, a, b):
        return jnp.einsum(eq, a.astype(f32), b.astype(f32),
                          precision=precision, preferred_element_type=f32)

    def rms(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                            ) * scale

    pos = jnp.arange(n_len, dtype=f32)
    half = dh // 2

    def rope_fn(kind):
        inv, factor = rope_inv_freq(dh, cfg["rope_parameters"][kind])
        ang = pos[:, None] * jnp.asarray(inv)[None, :]
        cos = (jnp.cos(ang) * factor)[:, None, :]
        sin = (jnp.sin(ang) * factor)[:, None, :]

        def rope(x):  # (L, heads, dh)
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                   axis=-1)
        return rope

    ropes = {k: rope_fn(k) for k in set(cfg["layer_types"])}
    q_idx = jnp.arange(n_len)[:, None]
    k_idx = jnp.arange(n_len)[None, :]
    causal = k_idx <= q_idx
    masks = {"full_attention": causal,
             "sliding_attention": causal & (k_idx > q_idx
                                            - cfg["sliding_window"])}

    def layer(x, p, kind):
        a = p["attn"]
        rope = ropes[kind]
        hn = rms(x, p["ln_attn"]["scale"])
        q = rope(mm("ld,dhk->lhk", hn, a["wq"]))
        k = rope(mm("ld,dhk->lhk", hn, a["wk"]))
        v = mm("ld,dhk->lhk", hn, a["wv"])
        qg = q.reshape(n_len, kv, g, dh)
        s = mm("legk,sek->egls", qg, k) / jnp.sqrt(f32(dh))
        s = jnp.where(masks[kind][None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm("egls,sek->legk", w, v).reshape(n_len, h, dh)
        x = x + mm("lhk,hkd->ld", o, a["wo"])
        m = p["moe"]
        hn = rms(x, p["ln_mlp"]["scale"])
        probs = jax.nn.softmax(mm("ld,de->le", hn, m["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, top_k)
        if cfg.get("norm_topk_prob", True):
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(held):  # the held experts, every token
            weight = jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
            hid = jax.nn.silu(mm("ld,df->lf", hn, m["w_gate"][e])) * mm(
                "ld,df->lf", hn, m["w_up"][e])
            y = y + weight[:, None] * mm("lf,fd->ld", hid, m["w_down"][e])
        return x + y

    table = params["embed"]["table"]
    x = jnp.take(table, tokens, axis=0).astype(f32)
    reps = params["stack"]["reps"]
    for i in range(cfg["num_hidden_layers"]):
        r, j = divmod(i, len(reps))
        p = jax.tree.map(lambda a, r=r: a[r], reps[j])
        x = layer(x, p, cfg["layer_types"][i])
    x = rms(x, params["final_norm"]["scale"])
    return mm("ld,vd->lv", x, params["lm_head"]["table"])  # (L, V)


def int8_weights(params):
    """The params with every matmul weight rounded to int8 steps, one
    symmetric scale per output channel (``ref_lm._int8``)."""
    inputs = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
              "router": (1,), "w_gate": (2,), "w_up": (2,), "w_down": (2,)}
    reps = tuple(
        dict(layer, **{grp: {k: ref_lm._int8(v, inputs[k])
                             for k, v in layer[grp].items()}
                       for grp in ("attn", "moe")})
        for layer in params["stack"]["reps"])
    return dict(params, stack={"reps": reps, "tail": []},
                lm_head={"table": ref_lm._int8(params["lm_head"]["table"],
                                               (1,))})


@functools.lru_cache(maxsize=None)
def _scorer(frozen_cfg, control):
    import json

    import jax
    import jax.numpy as jnp

    cfg = json.loads(frozen_cfg)
    hi = jax.lax.Precision.HIGHEST

    def score(params, tokens):
        ref = _forward(cfg, params, tokens, hi)
        nxt = jnp.roll(tokens, -1)
        out = {"best": jnp.max(ref, axis=-1),
               "next": jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]}
        if control == "int8":
            low = _forward(cfg, int8_weights(params), tokens, hi)
            first = jnp.argmax(low, axis=-1)
            out["control"] = jnp.take_along_axis(ref, first[:, None],
                                                 axis=-1)[:, 0]
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        return out

    return jax.jit(score)


KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "layer_types", "sliding_window", "rope_parameters",
        "num_experts", "num_experts_per_tok", "norm_topk_prob",
        "expert_share", "num_hidden_layers")


def logits(cfg: dict, params, tokens):
    """The reference's (L, V) float32 logits of one sequence."""
    import jax
    import jax.numpy as jnp

    return _forward({k: cfg[k] for k in KEYS}, params,
                    jnp.asarray(tokens, jnp.int32), jax.lax.Precision.HIGHEST)


def widest_gaps(cfg: dict, params, sequences, length: int,
                control=None) -> dict:
    """For each ``(prompt, served)`` pair, pad prompt + served tokens to
    ``length`` and score it once; the gaps as ``ref_lm.widest_gaps``
    defines them, with this model's reference."""
    import json

    import jax.numpy as jnp
    import numpy as np

    fn = _scorer(json.dumps({k: cfg[k] for k in KEYS}, sort_keys=True),
                 control)
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
    res = ref_lm._summary(gaps, "")
    if control:
        res.update(ref_lm._summary(ctrl, "control_"))
    return res
