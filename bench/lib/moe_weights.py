"""Random weights of a served Mellum2 model, pruned, made on the device.

One jitted call per configuration makes every weight from the seed in
float32, the type it is served in, and prunes each held expert's three
matrices to the configuration's density by magnitude, with
``lm_weights.prune`` (the benchmark's own copy of magnitude pruning): a
second magnitude pruning of the result at the same density keeps the
same nonzeros, so the program's plans and its dense prefill serve one
model.  The router is not pruned.

The tree has the layout the program's parameters have: ``embed.table``,
``lm_head.table`` (the head is untied), ``final_norm``, and one group per
position of the layer pattern (``layer_types`` repeats with period
``pattern``), ``stack.reps[i]``, each with a leading axis over the
pattern's repetitions: ``ln_attn``, ``attn`` (``wq``, ``wk``, ``wv``,
``wo``), ``ln_mlp`` and ``moe`` (``router`` of width
``expert_share.router_experts``; ``w_gate``, ``w_up``, ``w_down`` with an
axis over the ``num_experts`` held experts).
"""

from __future__ import annotations

import functools

from . import lm_weights

EXPERT_MATS = ("w_gate", "w_up", "w_down")


def period(cfg: dict) -> int:
    """The length of the layer pattern: the shortest prefix of
    ``layer_types`` that repeats through the stack."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p
    return len(kinds)


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    e = cfg["num_experts"]
    return {"wq": (d, h, dh), "wk": (d, kv, dh), "wv": (d, kv, dh),
            "wo": (h, dh, d), "router": (d, cfg["expert_share"]["router_experts"]),
            "w_gate": (e, d, f), "w_up": (e, d, f), "w_down": (e, f, d)}


@functools.lru_cache(maxsize=None)
def _maker(frozen_cfg):
    import json

    import jax
    import jax.numpy as jnp

    cfg = json.loads(frozen_cfg)
    density = cfg["density"]
    p = period(cfg)
    r = cfg["num_hidden_layers"] // p
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    sh = shapes(cfg)

    def he(key, shape, fan_in):
        return jax.random.normal(key, (r,) + shape, jnp.float32) / jnp.sqrt(
            jnp.float32(fan_in))

    def scale(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def position(key):
        ks = iter(jax.random.split(key, 16))
        attn = {
            "wq": he(next(ks), sh["wq"], d),
            "wk": he(next(ks), sh["wk"], d),
            "wv": he(next(ks), sh["wv"], d),
            "wo": he(next(ks), sh["wo"], sh["wo"][0] * sh["wo"][1]),
        }
        moe = {"router": he(next(ks), sh["router"], d)}
        nnz = {}
        for name in EXPERT_MATS:
            w = he(next(ks), sh[name], sh[name][1])
            k = lm_weights.keep_count(sh[name][1] * sh[name][2], density)
            moe[name] = jax.vmap(jax.vmap(
                lambda m, k=k: lm_weights.prune(m, k)))(w)
            nnz[name] = jnp.sum(moe[name] != 0, axis=(2, 3), dtype=jnp.int32)
        layer = {"ln_attn": {"scale": scale(next(ks), (r, d))}, "attn": attn,
                 "ln_mlp": {"scale": scale(next(ks), (r, d))}, "moe": moe}
        return layer, nnz

    def make(key):
        ks = jax.random.split(key, p + 3)
        layers, nnz = zip(*(position(ks[i]) for i in range(p)))
        params = {
            "embed": {"table": 0.02 * jax.random.normal(ks[p], (v, d),
                                                        jnp.float32)},
            "final_norm": {"scale": scale(ks[p + 1], (d,))},
            "stack": {"reps": tuple(layers), "tail": []},
            "lm_head": {"table": 0.02 * jax.random.normal(ks[p + 2], (v, d),
                                                          jnp.float32)},
        }
        # nonzeros per matrix, per layer in depth order and held expert
        counts = {n: jnp.stack([c[n] for c in nnz], axis=1).reshape(
            (r * p, -1)) for n in EXPERT_MATS}
        return params, counts

    return jax.jit(make)


def make_params(cfg: dict, density: float, seed: int):
    """(params, {matrix: [[nonzeros per held expert] per layer]}) for
    ``seed``; layers in depth order."""
    import json

    import jax
    import numpy as np

    if cfg["num_hidden_layers"] % period(cfg):
        raise ValueError("the layers are not whole periods of the pattern")
    keys = {k: cfg[k] for k in ("num_hidden_layers", "hidden_size",
                                "moe_intermediate_size", "num_attention_heads",
                                "num_key_value_heads", "head_dim",
                                "vocab_size", "num_experts", "expert_share",
                                "layer_types")}
    keys["density"] = float(density)
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    key = jax.random.fold_in(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF),
                             int(words[1]) & 0x7FFFFFFF)
    params, nnz = _maker(json.dumps(keys, sort_keys=True))(key)
    return params, {k: np.asarray(v).tolist() for k, v in nnz.items()}
