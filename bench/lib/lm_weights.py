"""Random weights of a served language model, pruned, made on the device.

One jitted call per configuration makes every weight from the seed in
float32, the type it is served in, and prunes each MLP matrix to the
configuration's density by magnitude.  The pruning is the benchmark's
own copy of magnitude pruning: keep the ``k = round(density * size)``
entries of largest magnitude, where the threshold is the k-th largest
|w|, found exactly by bisection on its float32 bit pattern.  A second
magnitude pruning of the result at the same density keeps the same
nonzeros, so the program's plans and its dense prefill serve one model.

The tree has the layout the program's parameters have (a dense
pattern-of-one stack): ``embed.table``, ``lm_head.table`` where the head
is not tied to the embedding, ``final_norm``, and
one stacked layer group ``stack.reps[0]`` with ``ln_attn``, ``attn``
(``wq``, ``wk``, ``wv``, ``wo``), ``ln_mlp`` and ``mlp`` (``w_gate``,
``w_up``, ``w_down``), each with a leading layer axis.
"""

from __future__ import annotations

import functools

MLP = ("w_gate", "w_up", "w_down")


def keep_count(size: int, density: float) -> int:
    return max(int(round(size * density)), 1)


def prune(w, k: int):
    """Zero all but the ``k`` entries of largest |w| (ties at the
    threshold are kept)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(jnp.abs(w), jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + 1) // 2
        ok = jnp.sum(bits >= mid, dtype=jnp.int32) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, 32, body,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
    return jnp.where(bits >= lo, w, 0.0).astype(w.dtype)


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"wq": (d, h, dh), "wk": (d, kv, dh), "wv": (d, kv, dh),
            "wo": (h, dh, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}


@functools.lru_cache(maxsize=None)
def _maker(frozen_cfg):
    import jax
    import jax.numpy as jnp

    cfg = dict(frozen_cfg)
    density = cfg["density"]
    r, d, v = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    sh = shapes(cfg)

    def he(key, shape, fan_in):
        return jax.random.normal(key, (r,) + shape, jnp.float32) / jnp.sqrt(
            jnp.float32(fan_in))

    def scale(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def make(key):
        ks = iter(jax.random.split(key, 16))
        attn = {
            "wq": he(next(ks), sh["wq"], d),
            "wk": he(next(ks), sh["wk"], d),
            "wv": he(next(ks), sh["wv"], d),
            "wo": he(next(ks), sh["wo"], sh["wo"][0] * sh["wo"][1]),
        }
        mlp = {}
        for name in MLP:
            w = he(next(ks), sh[name], sh[name][0])
            k = keep_count(sh[name][0] * sh[name][1], density)
            mlp[name] = jax.vmap(lambda m, k=k: prune(m, k))(w)
        layer = {"ln_attn": {"scale": scale(next(ks), (r, d))}, "attn": attn,
                 "ln_mlp": {"scale": scale(next(ks), (r, d))}, "mlp": mlp}
        params = {
            "embed": {"table": 0.02 * jax.random.normal(next(ks), (v, d),
                                                        jnp.float32)},
            "final_norm": {"scale": scale(next(ks), (d,))},
            "stack": {"reps": (layer,), "tail": []},
        }
        if not cfg["tie_word_embeddings"]:
            params["lm_head"] = {"table": 0.02 * jax.random.normal(
                next(ks), (v, d), jnp.float32)}
        nnz = {n: jnp.sum(mlp[n] != 0, axis=(1, 2), dtype=jnp.int32)
               for n in MLP}
        return params, nnz

    return jax.jit(make)


def make_params(cfg: dict, density: float, seed: int):
    """(params, {matrix: [nonzeros per layer]}) for ``seed``."""
    import jax
    import numpy as np

    keys = {k: cfg[k] for k in ("num_hidden_layers", "hidden_size",
                                "intermediate_size", "num_attention_heads",
                                "num_key_value_heads", "head_dim",
                                "vocab_size", "tie_word_embeddings")}
    keys["density"] = float(density)
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    key = jax.random.fold_in(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF),
                             int(words[1]) & 0x7FFFFFFF)
    params, nnz = _maker(tuple(sorted(keys.items())))(key)
    return params, {k: [int(x) for x in np.asarray(v)] for k, v in nnz.items()}
