"""Driver for a served mixture-of-experts model: ``ServeLoop`` with GUST
decode of the held experts under the router.

As ``drivers/serving.py``, whose clients and warm-up it uses: set-up
makes the weights from the seed (``lib/moe_weights``, held experts
pruned by the benchmark), builds the program's engine (``ServeLoop``:
gustify colours one plan per held expert matrix, jitted prefill and
decode) and admits the first request of every client, which compiles or
loads every program the window uses.  Window: ``ServeLoop.step`` in a
loop, closed-loop clients, every token's delivery timed on the host
clock at the return of the step that delivered it.  The program's
routing counters (``ServeLoop.read_counters``) are read before and after
the window; the record holds their change.  Check: once the window has
closed and the engine is freed, the plain reference (``lib/ref_moe``)
scores every request that was served a token, and each number the
configuration names under ``limits`` is compared with its limit; the
control (``ctx.control``) is the reference with int8 weights in the
program's place.
"""

from __future__ import annotations

import gc
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import harness, moe_weights, ref_moe, traffic  # noqa: E402

_serving = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "serving.py"),
    "driver_serving_for_moe")


def program_arch(cfg: dict):
    """The program's model for the configuration, checked against it."""
    import dataclasses

    from repro.configs.base import get_arch

    try:
        arch = get_arch(cfg["program"]["arch"])
    except ImportError as err:
        raise harness.RunFailure(
            f"the program has no arch {cfg['program']['arch']!r}: {err}")
    if cfg["program"].get("reduced"):  # the program's own smoke widths
        arch = arch.reduced()
    arch = dataclasses.replace(arch, n_layers=cfg["num_hidden_layers"])
    ropes = cfg["rope_parameters"]
    full = ropes["full_attention"]
    kinds = {"sliding_attention": "moe_local", "full_attention": "moe_global"}
    want = {"d_model": cfg["hidden_size"],
            "d_ff": cfg["moe_intermediate_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
            "vocab": cfg["vocab_size"], "padded_vocab": cfg["vocab_size"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "rope_theta": ropes["sliding_attention"]["rope_theta"],
            "local_window": cfg["sliding_window"],
            "n_experts": cfg["expert_share"]["router_experts"],
            "experts_held": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"]}
    got = {k: getattr(arch, k) for k in want}
    want["layers"] = [kinds[k] for k in cfg["layer_types"]]
    got["layers"] = [arch.pattern[i % len(arch.pattern)]
                     for i in range(arch.n_layers)]
    want["yarn"] = (full["rope_theta"], full["factor"],
                    full["original_max_position_embeddings"],
                    full["beta_fast"], full["beta_slow"],
                    full["attention_factor"])
    y = arch.global_yarn
    got["yarn"] = y and (arch.rope_theta, y.factor, y.original_max_position,
                         y.beta_fast, y.beta_slow, y.attention_factor)
    if (cfg["expert_share"]["first"] != 0 or set(cfg["mlp_layer_types"])
            != {"sparse"} or not cfg["norm_topk_prob"] or got != want):
        raise harness.RunFailure(f"program arch {arch.name} {got} differs "
                                 f"from the configuration {want}")
    return arch


def _fusable(tree) -> bool:
    """Every stacked expert plan's first slice packs fusable."""
    from repro.core.plan import GustPlan

    return all(
        GustPlan.from_spec({"leaves": {k: v[0][0] for k, v in m["leaves"].items()},
                            "meta": m["meta"]}).artifact.fusable
        for m in tree["mats"].values())


def _delta(a, b):
    """b - a, elementwise through lists."""
    if isinstance(b, list):
        return [_delta(x, y) for x, y in zip(a, b)]
    return b - a


def run(ctx) -> dict:
    import jax

    from repro.models.model_zoo import build_model
    from repro.serving import GustServeConfig, ServeConfig, ServeLoop

    cfg, mix = ctx.config, ctx.mix
    g, s = cfg["gust"], cfg["serve"]
    lm = build_model(program_arch(cfg))
    params, nnz = moe_weights.make_params(cfg, g["density"], ctx.seed)
    want = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    ctx.require(jax.tree.structure(want) == jax.tree.structure(params) and all(
        a.shape == b.shape for a, b in zip(jax.tree.leaves(want),
                                           jax.tree.leaves(params))),
        "the benchmark's weights do not have the program's layout")
    jax.block_until_ready(params)
    ctx.log("weights made")

    gcfg = GustServeConfig(**g)
    scfg = ServeConfig(batch=s["batch"], seq_len=s["seq_len"],
                       dtype=s["dtype"], gust=gcfg,
                       queue_capacity=int(mix.get("queue_capacity", 64)))
    t_b = time.perf_counter()
    loop = ServeLoop(lm, params, scfg, seed=ctx.seed % (1 << 31))
    jax.block_until_ready(loop._decode_extra)
    engine_build_s = time.perf_counter() - t_b
    ctx.log(f"engine built in {engine_build_s:.1f} s")
    tree = loop.gust_tree
    if ctx.require_chip:
        from repro.kernels.gust_spmv import _resolve_interpret

        ctx.require(not _resolve_interpret(gcfg.plan_config.interpret),
                    "GUST kernels would run interpreted")
    ctx.require(not gcfg.use_kernel or _fusable(tree),
                "a packed stream is not fusable: the kernel backend would "
                "run the jnp path")
    ctx.require("fallbacks" not in tree["stats"],
                f"fallbacks while building plans: {tree['stats'].get('fallbacks')}")

    work = traffic.generate(mix, ctx.seed, cfg["vocab_size"], ctx.kinds_dir)
    clients = _serving.Clients(loop, work)
    _serving.warm_prompt_lengths(loop, work)
    clients.start(time.perf_counter())
    for _ in range(2):  # admit every client (all prompt lengths), decode
        loop.step()
        clients.after_step(time.perf_counter())
    ctx.require(len(clients.streams) < len(loop.slots)
                or all(sl.active for sl in loop.slots),
                "not every slot is serving after warm-up")

    stats0 = dict(loop.read_counters())
    ctx.log("warmed up")
    with ctx.window():
        t0 = time.perf_counter()
        setup_s = ctx.now_age()
        while True:
            clients.release(t0, time.perf_counter())
            loop.step()
            t1 = time.perf_counter()
            clients.after_step(t1)
            if t1 - t0 >= ctx.seconds:
                break
    stats1 = dict(loop.read_counters())
    rec = {
        "kind": "serving",
        "setup_s": setup_s,
        "engine_build_s": engine_build_s,
        "window": {"t0": t0, "t1": t1, "seconds": t1 - t0},
        "batch": s["batch"],
        "stats": {k: _delta(stats0.get(k, 0), stats1[k]) for k in stats1},
        "memory_peak_bytes": harness.memory_peak_bytes(jax.devices()[:1]),
        "config": cfg,
        "peak": ctx.peak,
        "expert_nnz": nnz,
        "build_s": tree["stats"]["build_s"],
    }
    ctx.require(rec["stats"]["decode_retries"] == 0,
                f"{rec['stats']['decode_retries']} contained decode failures")
    rec["requests"] = [
        {"prompt_len": int(len(r["prompt"])), "max_new": r["max_new"],
         "sent": r["sent"], "due": r["due"], "deliveries": r["deliveries"],
         "status": r["status"]}
        for r in clients.reqs.values()]
    ended = [r for r in clients.reqs.values() if r["status"] is not None]
    rec["attempted"] = sum(1 for r in clients.reqs.values() if r["sent"] <= t1)
    rec["failed"] = sum(1 for r in ended if r["status"] != "DONE")
    rec["trace"] = ctx.reduced_trace()

    ctx.log("window closed")
    served = clients.served()
    del loop, clients, tree
    gc.collect()
    control = cfg["control"]["reference"] if ctx.control else None
    gaps = ref_moe.widest_gaps(cfg, params, served, s["seq_len"], control)
    rec["check"] = gaps
    ctx.log(f"reference compared {gaps['tokens']} served tokens")
    prefix = "control_" if ctx.control else ""
    for name, limit in cfg["limits"].items():
        ctx.compare(name, gaps[prefix + name], limit)
    return rec
