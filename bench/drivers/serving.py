"""Driver for a served language model: ``ServeLoop`` with GUST decode.

Set-up: the model's weights from the seed (``lib/lm_weights``, MLPs
pruned by the benchmark), the program's engine (``ServeLoop``: gustify
colours every plan, jitted prefill and decode), and the first
request of every client admitted, which compiles or loads every program
the window uses (the mix's prompt lengths all appear in its first
block).  Window: ``ServeLoop.step`` in a loop; a client sends its next
request as soon as its last one is done (closed loop).  Every token's
delivery is timed on the host clock at the return of the step that
delivered it.  Check: once the window has closed and the engine is
freed, the plain reference (``lib/ref_lm``) scores every request that
was served a token: the gap by which each served token's logit lies
below the reference's best, its widest and its mean; each of these the
configuration names under ``limits`` is compared with its limit.  The control (``ctx.control``) is the reference in the
configuration's ``control.reference`` precision put in the program's
place: at the same prompts and served tokens, the token it puts first at
each position is scored as if it had been served, and those numbers are
compared.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import harness, lm_weights, ref_lm, traffic  # noqa: E402


def program_arch(cfg: dict):
    """The program's model for the configuration, checked against it."""
    import dataclasses

    from repro.configs.base import get_arch

    arch = get_arch(cfg["program"]["arch"])
    if cfg["program"].get("reduced"):  # the program's own smoke widths
        arch = arch.reduced()
    arch = dataclasses.replace(arch, n_layers=cfg["num_hidden_layers"],
                               tie_embeddings=cfg["tie_word_embeddings"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
            "vocab": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "padded_vocab": cfg["vocab_size"]}
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise harness.RunFailure(f"program arch {arch.name} {got} differs "
                                 f"from the configuration {want}")
    return arch


def _fusable(tree) -> bool:
    from repro.core.plan import GustPlan

    return all(
        GustPlan.from_spec({"leaves": {k: v[0] for k, v in m["leaves"].items()},
                            "meta": m["meta"]}).artifact.fusable
        for m in tree["mats"].values())


class Clients:
    """The callers: closed-loop clients, each with its request stream,
    and open-loop arrivals due at fixed times after the window opens.
    Per request it keeps what was sent, when it was due and when each
    token came back."""

    def __init__(self, loop, work):
        self.loop = loop
        self.streams = work.get("streams", [])
        self.arrivals = sorted(work.get("arrivals", []), key=lambda a: a[0])
        self.next = [0] * len(self.streams)
        self.due_next = 0  # index of the next open-loop arrival
        self.reqs = {}  # rid -> request record
        self.open = set()

    def _enqueue(self, prompt, max_new, t, client=None, due=None) -> None:
        rid = self.loop.enqueue(prompt, max_new)
        self.reqs[rid] = {"client": client, "prompt": prompt,
                          "max_new": max_new, "sent": t, "due": due,
                          "deliveries": [], "seen": 0, "status": None}
        self.open.add(rid)

    def send(self, c: int, t: float) -> None:
        stream = self.streams[c]  # a client that runs out starts over
        prompt, max_new = stream[self.next[c] % len(stream)]
        self.next[c] += 1
        self._enqueue(prompt, max_new, t, client=c)

    def start(self, t: float) -> None:
        """Every closed-loop client sends its first request."""
        for c in range(len(self.streams)):
            self.send(c, t)

    def release(self, t0: float, t: float) -> None:
        """Send every open-loop arrival due by ``t`` (window opened at
        ``t0``)."""
        while (self.due_next < len(self.arrivals)
               and t0 + self.arrivals[self.due_next][0] <= t):
            due, prompt, max_new = self.arrivals[self.due_next]
            self._enqueue(prompt, max_new, t, due=t0 + due)
            self.due_next += 1

    def _deliver(self, rid: int, n: int, t: float) -> None:
        r = self.reqs[rid]
        if n > r["seen"]:
            r["deliveries"].append([t, n - r["seen"]])
            r["seen"] = n

    def after_step(self, t: float) -> None:
        for s in self.loop.slots:
            if s.active and s.request_id in self.reqs:
                self._deliver(s.request_id, len(s.generated), t)
        for rid in [r for r in self.open if r in self.loop.results]:
            res = self.loop.results[rid]
            self._deliver(rid, len(res.tokens), t)
            self.reqs[rid]["status"] = res.status.name
            self.reqs[rid]["tokens"] = list(res.tokens)
            self.open.discard(rid)
            if self.reqs[rid]["client"] is not None:
                self.send(self.reqs[rid]["client"], t)

    def served(self) -> list:
        """(prompt, tokens) of every request that was served a token."""
        live = {s.request_id: list(s.generated)
                for s in self.loop.slots if s.active}
        out = []
        for rid, r in self.reqs.items():
            toks = r.get("tokens", live.get(rid))
            if toks:
                out.append((r["prompt"], toks))
        return out


def warm_prompt_lengths(loop, work) -> None:
    """Serve one short request of every open-loop prompt length, so that
    each length's prefill is compiled or loaded before the window."""
    lengths = sorted({len(p) for _, p, _ in work.get("arrivals", [])})
    for n in lengths:
        loop.enqueue(np.zeros(n, np.int32), 1)
    while loop.pending or any(s.active for s in loop.slots):
        loop.step()


def run(ctx) -> dict:
    import jax

    from repro.models.model_zoo import build_model
    from repro.serving import GustServeConfig, ServeConfig, ServeLoop

    cfg, mix = ctx.config, ctx.mix
    g, s = cfg["gust"], cfg["serve"]
    lm = build_model(program_arch(cfg))
    params, nnz = lm_weights.make_params(cfg, g["density"], ctx.seed)
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
    clients = Clients(loop, work)
    warm_prompt_lengths(loop, work)
    clients.start(time.perf_counter())
    for _ in range(2):  # admit every client (all prompt lengths), decode
        loop.step()
        clients.after_step(time.perf_counter())
    ctx.require(len(clients.streams) < len(loop.slots)
                or all(sl.active for sl in loop.slots),
                "not every slot is serving after warm-up")

    stats0 = dict(loop.stats)
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
    stats1 = dict(loop.stats)
    rec = {
        "kind": "serving",
        "setup_s": setup_s,
        "engine_build_s": engine_build_s,
        "window": {"t0": t0, "t1": t1, "seconds": t1 - t0},
        "batch": s["batch"],
        "stats": {k: stats1[k] - stats0.get(k, 0) for k in stats1},
        "memory_peak_bytes": harness.memory_peak_bytes(jax.devices()[:1]),
        "config": cfg,
        "peak": ctx.peak,
        "mlp_nnz": nnz,
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
    gaps = ref_lm.widest_gaps(cfg, params, served, s["seq_len"], control)
    rec["check"] = gaps
    ctx.log(f"reference compared {gaps['tokens']} served tokens")
    prefix = "control_" if ctx.control else ""
    for name, limit in cfg["limits"].items():
        ctx.compare(name, gaps[prefix + name], limit)
    return rec
