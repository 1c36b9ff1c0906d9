"""Program spans and device names: the serve loop's spans under the
profiler (nesting, attributes, counts) and their totals in
``ServeLoop.stats``; the ``prefill`` and ``attn`` named scopes in the
served programs' op names; ``gustify``'s build phases."""

import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.core.scheduler import sched_counters
from repro.core.spans import Span
from repro.models.model_zoo import build_model
from repro.serving import GustServeConfig, ServeConfig, ServeLoop
from repro.serving.gust_serve import gustify

GCFG = GustServeConfig(density=0.5, gust_length=16)
CHILDREN = {
    "serve.step": {"serve.admit", "serve.decode", "serve.wait",
                   "serve.retire"},
    "serve.admit": {"serve.prefill", "serve.insert", "serve.first_token"},
}


@pytest.fixture(scope="module")
def gust_loop():
    cfg = get_arch("yi_6b").reduced()
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    sc = ServeConfig(batch=2, seq_len=32, dtype="float32", gust=GCFG)
    loop = ServeLoop(lm, params, sc)
    # warm every program the tests run: prompt lengths 5 and 6, decode
    for n in (5, 6):
        loop.enqueue(np.arange(n, dtype=np.int32), 2)
    loop.run_to_completion()
    return loop


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append({"name": ev.name, "line": line.name,
                                "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "stats": dict(ev.stats)})
    return out


def _parent(ev, events):
    """The innermost other span on ``ev``'s line that encloses it."""
    outer = [e for e in events if e is not ev and e["line"] == ev["line"]
             and e["start"] <= ev["start"] and ev["end"] <= e["end"]]
    return min(outer, key=lambda e: e["end"] - e["start"], default=None)


def test_span_adds_seconds_and_annotates():
    counters = {}
    with Span("a.b", counters):
        pass
    with Span("a.b", counters, rid=3):
        pass
    with pytest.raises(ValueError):
        with Span("a.c", counters, "c_s", step=1):
            raise ValueError("raised inside the span")
    assert set(counters) == {"a.b_s", "c_s"}
    assert all(v >= 0 for v in counters.values())


def test_serve_loop_spans_nest_under_profiler(gust_loop, tmp_path):
    loop = gust_loop
    prefills0 = loop.stats["prefills"]
    rids = {loop.enqueue(np.arange(n, dtype=np.int32), 2): n for n in (5, 6)}
    steps = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        while loop.pending or any(s.active for s in loop.slots):
            loop.step()
            steps += 1
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = [e["name"] for e in events]
    assert set(names) == set(CHILDREN) | set().union(*CHILDREN.values())
    assert names.count("serve.step") == steps
    assert names.count("serve.admit") == loop.stats["prefills"] - prefills0
    for ev in events:
        parent = _parent(ev, events)
        if ev["name"] == "serve.step":
            assert parent is None
            assert int(ev["stats"]["step_num"]) > 0
        else:
            assert ev["name"] in CHILDREN[parent["name"]], (ev, parent)
    admits = [e for e in events if e["name"] == "serve.admit"]
    assert {int(e["stats"]["rid"]): int(e["stats"]["prompt_len"])
            for e in admits} == rids


def test_serve_loop_span_totals_consistent(gust_loop):
    loop = gust_loop
    # a caller may replace or zero the stats (benchmarks/serve_bench.py)
    loop.stats = {"decode_steps": 0, "active_slot_steps": 0, "prefills": 0}
    for n in (5, 6, 5):
        loop.enqueue(np.arange(n, dtype=np.int32), 3)
    steps = 0
    while loop.pending or any(s.active for s in loop.slots):
        loop.step()
        steps += 1
    st = loop.stats
    assert st["prefills"] == 3 and 0 < st["decode_steps"] <= steps
    assert st["serve.step_s"] >= st["serve.wait_s"] + st["serve.admit_s"]
    assert st["serve.step_s"] >= (st["serve.admit_s"] + st["serve.decode_s"]
                                  + st["serve.wait_s"] + st["serve.retire_s"])
    assert st["serve.admit_s"] >= (st["serve.prefill_s"] + st["serve.insert_s"]
                                   + st["serve.first_token_s"])
    assert all(st[k] > 0 for k in st if k.startswith("serve."))


def test_served_programs_carry_named_scopes(gust_loop):
    import re

    loop = gust_loop

    def op_names(fn, *args):
        text = fn.lower(*args).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    b = loop.cfg.batch
    dec = op_names(loop._decode, loop.params, loop.caches,
                   jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
                   loop._counts, None, *loop._decode_extra)
    pre = op_names(loop._prefill, loop.params,
                   {"tokens": jnp.zeros((1, 5), jnp.int32)},
                   loop._cache_template_b1)
    in_scope = lambda names, s: [n for n in names  # noqa: E731
                                 if re.search(rf"(^|/){s}/", n)]
    assert in_scope(dec, "attn") and not in_scope(dec, "prefill")
    assert in_scope(pre, "prefill") and not in_scope(pre, "attn")
    # the KV update is attention's: the cache scatter carries the scope
    assert any("scatter" in n for n in in_scope(dec, "attn"))


def test_gustify_build_phases_within_wall_time(gust_loop):
    loop = gust_loop
    c0 = dict(sched_counters)
    tree = gustify(loop.lm, loop.params, GustServeConfig(density=0.4,
                                                         gust_length=16))
    phases, wall = tree["stats"]["build_s"], tree["stats"]["gustify_s"]
    assert set(phases) == {"prune", "colour", "pack", "stack", "upload"}
    assert all(v >= 0 for v in phases.values())
    assert phases["prune"] > 0 and phases["colour"] > 0 and phases["pack"] > 0
    assert sum(phases.values()) <= wall
    assert phases["colour"] == pytest.approx(
        sched_counters["colour_s"] - c0["colour_s"])
    assert phases["pack"] == pytest.approx(
        sched_counters["pack_s"] - c0["pack_s"])
