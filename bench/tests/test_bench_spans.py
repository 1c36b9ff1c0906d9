"""The readers of the program's spans and named scopes, by hand on a
made record and trace, on a record of a program without them, and on
the record of a tiny served run on the CPU.  The made trace carries each
operation's op name under ``scopes.SCOPE_STAT``, as a trace reader that
keeps the events' metadata would give it."""

import argparse
import os

import pytest

import benchtools
from lib import harness, scopes, trace

READERS = ("serve_host_ms.decode", "admit_ms.decode", "prefill_ms.decode",
           "attn_ms.decode")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def reader(name):
    return harness.load_module(
        os.path.join(benchtools.BENCH, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read


def ev(plane, line, name, start, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


#: a window of 10 ms: one prefill program (a while enclosing two ops),
#: two decode steps with attention and kernel ops
TRACE = trace.reduce([
    ev(HOST, "python", "bench_window", 0, 10_000_000),
    ev(DEV, "XLA Ops", "%while.1 = (f32[8]) while(%t)", 0, 2_000_000,
       tf_op="jit(prefill_fn)/prefill/while"),
    ev(DEV, "XLA Ops", "%fusion.1 = f32[1,64] fusion(%a)", 0, 1_500_000,
       tf_op="jit(prefill_fn)/prefill/while/body/dot_general"),
    ev(DEV, "XLA Ops", "%fusion.2 = f32[1,64] fusion(%b)", 1_500_000,
       500_000, tf_op="jit(prefill_fn)/prefill/while/body/add"),
    ev(DEV, "XLA Ops", "%fusion.7 = f32[8,64] fusion(%c)", 3_000_000,
       400_000, tf_op="jit(decode_fn)/while/body/closed_call/attn/dot_general"),
    ev(DEV, "XLA Ops", "%scatter.2 = f32[8,64] scatter(%d)", 3_400_000,
       200_000, tf_op="attn/scatter"),
    ev(DEV, "XLA Ops", "%gust_spmv_padded_resident.1 = f32[16,8,256] "
       "custom-call(%e)", 3_600_000, 3_000_000,
       tf_op="jit(decode_fn)/while/body/closed_call/pallas_call"),
    ev(DEV, "XLA Ops", "%fusion.7 = f32[8,64] fusion(%c)", 7_000_000,
       400_000, tf_op="jit(decode_fn)/while/body/closed_call/attn/dot_general"),
    ev(DEV, "XLA Ops", "%fusion.9 = f32[8,64] fusion(%f)", 7_400_000,
       100_000, tf_op="jit(decode_fn)/attention_head/add"),
])

RECORD = {
    "kind": "serving",
    "stats": {"decode_steps": 2, "prefills": 1, "serve.step_s": 0.010,
              "serve.admit_s": 0.0025, "serve.wait_s": 0.0055,
              "serve.decode_s": 0.0015, "serve.prefill_s": 0.0004},
    "trace": TRACE,
}


def test_scope_seconds_reads_leaf_ops_under_the_scope():
    # the enclosing while is left out; "attention_head" is no "attn"
    assert scopes.scope_seconds(TRACE, "prefill") == pytest.approx(2e-3)
    assert scopes.scope_seconds(TRACE, "attn") == pytest.approx(1e-3)
    assert scopes.scope_seconds(TRACE, "mlp") is None
    assert scopes.scope_seconds({}, "attn") is None


def test_readers_on_a_made_record():
    got = {name: reader(name)(RECORD) for name in READERS}
    assert got == pytest.approx({
        "serve_host_ms.decode": (10.0 - 2.5 - 5.5) / 2,
        "admit_ms.decode": 2.5,
        "prefill_ms.decode": 2.0,
        "attn_ms.decode": 0.5,
    })


def test_readers_read_nothing_of_a_program_without_spans_or_scopes():
    bare = {"kind": "serving",
            "stats": {"decode_steps": 2, "prefills": 1},
            "trace": trace.reduce([
                ev(HOST, "python", "bench_window", 0, 1000),
                ev(DEV, "XLA Ops", "fusion.1", 0, 500,
                   tf_op="jit(decode_fn)/while/body/dot_general")])}
    library = {"kind": "library", "stats": RECORD["stats"], "trace": TRACE}
    for name in READERS:
        assert reader(name)(bare) is None
        assert reader(name)(library) is None
        assert reader(name)({"kind": "serving"}) is None


def test_counter_readers_on_a_tiny_served_run(tmp_path):
    """The spans' totals reach the run record as window deltas of
    ServeLoop.stats (``bench/drivers/serving.py``)."""
    root = benchtools.scratch_root(tmp_path)
    _, rec = benchtools.bench_module("run.py", "bench_run").run(
        argparse.Namespace(
            workload="tiny.chat", seed=2**33 + 7, seconds=1.0, trace=0,
            list=False, control=0),
        require_chip=False, root=str(root), with_record=True)
    st = rec["stats"]
    assert st["decode_steps"] > 0 and st["prefills"] > 0
    assert st["serve.step_s"] >= st["serve.wait_s"] + st["serve.admit_s"]
    host = reader("serve_host_ms.decode")(rec)
    admit = reader("admit_ms.decode")(rec)
    assert host > 0 and admit > 0
    assert host * st["decode_steps"] <= rec["window"]["seconds"] * 1e3
