"""The reduction from trace events to busy time, idle share, kernel time
and the breakdown, by hand on small traces."""

import benchtools  # noqa: F401  (puts bench/ on the path)
from lib import records, trace


def ev(plane, line, name, start, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


DEV, HOST = "/device:TPU:0", "/host:CPU"
SMALL = [
    ev(HOST, "python", "bench_window", 100, 1000),
    ev(HOST, "python", "PjitFunction(step)", 90, 400),
    ev(HOST, "python", "sample", 600, 300),
    ev(DEV, "XLA Ops", "fusion.1", 50, 100),      # clipped to 100..150
    ev(DEV, "XLA Ops", "gust_kernel", 150, 300, long_name="pallas_call"),
    ev(DEV, "XLA Ops", "fusion.2", 400, 100),     # overlaps the kernel
    ev(DEV, "XLA Ops", "gust_kernel", 900, 150),
    ev(DEV, "XLA Modules", "jit_step", 50, 1050),  # not an op line
    ev(DEV, "XLA Ops", "late", 2000, 10),          # outside the window
]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30),
                                                               (40, 50)]


def test_reduce_small_trace_by_hand():
    r = trace.reduce(SMALL)
    assert abs(r["window_s"] - 1000e-9) < 1e-15
    # busy: 100..500 and 900..1050 -> 550 ns of the 1000 ns window
    assert abs(r["busy_s"] - 550e-9) < 1e-15
    assert abs(records.idle_share(r) - 45.0) < 1e-9
    assert r["ops"]["gust_kernel"]["count"] == 2
    assert abs(r["ops"]["gust_kernel"]["seconds"] - 450e-9) < 1e-15
    assert "late" not in r["ops"]
    assert r["breakdown"]["device_ops"][0][0] == "gust_kernel"
    # idle 500..900 lies mostly under the host's "sample"; 1050..1100
    # under no host event
    (n1, g1), (n2, g2) = r["breakdown"]["idle_gaps"]
    assert n1 == "sample" and abs(g1 - 400e-9) < 1e-15
    assert n2 == "no host event" and abs(g2 - 50e-9) < 1e-15


def test_kernel_seconds_and_roofline_share():
    r = trace.reduce(SMALL)
    k = records.kernel_seconds(r)
    assert abs(k - 450e-9) < 1e-15
    assert abs(records.roofline_share(225e-9, r) - 50.0) < 1e-9
    assert records.roofline_share(1e-9, {"ops": {}}) is None


def test_reduce_needs_a_window_and_device_ops():
    assert trace.reduce([e for e in SMALL if e["name"] != "bench_window"]) == {}
    assert trace.reduce([e for e in SMALL if e["plane"] == HOST]) == {}


def test_parents_left_out_of_the_breakdown_and_names_cut():
    """A ``while`` that encloses a step's operations counts once in the
    busy time, is no kernel, and leaves the breakdown to its leaves; an
    instruction's text is cut to its name, result type and target."""
    loop = ev(DEV, "XLA Ops", "%while.3 = (f32[8]{0}) while(%t), "
              "body=%region_1", 120, 900)
    r = trace.reduce(SMALL + [loop])
    assert abs(r["busy_s"] - 950e-9) < 1e-15
    assert r["ops"][loop["name"]]["parent"]
    assert abs(records.kernel_seconds(r) - 450e-9) < 1e-15
    assert [n for n, _ in r["breakdown"]["device_ops"]] == [
        "gust_kernel", "fusion.2", "fusion.1"]
    assert trace.short_name(
        '%_execute_spmm_impl.23 = f32[16,8,256]{2,1,0:T(8,128)S(1)} '
        'custom-call(f32[22656,256]{1,0} %a), custom_call_target='
        '"tpu_custom_call"') == "_execute_spmm_impl.23 f32[16,8,256] " \
        "tpu_custom_call"

