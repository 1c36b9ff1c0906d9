"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; ``reduce`` clips them to the benchmark's window annotation and
computes, over the device planes:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
- ``window_s``: the length of the window annotation;
- ``ops``: per operation name, its count, summed device seconds, and
  whether it encloses other operations (``parent``: a ``while`` that
  runs a whole decode step holds its body's operations on the same
  line);
- ``breakdown``: the ten operations that took most time, parents left
  out and names cut to the instruction, its result type and its custom
  call target; and the ten longest gaps in which no operation ran, each
  named by the host event that covered most of it.

Events are ``{"plane", "line", "name", "start_ns", "dur_ns", "stats"}``
dicts, so a small recorded trace can be kept as JSON and reduced again.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench_window"  # the host annotation around the measured window
#: the device line whose events are operations (modules enclose them)
OP_LINE = "XLA Ops"
KEEP_STATS = ("hlo_op", "long_name", "tf_op", "hlo_category", "name")


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def load(trace_dir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = _is_device(plane.name)
        for line in plane.lines:
            for ev in line.events:
                stats = {}
                if device:
                    for k, v in ev.stats:
                        if k in KEEP_STATS:
                            stats[k] = str(v)
                events.append({"plane": plane.name, "line": line.name,
                               "name": ev.name, "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns),
                               "stats": stats})
    return events


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 f32[8,256]``, with `` tpu_custom_call`` (or another
    custom call target) where the instruction names one."""
    head, _, rest = name.partition(" = ")
    out = head.lstrip("%")
    rtype = ("(tuple)" if rest.startswith("(")
             else rest.split(" ", 1)[0].split("{", 1)[0])
    if rtype:
        out += " " + rtype
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        out += " " + target.group(1)
    return out


def _parents(dev: list) -> set:
    """Indices (into ``dev``) of the events that enclose the next event
    of their plane: sorted by start, longest first, a parent's first
    child comes right after it."""
    order = sorted(range(len(dev)), key=lambda i: (
        dev[i]["plane"], dev[i]["start_ns"], -dev[i]["dur_ns"]))
    out = set()
    for i, j in zip(order, order[1:]):
        a, b = dev[i], dev[j]
        if (a["plane"] == b["plane"] and b["start_ns"] < a["start_ns"]
                + a["dur_ns"] and b["start_ns"] + b["dur_ns"]
                <= a["start_ns"] + a["dur_ns"]):
            out.add(i)
    return out


def _clip(ev, lo, hi):
    s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
    return max(s, lo), min(e, hi)


def reduce(events: list, top: int = 10) -> dict:
    """Window, busy time, per-op totals and the breakdown; ``{}`` when the
    trace holds no window annotation or no device operation in it."""
    wins = [e for e in events if e["name"] == WINDOW
            and not _is_device(e["plane"])]
    if not wins:
        return {}
    win = max(wins, key=lambda e: e["dur_ns"])
    lo, hi = win["start_ns"], win["start_ns"] + win["dur_ns"]
    dev = [e for e in events if _is_device(e["plane"]) and e["line"] == OP_LINE
           and e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo]
    if not dev:
        return {}
    planes = sorted({e["plane"] for e in dev})
    busy, all_gaps = 0.0, []
    for p in planes:
        iv = [_clip(e, lo, hi) for e in dev if e["plane"] == p]
        busy += union_ns(iv)
        all_gaps += gaps(iv, lo, hi)
    parents = _parents(dev)
    ops = {}
    for i, e in enumerate(dev):
        s, t = _clip(e, lo, hi)
        rec = ops.setdefault(e["name"], {"count": 0, "seconds": 0.0,
                                         "stats": e["stats"],
                                         "parent": False})
        rec["count"] += 1
        rec["seconds"] += (t - s) * 1e-9
        rec["parent"] |= i in parents
    host = [e for e in events if not _is_device(e["plane"])
            and e["name"] != WINDOW and e["dur_ns"] > 0]
    named = []
    for s, t in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "no host event", 0.0
        for h in host:
            c = min(t, h["start_ns"] + h["dur_ns"]) - max(s, h["start_ns"])
            if c > cover:
                best, cover = h["name"], c
        named.append([best, (t - s) * 1e-9])
    leaves = [kv for kv in ops.items() if not kv[1]["parent"]]
    top_ops = sorted(leaves, key=lambda kv: -kv[1]["seconds"])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / len(planes) * 1e-9,
        "devices": len(planes),
        "ops": ops,
        "breakdown": {"device_ops": [[short_name(k), v["seconds"]]
                                     for k, v in top_ops],
                      "idle_gaps": named},
    }
