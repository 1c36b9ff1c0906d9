"""Arithmetic over a run record that more than one metric reader uses."""

from __future__ import annotations

import re

from . import work

#: A GUST kernel's events in the device trace: the Pallas custom calls
#: the program's SpMV builders lower to (``lib/trace`` keeps each
#: operation's name and its ``long_name``/``tf_op`` statistics).
GUST_KERNEL = re.compile(r"tpu_custom_call|pallas_call")


def deliveries_in_window(rec: dict):
    """Per request: its prompt length, and each delivery in the window as
    (time, index of its first token, tokens)."""
    t0, t1 = rec["window"]["t0"], rec["window"]["t1"]
    out = []
    for r in rec["requests"]:
        seen, ds = 0, []
        for t, n in r["deliveries"]:
            if t0 < t <= t1:
                ds.append((t, seen, n))
            seen += n
        out.append((r["prompt_len"], ds))
    return out


def tokens_in_window(rec: dict) -> int:
    return sum(n for _, ds in deliveries_in_window(rec) for _, _, n in ds)


def token_gaps(rec: dict) -> list:
    """Every gap between two consecutive deliveries of one request whose
    both ends lie in the window (two tokens delivered by one step return
    have no gap between them)."""
    t0, t1 = rec["window"]["t0"], rec["window"]["t1"]
    out = []
    for r in rec["requests"]:
        times = [t for t, _ in r["deliveries"]]
        out += [b - a for a, b in zip(times, times[1:]) if a >= t0 and b <= t1]
    return out


def served_flops(rec: dict) -> int:
    """Operations the tokens delivered in the window required: a
    request's first token its whole prompt, every later token one
    decode position."""
    cfg = rec["config"]
    nnz = sum(sum(v) for v in rec["mlp_nnz"].values())
    total = 0
    for prompt_len, ds in deliveries_in_window(rec):
        for _, first, n in ds:
            for j in range(first, first + n):
                if j == 0:
                    total += work.prompt_flops(cfg, nnz, prompt_len)
                else:
                    total += work.lm_token_flops(cfg, nnz, prompt_len + j - 1)
    return total


def kernel_seconds(trace: dict) -> float:
    """Summed device seconds of the GUST kernels' events in the window."""
    total = 0.0
    for name, op in trace.get("ops", {}).items():
        if op.get("parent"):
            continue
        text = " ".join([name] + list(op["stats"].values()))
        if GUST_KERNEL.search(text):
            total += op["seconds"]
    return total


def roofline_share(least_seconds: float, trace: dict):
    """Least time over measured kernel time, in %; None without kernel
    events to read."""
    t = kernel_seconds(trace)
    return 100.0 * least_seconds / t if t > 0 else None


def idle_share(trace: dict):
    if not trace or trace.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
