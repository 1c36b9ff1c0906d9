"""Kernel resource/race audit — static ``GUST-Kxx`` checks over the
Pallas kernel builders, from their source AST alone (no jax import, no
kernel execution, runs on any machine).

Three checks per kernel module (``kernels/gust_spmv.py``,
``gust_spmv_ragged.py``, ``gust_spgemm.py``, ``gather_fill.py``):

* **GUST-K01 — VMEM footprint.**  For every ``make_*`` builder, evaluate
  the BlockSpec tile shapes and ``pltpu.VMEM`` scratch shapes under an
  audit config (the builder's local arithmetic — ``num_cb = c_pad //
  c_blk`` etc. — is interpreted symbolically) and report the resulting
  VMEM bytes against the ~16 MB/core budget (pallas_guide.md).
  Pipelined operand/output tiles are counted twice (Pallas
  double-buffers them); ``memory_space=ANY`` operands are free; tile
  element size is taken as 4 bytes (f32 — an upper bound for the int8 /
  bf16 / int16 streams).  Every tile is counted at its padded VMEM
  footprint: the minor dimension rounds up to 128 lanes and the
  second-minor to whole sublane tiles (8 rows of 32-bit words; 16 / 32
  rows for 2- / 1-byte scratch), so a narrow batch axis is charged what
  the chip actually allocates.  An over-budget config is an ``error`` finding:
  the audit configs are chosen to fit, so exceeding the budget means a
  builder's footprint grew.
* **GUST-K02 — DB ping/pong pairing.**  In every double-buffered kernel
  body (a function issuing ``.start()``/``.wait()`` on async-copy
  descriptors around a ``fori_loop``), verify the race-freedom protocol
  structurally: (a) an initial ``.start()`` fills slot 0 before the
  loop; (b) every in-loop ``.start()`` targets the *other* slot
  (``1 - slot``) and sits under a ``pl.when`` bound guard; (c) the loop
  waits on the current slot **before** any read of a ping/pong scratch
  at ``[slot]`` — i.e. every ``make_async_copy`` start has a matching
  semaphore wait before its scratch slot is reused.
* **GUST-K03 — grid-index bounds.**  Every subscript of a
  scalar-prefetch steering table (``seg``/``bw``/``bs`` and their
  ``_ref`` forms, in index-map lambdas and kernel bodies) is evaluated
  at the grid maxima and compared against the table's extent
  (``seg``: blocks×S_blk, ``bw``: num_blocks, ``bs``: num_windows+1).

Entry point: :func:`audit_kernels` → :class:`AuditResult` with
per-builder :class:`KernelReport` rows and :class:`AuditFinding`
violations.  ``python -m repro.analysis audit`` prints the report and
exits nonzero on any finding.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Tuple

__all__ = [
    "AuditFinding",
    "KernelReport",
    "AuditResult",
    "audit_kernels",
    "VMEM_BUDGET_BYTES",
]

#: ~16 MB of VMEM per TPU core (pallas_guide.md, "Memory Spaces").
VMEM_BUDGET_BYTES = 16 * 2 ** 20

#: Kernel modules under repro/kernels owning pallas builders.
_KERNEL_MODULES = (
    "gust_spmv.py",
    "gust_spmv_ragged.py",
    "gust_spgemm.py",
    "gather_fill.py",
)

#: Scalar-prefetch steering tables and their extents (as expressions
#: over the audit config) per module.
_TABLE_EXTENTS: Dict[str, Dict[str, str]] = {
    "gust_spmv.py": {"seg": "t_blk * s_blk"},
    "gust_spmv_ragged.py": {
        "seg": "num_blocks * s_blk",
        "bw": "num_blocks",
        "bs": "num_windows + 1",
    },
    "gust_spgemm.py": {"bw": "num_blocks", "bs": "num_windows + 1"},
    "gather_fill.py": {},
}

_DTYPE_ITEMSIZE = {
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "float64": 8, "int64": 8,
}

#: Default audit configs: one tiny CI geometry and one serving-shaped
#: geometry (l=256 is the paper's hardware length).  Every builder picks
#: the names its signature mentions.
DEFAULT_CONFIGS: Tuple[Dict[str, object], ...] = (
    dict(name="tiny", num_windows=4, c_pad=16, l=8, seg_count=4, s_blk=4,
         b=8, c_blk=8, num_blocks=8, total_rows=16, r_rows=16, k_max=4,
         n_out=16, value_dtype="float32", index_dtype="int32",
         x_dtype="float32"),
    dict(name="serve256", num_windows=16, c_pad=64, l=256, seg_count=64,
         s_blk=8, b=8, c_blk=8, num_blocks=128, total_rows=1024,
         r_rows=256, k_max=8, n_out=256, value_dtype="int8",
         index_dtype="int16", x_dtype="float32"),
    # yi_6b w_down (4096 x 11008, l=256) decoded at batch 4: the widest
    # resident x the serving path holds (43 segments)
    dict(name="yi6b_down", num_windows=16, c_pad=1152, l=256, seg_count=43,
         s_blk=43, b=4, c_blk=8, num_blocks=2304, total_rows=1024,
         r_rows=256, k_max=8, n_out=256, value_dtype="float32",
         index_dtype="int32", x_dtype="float32"),
)

#: (lanes, 32-bit sublanes) of one VMEM tile.
_LANES, _SUBLANES = 128, 8


def _padded_elems(dims: Tuple[int, ...], itemsize: int) -> int:
    """Elements a VMEM buffer of ``dims`` occupies once its minor dim is
    rounded up to whole lanes and its second-minor dim to whole sublane
    tiles (packed dtypes stack more rows per tile)."""
    dims = [int(d) for d in dims]
    if dims:
        dims[-1] = -(-dims[-1] // _LANES) * _LANES
    if len(dims) >= 2:
        rows = _SUBLANES * max(4 // itemsize, 1)
        dims[-2] = -(-dims[-2] // rows) * rows
    n = 1
    for d in dims:
        n *= d
    return n


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    rule: str        # GUST-K01 | GUST-K02 | GUST-K03
    severity: str    # "error"
    builder: str     # module::function
    message: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.builder}: {self.message}"


@dataclasses.dataclass(frozen=True)
class KernelReport:
    builder: str           # module::function
    config: str            # audit config name
    vmem_bytes: int
    budget: int = VMEM_BUDGET_BYTES
    tiles: Tuple[str, ...] = ()

    @property
    def over_budget(self) -> bool:
        return self.vmem_bytes > self.budget

    def __str__(self) -> str:
        pct = 100.0 * self.vmem_bytes / self.budget
        flag = "  OVER BUDGET" if self.over_budget else ""
        return (f"{self.builder:55s} {self.config:9s} "
                f"{self.vmem_bytes / 2**20:8.3f} MiB ({pct:5.1f}%){flag}")


@dataclasses.dataclass(frozen=True)
class AuditResult:
    reports: Tuple[KernelReport, ...]
    findings: Tuple[AuditFinding, ...]
    db_kernels_checked: Tuple[str, ...]
    subscripts_checked: int

    @property
    def ok(self) -> bool:
        return not self.findings


# ---------------------------------------------------------------------------
# tiny symbolic evaluator over builder-local integer arithmetic
# ---------------------------------------------------------------------------


class _Unsupported(Exception):
    pass


def _eval(node: ast.AST, env: Dict[str, object]):
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _Unsupported(node.id)
    if isinstance(node, ast.Tuple):
        return tuple(_eval(e, env) for e in node.elts)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval(node.operand, env)
    if isinstance(node, ast.BinOp):
        left, right = _eval(node.left, env), _eval(node.right, env)
        op = node.op
        if isinstance(op, ast.Add):
            return left + right
        if isinstance(op, ast.Sub):
            return left - right
        if isinstance(op, ast.Mult):
            return left * right
        if isinstance(op, ast.FloorDiv):
            return left // right
        if isinstance(op, ast.Mod):
            return left % right
        if isinstance(op, ast.Div):
            return left / right
    raise _Unsupported(ast.dump(node)[:60])


def _itemsize(node: Optional[ast.AST], env: Dict[str, object]) -> int:
    """Element size of a dtype expression (``jnp.float32``, or a local
    like ``vdt`` bound from ``jnp.dtype(value_dtype)``).  Unknown → 4
    (the f32 upper bound for every stream the kernels carry)."""
    if node is None:
        return 4
    if isinstance(node, ast.Attribute):
        return _DTYPE_ITEMSIZE.get(node.attr, 4)
    if isinstance(node, ast.Name):
        v = env.get(node.id)
        if isinstance(v, int):
            return v
        if isinstance(v, str):
            return _DTYPE_ITEMSIZE.get(v, 4)
    return 4


def _bind_assigns(fn: ast.FunctionDef, env: Dict[str, object]) -> None:
    """Interpret the builder's simple local assignments into ``env``:
    integer arithmetic, ``_batch_pad`` / ``_resident_x_rows`` and
    ``jnp.dtype(<name>)`` (bound to its itemsize).  Anything richer is
    skipped."""

    def value_of(node: ast.AST):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("_batch_pad", "_resident_x_rows") \
                and len(node.args) == 1:
            # both round their argument up to whole groups of 8
            v = _eval(node.args[0], env)
            return -(-v // _SUBLANES) * _SUBLANES
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "dtype" and node.args:
            name = node.args[0]
            if isinstance(name, ast.Name) and isinstance(env.get(name.id), str):
                return _DTYPE_ITEMSIZE.get(env[name.id], 4)
            if isinstance(name, ast.Constant):
                return _DTYPE_ITEMSIZE.get(name.value, 4)
            raise _Unsupported("dtype")
        return _eval(node, env)

    for stmt in ast.walk(fn):
        if not isinstance(stmt, ast.Assign) or len(stmt.targets) != 1:
            continue
        tgt = stmt.targets[0]
        try:
            if isinstance(tgt, ast.Name):
                env[tgt.id] = value_of(stmt.value)
            elif isinstance(tgt, ast.Tuple) and isinstance(stmt.value, ast.Tuple) \
                    and len(tgt.elts) == len(stmt.value.elts):
                for t, v in zip(tgt.elts, stmt.value.elts):
                    if isinstance(t, ast.Name):
                        env[t.id] = value_of(v)
        except _Unsupported:
            continue


# ---------------------------------------------------------------------------
# GUST-K01: VMEM footprint per builder
# ---------------------------------------------------------------------------


def _builder_footprint(fn: ast.FunctionDef, config: Dict[str, object]):
    """(bytes, tile descriptions) for one ``make_*`` builder under one
    audit config — or None when the config lacks a parameter the builder
    needs (different kernel family)."""
    params = [a.arg for a in fn.args.args] + [a.arg for a in fn.args.kwonlyargs]
    env: Dict[str, object] = {}
    for p in params:
        if p in ("interpret", "quantized"):
            continue
        if p not in config:
            return None
        env[p] = config[p]
    _bind_assigns(fn, env)

    total = 0
    tiles: List[str] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Attribute):
            continue
        if node.func.attr == "BlockSpec":
            if not node.args:      # memory_space=ANY: stays in HBM
                continue
            shape = node.args[0]
            if not isinstance(shape, ast.Tuple):
                continue
            try:
                dims = _eval(shape, env)
            except _Unsupported as e:
                raise _Unsupported(f"BlockSpec shape: {e}") from None
            total += 2 * _padded_elems(dims, 4) * 4  # pipelined, x2
            tiles.append(f"tile{tuple(int(d) for d in dims)}x2")
        elif node.func.attr == "VMEM":
            shape = node.args[0]
            try:
                dims = _eval(shape, env)
            except _Unsupported as e:
                raise _Unsupported(f"VMEM scratch shape: {e}") from None
            isz = _itemsize(node.args[1] if len(node.args) > 1 else None, env)
            total += _padded_elems(dims, isz) * isz
            tiles.append(f"scratch{tuple(int(d) for d in dims)}@{isz}B")
    return total, tuple(tiles)


# ---------------------------------------------------------------------------
# GUST-K02: DB ping/pong start/wait pairing
# ---------------------------------------------------------------------------

#: helpers that construct async-copy descriptors; index of the slot arg.
_COPY_HELPERS = {"copy": 0, "copies": 0, "stream_copy": 3}


def _slot_kind(node: ast.AST) -> str:
    """Classify a slot expression: the loop's current slot (``slot``),
    the opposite slot (``1 - slot``), a constant (initial fill), or
    unknown."""
    if isinstance(node, ast.Name) and node.id == "slot":
        return "cur"
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
            and isinstance(node.left, ast.Constant) and node.left.value == 1 \
            and isinstance(node.right, ast.Name) and node.right.id == "slot":
        return "alt"
    if isinstance(node, ast.Constant):
        return "const"
    return "unknown"


@dataclasses.dataclass
class _Event:
    line: int
    kind: str        # "start" | "wait" | "read"
    slot: str        # _slot_kind result
    in_body: bool    # inside the fori_loop body fn
    guarded: bool    # inside a pl.when-decorated nested def of body


def _copy_slot_expr(call: ast.Call) -> Optional[ast.AST]:
    fn = call.func
    if isinstance(fn, ast.Name) and fn.id in _COPY_HELPERS:
        idx = _COPY_HELPERS[fn.id]
        if len(call.args) > idx:
            return call.args[idx]
    return None


def _collect_events(fn: ast.FunctionDef) -> List[_Event]:
    events: List[_Event] = []

    def walk(node: ast.AST, stack: Tuple[str, ...]) -> None:
        if isinstance(node, ast.FunctionDef) and node is not fn:
            stack = stack + (node.name,)
        in_body = "body" in stack
        guarded = in_body and stack[-1] != "body"
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("start", "wait") \
                and isinstance(node.func.value, ast.Call):
            slot = _copy_slot_expr(node.func.value)
            if slot is not None:
                events.append(_Event(node.lineno, node.func.attr,
                                     _slot_kind(slot), in_body, guarded))
        if isinstance(node, ast.For) and isinstance(node.iter, ast.Call):
            slot = _copy_slot_expr(node.iter)
            if slot is not None:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) \
                            and isinstance(sub.func, ast.Attribute) \
                            and sub.func.attr in ("start", "wait"):
                        events.append(_Event(sub.lineno, sub.func.attr,
                                             _slot_kind(slot), in_body,
                                             guarded))
        if isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name) \
                and node.value.id.endswith("scr") \
                and isinstance(node.ctx, ast.Load):
            if any(isinstance(s, ast.Name) and s.id == "slot"
                   for s in ast.walk(node.slice)):
                events.append(_Event(node.lineno, "read", "cur", in_body,
                                     guarded))
        for child in ast.iter_child_nodes(node):
            walk(child, stack)

    walk(fn, ())
    return sorted(events, key=lambda e: e.line)


def _check_db_pairing(module: str, fn: ast.FunctionDef) -> List[AuditFinding]:
    events = _collect_events(fn)
    if not any(e.kind in ("start", "wait") for e in events):
        return []          # not a manual-DMA kernel
    site = f"{module}::{fn.name}"
    out: List[AuditFinding] = []

    def err(msg: str) -> None:
        out.append(AuditFinding("GUST-K02", "error", site, msg))

    pre = [e for e in events if not e.in_body]
    body = [e for e in events if e.in_body]
    if not any(e.kind == "start" for e in pre):
        err("no initial .start() before the fori_loop — slot 0 is read "
            "without ever being filled")
    for e in body:
        if e.kind == "start":
            if e.slot != "alt":
                err(f"line {e.line}: in-loop .start() targets slot "
                    f"{e.slot!r}, not the opposite slot (1 - slot) — "
                    "overwrites data the current iteration still reads")
            if not e.guarded:
                err(f"line {e.line}: in-loop prefetch .start() is not "
                    "under a pl.when bound guard — runs past the stream "
                    "extent on the last iteration")
    waits = [e for e in body if e.kind == "wait" and e.slot == "cur"]
    reads = [e for e in body if e.kind == "read"]
    if not waits:
        err("fori_loop body never .wait()s on the current slot")
    elif reads and min(r.line for r in reads) < min(w.line for w in waits):
        err(f"line {min(r.line for r in reads)}: ping/pong scratch read "
            "at [slot] before the matching semaphore .wait() — the DMA "
            "may still be in flight")
    return out


# ---------------------------------------------------------------------------
# GUST-K03: steering-table subscript bounds at grid maxima
# ---------------------------------------------------------------------------


def _grid_max_env(config: Dict[str, object]) -> Dict[str, object]:
    env = {k: v for k, v in config.items() if isinstance(v, int)}
    env["num_cb"] = env["c_pad"] // env["c_blk"]
    env["t_blk"] = env["num_windows"] * env["num_cb"]
    # grid / loop variables at their maxima
    env["w"] = env["num_windows"] - 1
    env["cb"] = env["num_cb"] - 1
    env["s"] = env["s_blk"] - 1
    env["t"] = max(env["num_blocks"], env["t_blk"]) - 1
    env["i"] = max(env["num_cb"], env["num_blocks"]) - 1
    env["blk"] = env["num_cb"] - 1
    env["slot"] = 1
    return env


def _check_subscripts(module: str, tree: ast.Module,
                      config: Dict[str, object]):
    tables = _TABLE_EXTENTS.get(module, {})
    if not tables:
        return [], 0
    env = _grid_max_env(config)
    # 't' must stay inside the *family's* block count, not the max of
    # both families: within one module t ranges over its own stream.
    if module == "gust_spmv.py":
        env["t"] = env["t_blk"] - 1
    elif module in ("gust_spmv_ragged.py", "gust_spgemm.py"):
        env["t"] = env["num_blocks"] - 1
    findings: List[AuditFinding] = []
    checked = 0
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript) \
                or not isinstance(node.value, ast.Name):
            continue
        base = node.value.id
        key = base[:-4] if base.endswith("_ref") else base
        if key not in tables:
            continue
        try:
            idx = _eval(node.slice, env)
            extent = _eval(ast.parse(tables[key], mode="eval").body, env)
        except _Unsupported:
            continue
        checked += 1
        if not isinstance(idx, int):
            continue
        if idx >= extent or idx < 0:
            findings.append(AuditFinding(
                "GUST-K03", "error", f"{module}:{node.lineno}",
                f"subscript {base}[...] reaches {idx} at the grid maxima "
                f"but the table extent is {extent}"))
    return findings, checked


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _kernels_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "kernels")


def audit_kernels(
    configs: Optional[Tuple[Dict[str, object], ...]] = None,
    kernels_dir: Optional[str] = None,
) -> AuditResult:
    """Run all three static checks over every kernel module; returns the
    footprint reports and the (empty on a healthy tree) finding list."""
    configs = configs or DEFAULT_CONFIGS
    kdir = kernels_dir or _kernels_dir()
    reports: List[KernelReport] = []
    findings: List[AuditFinding] = []
    db_checked: List[str] = []
    subscripts = 0

    for module in _KERNEL_MODULES:
        path = os.path.join(kdir, module)
        if not os.path.exists(path):
            findings.append(AuditFinding(
                "GUST-K01", "error", module, "kernel module missing"))
            continue
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)

        for fn in tree.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            if fn.name.startswith("make_"):
                for cfg in configs:
                    try:
                        got = _builder_footprint(fn, cfg)
                    except _Unsupported as e:
                        findings.append(AuditFinding(
                            "GUST-K01", "error", f"{module}::{fn.name}",
                            f"unevaluable VMEM shape under config "
                            f"{cfg['name']}: {e}"))
                        continue
                    if got is None:
                        continue
                    total, tiles = got
                    rep = KernelReport(
                        builder=f"{module}::{fn.name}",
                        config=str(cfg["name"]), vmem_bytes=total,
                        tiles=tiles)
                    reports.append(rep)
                    if rep.over_budget:
                        findings.append(AuditFinding(
                            "GUST-K01", "error", rep.builder,
                            f"VMEM footprint {total / 2**20:.2f} MiB "
                            f"exceeds the {VMEM_BUDGET_BYTES / 2**20:.0f} "
                            f"MiB budget under config {cfg['name']}"))
            # DB pairing runs over every function (the db bodies are
            # private helpers, not builders)
            db = _check_db_pairing(module, fn)
            if db or any(e.kind in ("start", "wait")
                         for e in _collect_events(fn)):
                db_checked.append(f"{module}::{fn.name}")
            findings.extend(db)

        sub_findings, n = _check_subscripts(module, tree, dict(configs[0]))
        findings.extend(sub_findings)
        subscripts += n

    return AuditResult(
        reports=tuple(reports), findings=tuple(findings),
        db_kernels_checked=tuple(db_checked),
        subscripts_checked=subscripts,
    )
