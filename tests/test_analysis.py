"""repro.analysis: artifact verifier mutation matrix, policy linter,
kernel audit, and the PlanStore verify-on-load mode.

The verifier tests are mutation tests: each seeds exactly one corruption
into a clean artifact's leaves and asserts exactly that rule fires —
plus a clean pass over both layouts x f32/int8 x both gathers that must
produce zero findings.
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO, SRC

from repro.analysis.verify import verify
from repro.core.formats import COOMatrix
from repro.core.plan import plan
from repro.core.plan_store import PlanStore


L = 8


def _coo(m=96, n=80, nnz=600, seed=3):
    r = np.random.default_rng(seed)
    idx = r.choice(m * n, size=nnz, replace=False)
    rows, cols = idx // n, idx % n
    vals = r.standard_normal(nnz).astype(np.float32)
    order = np.argsort(rows * n + cols)
    return COOMatrix((m, n), rows[order].astype(np.int64),
                     cols[order].astype(np.int64), vals[order])


def _leaves_meta(p):
    """Deep-copied (leaves, meta) wire form of a plan's artifact, safe to
    mutate."""
    spec = p.to_spec()
    leaves = {k: np.array(np.asarray(v)) for k, v in spec["leaves"].items()}
    return leaves, tuple(spec["meta"])


def _fired(leaves, meta):
    return sorted({f.rule for f in verify(leaves, meta)})


@pytest.fixture(scope="module")
def padded_f32():
    return plan(_coo(), l=L, layout="padded", value_dtype="float32",
                cache=None)


@pytest.fixture(scope="module")
def padded_int8():
    return plan(_coo(), l=L, layout="padded", value_dtype="int8",
                cache=None)


@pytest.fixture(scope="module")
def ragged_f32():
    return plan(_coo(), l=L, layout="ragged", value_dtype="float32",
                cache=None)


# ---------------------------------------------------------------------------
# clean artifacts: zero findings across the config matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["padded", "ragged"])
@pytest.mark.parametrize("value_dtype", ["float32", "int8"])
@pytest.mark.parametrize("gather", ["resident", "local"])
def test_clean_artifact_zero_findings(layout, value_dtype, gather):
    p = plan(_coo(), l=L, layout=layout, value_dtype=value_dtype,
             gather=gather, cache=None)
    assert p.verify() == []


def test_clean_bf16_and_balanced():
    for kw in (dict(value_dtype="bfloat16"),
               dict(load_balance=True),
               dict(load_balance=True, layout="ragged",
                    value_dtype="int8")):
        p = plan(_coo(seed=7), l=L, cache=None, **kw)
        assert p.verify() == []


# ---------------------------------------------------------------------------
# one mutation -> exactly one rule
# ---------------------------------------------------------------------------


def test_p01_padding_value_flip(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    m, seg = leaves["m_blk"], leaves["seg_blk"]
    c_pad, c_blk = meta[2], meta[5]
    row_zero = (m == 0).all(axis=1)
    target = None
    for r in range(m.shape[0]):
        # a padding row preceded by another padding row in its window,
        # inside a block whose first referenced segment is 0 (so the
        # slot's untouched col/col_loc stay remap-consistent)
        if (row_zero[r] and r % c_pad != 0 and row_zero[r - 1]
                and (r - 1) // c_pad == r // c_pad
                and seg[r // c_blk, 0] == 0):
            target = r
    assert target is not None, "no padded window with >= 2 padding rows"
    leaves["m_blk"][target, 0] = 1.0
    assert _fired(leaves, meta) == ["GUST-P01"]


def _all_padding_block_row(leaves, c_blk):
    m = leaves["m_blk"]
    t_blk = m.shape[0] // c_blk
    blk_zero = (m == 0).reshape(t_blk, -1).all(axis=1)
    ts = np.flatnonzero(blk_zero)
    assert ts.size, "no all-padding block in the stream"
    return int(ts[0]) * c_blk  # first row of the block


def test_p02_padding_col_not_lane(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    r = _all_padding_block_row(leaves, meta[5])
    # lane 0 -> the flipped offset l-1 (still fusable, still remapping
    # consistently through the all-padding block's segment-0 table row)
    leaves["col_blk"][r, 0] = L - 1
    leaves["col_loc"][r, 0] = L - 1
    assert _fired(leaves, meta) == ["GUST-P02"]


def test_p03_padding_row_nonzero(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    r = _all_padding_block_row(leaves, meta[5])
    leaves["row_blk"][r, 0] = 3
    assert _fired(leaves, meta) == ["GUST-P03"]


def test_p04_fusable_lane_structure(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    assert meta[4], "artifact must be fusable for the GUST-P04 test"
    m, col = leaves["m_blk"], leaves["col_blk"]
    target = None
    for r, j in zip(*np.nonzero(m)):
        off = col[r, j] % L
        # moving one column right stays in the segment and leaves the
        # allowed {lane, l-1-lane} set
        if off == j and (off + 1) % L != 0 and off + 1 != L - 1 - j:
            target = (r, j)
            break
    assert target is not None
    r, j = target
    leaves["col_blk"][r, j] += 1
    leaves["col_loc"][r, j] += 1
    assert _fired(leaves, meta) == ["GUST-P04"]


def test_p05_index_dtype_policy(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    leaves["col_blk"] = leaves["col_blk"].astype(np.int64)
    assert _fired(leaves, meta) == ["GUST-P05"]


def test_p06_block_starts_monotone(ragged_f32):
    leaves, meta = _leaves_meta(ragged_f32)
    leaves["block_starts"][1] = leaves["block_starts"][0]
    assert _fired(leaves, meta) == ["GUST-P06"]


def test_p07_block_window_ownership(ragged_f32):
    leaves, meta = _leaves_meta(ragged_f32)
    bs = leaves["block_starts"]
    b = int(bs[1])  # first window boundary: swap the blocks around it
    assert 0 < b < leaves["block_window"].shape[0]
    bw = leaves["block_window"]
    bw[b - 1], bw[b] = bw[b], bw[b - 1]
    assert _fired(leaves, meta) == ["GUST-P07"]


def _row_with_two_segments(seg):
    for t in range(seg.shape[0]):
        nz = seg[t][seg[t] > 0]
        if nz.size >= 2:
            return t
    raise AssertionError("no seg_blk row with two nonzero segments")


def test_p08_seg_row_unsorted(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    seg = leaves["seg_blk"]
    t = _row_with_two_segments(seg)
    pos = np.flatnonzero(seg[t] > 0)[:2]
    seg[t, pos[0]], seg[t, pos[1]] = seg[t, pos[1]], seg[t, pos[0]]
    assert _fired(leaves, meta) == ["GUST-P08"]


def test_p09_seg_out_of_bounds(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    seg = leaves["seg_blk"]
    seg_count = -(-meta[3][1] // L)
    assert meta[6] >= 2, "need S_blk >= 2"
    seg[0, meta[6] - 1] = seg_count  # stays sorted, lands out of bounds
    assert _fired(leaves, meta) == ["GUST-P09"]


def test_p10_col_loc_remap(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    m, col, loc, seg = (leaves["m_blk"], leaves["col_blk"],
                        leaves["col_loc"], leaves["seg_blk"])
    c_blk, s_blk = meta[5], meta[6]
    target = None
    for r, j in zip(*np.nonzero(m)):
        t = r // c_blk
        cur = loc[r, j] // L
        alt = cur + 1 if cur + 1 < s_blk else cur - 1
        if alt >= 0 and seg[t, alt] != col[r, j] // L:
            target = (r, j, alt)
            break
    assert target is not None
    r, j, alt = target
    leaves["col_loc"][r, j] = alt * L + loc[r, j] % L
    assert _fired(leaves, meta) == ["GUST-P10"]


def test_p11_scale_leaf_contract(padded_int8):
    leaves, meta = _leaves_meta(padded_int8)
    leaves["scale_blk"] = leaves["scale_blk"].astype(np.float64)
    assert _fired(leaves, meta) == ["GUST-P11"]


def test_p12_padding_block_scale(padded_int8):
    leaves, meta = _leaves_meta(padded_int8)
    r = _all_padding_block_row(leaves, meta[5])
    leaves["scale_blk"][r // meta[5]] = 2.0
    assert _fired(leaves, meta) == ["GUST-P12"]


def test_p13_quantized_peak(padded_int8):
    leaves, meta = _leaves_meta(padded_int8)
    m = leaves["m_blk"]
    c_blk = meta[5]
    t_blk = m.shape[0] // c_blk
    blocks = m.reshape(t_blk, -1)
    t = int(np.flatnonzero((blocks != 0).any(axis=1))[0])
    blk = m[t * c_blk:(t + 1) * c_blk]
    peak = np.abs(blk) == 127
    assert peak.any()
    blk[peak] = (np.sign(blk[peak]) * 126).astype(np.int8)
    assert _fired(leaves, meta) == ["GUST-P13"]


def test_p14_adder_collision(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    m, row = leaves["m_blk"], leaves["row_blk"]
    target = None
    for r in range(m.shape[0]):
        real = np.flatnonzero(m[r] != 0)
        if real.size >= 2:
            target = (r, real[0], real[1])
            break
    assert target is not None
    r, j1, j2 = target
    leaves["row_blk"][r, j2] = row[r, j1]
    assert _fired(leaves, meta) == ["GUST-P14"]


def test_p15_row_perm_not_a_permutation(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    perm = leaves["row_perm"]
    perm[0] = perm[1]  # duplicate entry: no longer a bijection
    assert _fired(leaves, meta) == ["GUST-P15"]


def test_p16_canonical_coo():
    good = COOMatrix((4, 4), np.array([0, 1, 2]), np.array([1, 0, 3]),
                     np.array([1.0, 2.0, 3.0], np.float32))
    assert verify(good) == []
    dup = COOMatrix((4, 4), np.array([0, 0, 2]), np.array([1, 1, 3]),
                    np.array([1.0, 2.0, 3.0], np.float32))
    assert sorted({f.rule for f in verify(dup)}) == ["GUST-P16"]
    zeros = COOMatrix((4, 4), np.array([0, 1]), np.array([1, 2]),
                      np.array([1.0, 0.0], np.float32))
    assert sorted({f.rule for f in verify(zeros)}) == ["GUST-P16"]


def test_p17_col_out_of_bounds(padded_f32):
    leaves, meta = _leaves_meta(padded_f32)
    m = leaves["m_blk"]
    seg_count = -(-meta[3][1] // L)
    r, j = next(zip(*np.nonzero(m)))
    leaves["col_blk"][r, j] += seg_count * L
    assert _fired(leaves, meta) == ["GUST-P17"]


@pytest.mark.parametrize("mutation", ["moved", "dropped", "shape"])
def test_p18_source_lanes_not_the_route_inverse(padded_f32, mutation):
    """The table the kernels route by: a source moved to another lane, a
    source dropped, or a table of the wrong shape fires GUST-P18 alone;
    leaves written without the table verify clean."""
    leaves, meta = _leaves_meta(padded_f32)
    src = leaves["src_blk"]
    r, a = next(zip(*np.nonzero(src >= 0)))
    if mutation == "moved":
        src[r, a] = (src[r, a] + 1) % L
    elif mutation == "dropped":
        src[r, a] = -1
    else:
        leaves["src_blk"] = src[:-1]
    assert _fired(leaves, meta) == ["GUST-P18"]
    del leaves["src_blk"]
    assert _fired(leaves, meta) == []


def test_mutations_on_ragged_layout(ragged_f32):
    """The element rules run identically on the ragged stream (which has
    no all-padding blocks — only padding slots inside real blocks)."""
    leaves, meta = _leaves_meta(ragged_f32)
    m = leaves["m_blk"]
    pads = np.argwhere(m == 0)
    assert pads.size, "ragged stream has no padding slot"
    r, j = pads[0]
    leaves["row_blk"][r, j] = 2
    assert _fired(leaves, meta) == ["GUST-P03"]


# ---------------------------------------------------------------------------
# wiring: GustPlan.verify, PlanStore verify-on-load, CLI
# ---------------------------------------------------------------------------


def test_plan_verify_method(padded_f32):
    findings = padded_f32.verify()
    assert findings == []


def test_store_verify_on_load(tmp_path):
    store = PlanStore(tmp_path / "store")
    p = plan(_coo(), l=L, layout="padded", cache=None, store=store)
    p.artifact  # materialize -> write-behind
    assert store.writes == 1
    key = store.keys()[0]

    # clean artifact: verify-on-load is a normal hit
    checking = PlanStore(tmp_path / "store", verify="load")
    assert checking.get(key) is not None
    assert checking.corrupt == 0

    # corrupt one leaf in place and re-put under the same key
    record = store.get(key)
    spec = record["spec"]
    bad = {k: np.array(v) for k, v in spec["leaves"].items()}
    bad["row_blk"][_all_padding_block_row(bad, 8), 0] = 3
    store.put(key, {"leaves": bad, "meta": spec["meta"],
                    "config": spec["config"]})

    # verify=off serves the corrupt bits; verify=load counts a corrupt
    # miss and never raises
    assert PlanStore(tmp_path / "store").get(key) is not None
    before = (checking.corrupt, checking.misses)
    assert checking.get(key) is None
    assert (checking.corrupt, checking.misses) == (before[0] + 1,
                                                   before[1] + 1)

    # plan() through the verifying store falls back to a fresh pack
    p2 = plan(_coo(), l=L, layout="padded", cache=None, store=checking)
    assert p2.verify() == []


def test_store_verify_arg_validated(tmp_path):
    with pytest.raises(ValueError):
        PlanStore(tmp_path / "s", verify="always")


def test_serve_config_store_verify_field():
    from repro.serving.gust_serve import GustServeConfig

    cfg = GustServeConfig(plan_store="/tmp/x", store_verify="load")
    assert cfg.store_verify == "load"


def test_cli_verify_store(tmp_path):
    store = PlanStore(tmp_path / "store")
    plan(_coo(), l=L, cache=None, store=store).artifact
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "verify",
         str(tmp_path / "store")],
        capture_output=True, text=True, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "1 artifact(s), 0 failing" in out.stdout


# ---------------------------------------------------------------------------
# policy linter
# ---------------------------------------------------------------------------


def test_lint_src_clean():
    from repro.analysis.lint import lint_sources

    assert lint_sources() == []


def _lint_tmp(tree, tmp_path):
    from repro.analysis.lint import lint_sources

    for rel, src in tree.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    return lint_sources(str(tmp_path), allowlist="/dev/null")


def test_lint_rules_fire(tmp_path):
    findings = _lint_tmp({
        "repro/__init__.py": "import jax\n",
        "repro/core/x.py": (
            "import numpy as np\n"
            "def shiny_new_api():\n"
            "    np.savez('a.npz')\n"
            "    spmv(None, None)\n"
            "    resolve_layout(None, 8, None)\n"
            "_cache = {}\n"
            "def _lookup(backend):\n"
            "    return _cache.get((1, backend))\n"
        ),
    }, tmp_path)
    rules = sorted({f.rule for f in findings})
    assert rules == ["GUST-L01", "GUST-L02", "GUST-L03", "GUST-L04",
                     "GUST-L05", "GUST-L06"]


def test_lint_l07_bare_except_pass_on_serving_path(tmp_path):
    swallow = (
        "def _risky():\n"
        "    try:\n"
        "        pass\n"
        "    except Exception:\n"
        "        pass\n"
    )
    findings = _lint_tmp({"repro/serving/loop.py": swallow}, tmp_path)
    assert [f.rule for f in findings] == ["GUST-L07"]
    assert findings[0].qualname == "_risky"
    # the same swallow off the serving path is not L07's business
    assert _lint_tmp({"repro/graph/x.py": swallow}, tmp_path / "b") == []
    # a handler that *does* something (count, retire, degrade) is fine
    handled = (
        "def _contained():\n"
        "    try:\n"
        "        pass\n"
        "    except Exception as err:\n"
        "        record(err)\n"
    )
    assert _lint_tmp({"repro/serving/ok.py": handled}, tmp_path / "c") == []
    # narrow except-pass is equally fine: L07 targets broad swallows only
    narrow = (
        "def _narrow():\n"
        "    try:\n"
        "        pass\n"
        "    except KeyError:\n"
        "        pass\n"
    )
    assert _lint_tmp({"repro/serving/nrw.py": narrow}, tmp_path / "d") == []


def test_lint_type_checking_import_allowed(tmp_path):
    findings = _lint_tmp({
        "repro/__init__.py": (
            "from typing import TYPE_CHECKING\n"
            "if TYPE_CHECKING:\n"
            "    import jax\n"
        ),
    }, tmp_path)
    assert findings == []


def test_lint_allowlist_silences_exact_site(tmp_path):
    (tmp_path / "allow.txt").write_text(
        "GUST-L02  repro/core/x.py::shiny  # test entry\n")
    from repro.analysis.lint import lint_sources

    (tmp_path / "repro" / "core").mkdir(parents=True)
    (tmp_path / "repro" / "core" / "x.py").write_text(
        "def shiny():\n    pass\n\n\ndef other():\n    pass\n")
    findings = lint_sources(str(tmp_path),
                            allowlist=str(tmp_path / "allow.txt"))
    assert [f.qualname for f in findings] == ["other"]


# ---------------------------------------------------------------------------
# kernel audit
# ---------------------------------------------------------------------------


def test_audit_clean_tree():
    from repro.analysis.kernel_audit import audit_kernels

    result = audit_kernels()
    assert result.ok, [str(f) for f in result.findings]
    builders = {r.builder.split("::")[1] for r in result.reports}
    assert {"make_gust_spmv", "make_gust_spmv_local", "make_gust_spmv_db",
            "make_gust_spmv_local_db", "make_gust_spmv_ragged",
            "make_gust_spmv_ragged_db", "make_gust_spgemm",
            "make_gather_fill"} <= builders
    # every manual-DMA body: the padded and ragged resident pipelines and
    # the segment-local tile pipeline both layouts share
    assert {"gust_spmv.py::_db_kernel", "gust_spmv.py::_local_db_block",
            "gust_spmv_ragged.py::_db_kernel"} <= set(result.db_kernels_checked)
    assert result.subscripts_checked > 0
    assert all(r.vmem_bytes > 0 for r in result.reports)


def test_audit_over_budget_config():
    from repro.analysis.kernel_audit import (DEFAULT_CONFIGS, audit_kernels)

    huge = dict(DEFAULT_CONFIGS[0], name="huge", seg_count=65536, l=256,
                b=8, c_pad=64, num_windows=16)
    result = audit_kernels(configs=(huge,))
    assert any(f.rule == "GUST-K01" for f in result.findings)


def _patched_kernels(tmp_path, old, new):
    kdir = tmp_path / "kernels"
    shutil.copytree(os.path.join(SRC, "repro", "kernels"), kdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = kdir / "gust_spmv.py"
    src = path.read_text()
    assert old in src
    path.write_text(src.replace(old, new))
    return str(kdir)


def test_audit_catches_missing_wait(tmp_path):
    from repro.analysis.kernel_audit import audit_kernels

    kdir = _patched_kernels(tmp_path, "c.wait()", "pass")
    result = audit_kernels(kernels_dir=kdir)
    assert any(f.rule == "GUST-K02" and "_db_kernel" in f.builder
               for f in result.findings)


def test_audit_catches_same_slot_prefetch(tmp_path):
    from repro.analysis.kernel_audit import audit_kernels

    kdir = _patched_kernels(tmp_path, "copies(1 - slot, i + 1)",
                            "copies(slot, i + 1)")
    result = audit_kernels(kernels_dir=kdir)
    assert any(f.rule == "GUST-K02" for f in result.findings)


def test_audit_catches_index_overrun(tmp_path):
    from repro.analysis.kernel_audit import audit_kernels

    kdir = _patched_kernels(
        tmp_path,
        "seg[(w * num_cb + cb) * s_blk + s]",
        "seg[(w * num_cb + cb) * s_blk + s + 1]")
    result = audit_kernels(kernels_dir=kdir)
    assert any(f.rule == "GUST-K03" for f in result.findings)


def test_cli_lint_and_audit():
    env = dict(os.environ, PYTHONPATH=SRC)
    for cmd in ("lint", "audit"):
        out = subprocess.run(
            [sys.executable, "-m", "repro.analysis", cmd],
            capture_output=True, text=True, env=env, cwd=REPO,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 finding(s)" in out.stdout
