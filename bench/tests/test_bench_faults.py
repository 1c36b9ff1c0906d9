"""Whole runs of tiny cells on the CPU, without the look for a chip: a
sound run is correct, and each fault the timed path can have, planted
underneath, makes ``correct`` come out false, and so does each cell's
control put in the program's place."""

import pytest

import benchtools


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtools.scratch_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.power"])
def test_sound_run_is_correct(root, cell):
    line = benchtools.run_cell(root, cell, seed=2**33 + 1, seconds=0.5)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    assert "setup_s" in line["metrics"]


def _decode_keeps_its_cache(monkeypatch):
    import repro.serving.serve_loop as sl

    orig = sl.decode_step_gust

    def stale(lm, params, gust, caches, tokens, pos, **kw):
        logits, _ = orig(lm, params, gust, caches, tokens, pos, **kw)
        return logits, caches

    monkeypatch.setattr(sl, "decode_step_gust", stale)


def _decode_token_altered(monkeypatch):
    import numpy as np

    from repro.serving.serve_loop import ServeLoop

    orig = ServeLoop._sample_rows
    calls = []

    def altered(self, logits_rows, rid_step):
        out = np.array(orig(self, logits_rows, rid_step))
        calls.append(1)
        if len(calls) == 6:  # one token of one request, mid-stream
            out[0] = (out[0] + 1) % self.lm.cfg.vocab
        return out

    monkeypatch.setattr(ServeLoop, "_sample_rows", altered)


def _spmv_returns_its_input(monkeypatch):
    from repro.core.plan import GustPlan

    monkeypatch.setattr(GustPlan, "spmv", lambda self, v: v * 1.0)


def _spmv_answer_altered(monkeypatch):
    from repro.core.plan import GustPlan

    orig = GustPlan.spmv

    def altered(self, v):
        y = orig(self, v)
        return y.at[0].add(1e-2 * (1.0 + abs(y[0])))

    monkeypatch.setattr(GustPlan, "spmv", altered)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.chat", _decode_keeps_its_cache),
    ("tiny.chat", _decode_token_altered),
    ("tiny.power", _spmv_returns_its_input),
    ("tiny.power", _spmv_answer_altered),
], ids=["decode-state-unchanged", "decode-token-altered",
        "spmv-state-unchanged", "spmv-answer-altered"])
def test_fault_makes_the_run_incorrect(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = benchtools.run_cell(root, cell, seed=2**35 + 9, seconds=0.5)
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.power"])
def test_control_makes_the_run_incorrect(root, cell):
    """The configuration's lower precision in the program's place: the
    bfloat16 reference's first tokens scored as served, or the plan's
    values in bfloat16."""
    line = benchtools.run_cell(root, cell, seed=2**36 + 5, seconds=2.0,
                               control=1)
    assert not line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
