"""The correctness check must fail broken runs (CPU, smoke widths).

- a token altered where the engine produces it (the greedy accept rule
  commits a wrong token): the served stream is no longer the model's, and
  ``correct`` comes out false;
- the control, the plain reference at int4 activations put in the
  program's place, comes out not correct through the same comparison
  (``check.verdict``) on the same sample where the program's own tokens
  come out correct.
"""
from __future__ import annotations

import time

import pytest

from chipbench import check, harness, smoke
from chipbench.peaks import PEAKS

# the smoke cells' limit: at these widths (2 layers, 512-token vocabulary)
# the program's tokens read 0 and the control reads 0.2 or more
LIMIT = 0.05


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(tmp_path_factory.mktemp("bench"), gap_limit=LIMIT)


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: PEAKS["TPU v5 lite"])


def test_token_altered_where_produced_fails_the_check(root, monkeypatch):
    import repro.serving.engine as eng

    accept = eng.speculative_accept_greedy_host

    def wrong(drafts, p_logits, dl):
        new, n_acc = accept(drafts, p_logits, dl)
        new = list(new)
        new[-1] = int(p_logits[len(new) - 1].argmin())  # the model's least likely token
        return new, n_acc

    monkeypatch.setattr(eng, "speculative_accept_greedy_host", wrong)
    greedy = [c for c in smoke.cells() if c.endswith("-greedy")][0]
    res = harness.run(greedy, 9, 2.0, False, time.perf_counter(), root=root,
                      compile_cache=False)
    assert res["correct"] is False
    assert res["checks"]["served_gap"]["value"] > LIMIT


@pytest.mark.parametrize("cell", smoke.cells())
def test_control_reads_above_the_limit_where_the_program_reads_within(root, cell):
    keep: dict = {}
    res = harness.run(cell, 11, 2.0, False, time.perf_counter(), root=root,
                      compile_cache=False, keep=keep)
    assert res["correct"] is True
    gaps = [check.gaps(keep["reference"], keep["params"], keep["config"],
                       r.req.prompt, r.tokens, control=True) for r in keep["sample"]]
    ck = keep["traffic"]["check"]
    ok_program, checks_program = check.verdict([g["program"] for g in gaps], 0, ck)
    ok_control, checks_control = check.verdict([g["control"] for g in gaps], 0, ck)
    assert ok_program is True and ok_control is False
    program = checks_program["served_gap"]["value"]
    control = checks_control["served_gap"]["value"]
    assert program <= LIMIT < control, (program, control)
    assert control >= 3 * max(program, 0.05), (program, control)
