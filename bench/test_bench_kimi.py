"""The ``kimi_k2`` cell at its smoke size on the CPU: the sound run comes out
correct; the plain reference one precision below the configuration's (the
control) and each planted fault come out not correct against the cell's
limits."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common as C  # noqa: E402
import control as K  # noqa: E402
import fed  # noqa: E402
import run as R  # noqa: E402
import test_bench_faults_fed as F  # noqa: E402

C.use_program()

CELL = "fedara.kimi_k2.dir0.1"


def _limits():
    bench = C.benchmark()
    w = C.find_cell(bench, CELL)
    return C.traffic_file(w["traffic"])["limits"][w["config"]]


def test_kimi_sound_run_is_correct_and_control_fails(monkeypatch):
    K.READ.clear()
    monkeypatch.setattr(fed, "_checks", K._fed_checks)
    res = R.execute(R.smoke_ctx(CELL, 2**31 + 29, 1.0, False))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    lim, got = _limits(), K.READ[-1]
    assert any(got["control"][n] > lim[n] for n in lim), got["control"]
    assert any(got["half_batch"][n] > lim[n] for n in lim), got["half_batch"]


@pytest.mark.parametrize("fault,number", [
    (F._state_unchanged, "update_gap"), (F._half_batch, "loss_gap"),
    (F._masks_unchanged, "mask_gap")],
    ids=["state_unchanged", "half_batch", "masks_unchanged"])
def test_kimi_fault_is_caught(fault, number, monkeypatch):
    fault(monkeypatch)
    res = R.execute(R.smoke_ctx(CELL, 2**31 + 17, 1.0, False))
    assert res["correct"] is False, res["checks"]
    c = res["checks"][number]
    assert c["value"] is None or c["value"] > c["limit"], res["checks"]


def test_kimi_reference_imports_nothing_of_the_program():
    import ast
    tree = ast.parse((Path(__file__).resolve().parent / "configs"
                      / "kimi_k2.py").read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            assert not any(a.name.startswith("repro") for a in node.names)
        if isinstance(node, ast.ImportFrom):
            assert not (node.module or "").startswith("repro")
