"""A model family the harness has never seen is added in files alone: a
configuration names ``dense_qknorm`` (``dense_qknorm.py`` beside this
file, copied into the test's own ``models/``), and a whole run on the
CPU finds its leaves, weights, equations and program configuration by
that name."""
import json
import shutil
from pathlib import Path

import jax
import pytest

import harness
import run
import tiny

HERE = Path(__file__).resolve().parent
CELL = "tiny-qknorm.ssd"


def _registry(root: Path, model: str) -> harness.Registry:
    cfg = dict(tiny.CONFIG, name="tiny-qknorm", model=model)
    bench = tiny.make(root, configs=(("tiny-qknorm", cfg),),
                      cells=((CELL, "tiny-qknorm", "ssd"),))
    shutil.copy(HERE / "dense_qknorm.py", root / "models")
    return harness.Registry(root, bench)


@pytest.fixture
def reg(tmp_path):
    return _registry(tmp_path, "dense_qknorm")


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices()[0])


def _run(reg, capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2**34 + 11),
                   "--seconds", "0.5"], reg)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_family_names_its_leaves(reg):
    fam = reg.model_of(reg.config("tiny-qknorm"))
    a = fam.Arch.from_config(tiny.CONFIG)
    names = [n for n, _ in fam.layer_leaves(a, 0)]
    assert names[:2] == ["attn/k_norm", "attn/q_norm"]
    assert names == sorted(names)


def test_a_new_family_runs_correct(reg, on_cpu, capsys):
    res = _run(reg, capsys)
    assert res["correct"], res["checks"]


def test_a_reference_without_the_qk_norm_is_not_correct(reg, on_cpu, capsys,
                                                        monkeypatch):
    fam = reg.model("dense_qknorm")
    monkeypatch.setattr(fam, "block", fam.dense.block)
    res = _run(reg, capsys)
    assert not res["correct"], res["checks"]


def test_a_missing_family_is_an_error(tmp_path, capsys):
    reg = _registry(tmp_path, "no_such_family")
    with pytest.raises(LookupError):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"], reg)
    assert capsys.readouterr().out == ""
