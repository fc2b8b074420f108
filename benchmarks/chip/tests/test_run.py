"""A whole run of the harness on the CPU at a tiny size, with the look for
a chip skipped in the test: sound, with each fault a training cell can
have planted in the timed path, and without a chip."""
import json

import jax
import pytest

import harness
import run
import tiny


@pytest.fixture
def reg(tmp_path):
    return harness.Registry(tmp_path, tiny.make(tmp_path))


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices()[0])


def _run(reg, capsys, seed=2**33 + 5):
    rc = run.main(["--workload", "tiny.ssd", "--seed", str(seed),
                   "--seconds", "0.5"], reg)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    return res


def test_sound_run_is_correct(reg, on_cpu, capsys):
    res = _run(reg, capsys)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_state_left_unchanged_is_not_correct(reg, on_cpu, capsys,
                                             monkeypatch):
    from repro.optim.cpu_adam import CpuAdam
    from repro.offload import engine
    monkeypatch.setattr(CpuAdam, "update", lambda self, *a, **k: None)
    monkeypatch.setattr(engine, "_adam_device",
                        lambda p, m, v, g, step, lr: (p, m, v))
    res = _run(reg, capsys)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(reg, on_cpu, capsys,
                                                monkeypatch):
    from repro.offload import OffloadEngine
    step = OffloadEngine.train_step

    def half(self, tokens):
        t = tokens.copy()
        h = t.shape[0] // 2
        t[h:] = t[:h]          # the mean is taken over the first half
        return step(self, t)
    monkeypatch.setattr(OffloadEngine, "train_step", half)
    res = _run(reg, capsys)
    assert not res["correct"]


def test_without_a_chip_no_result(reg, capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tiny.ssd", "--seed", "1",
                  "--seconds", "1"], reg)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_no_result(reg, capsys, monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(run, "require_chip", lambda chips: Dev())
    with pytest.raises(LookupError):
        run.main(["--workload", "tiny.ssd", "--seed", "1",
                  "--seconds", "1"], reg)
    assert capsys.readouterr().out == ""
