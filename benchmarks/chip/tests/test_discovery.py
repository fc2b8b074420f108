"""Everything of a cell is found by name: adding a configuration, a model
family, a traffic mix, a cell or a metric is adding files and entries,
with no edit to the harness."""
import json

import pytest

import harness
import tiny


def test_the_committed_benchmark_resolves():
    reg = harness.Registry()
    for w in reg.bench["workloads"]:
        cell = reg.cell(w["name"])
        cfg = reg.config(cell["config"])
        assert cfg["name"] == cell["config"]
        model = reg.model_of(cfg)
        a = model.Arch.from_config(cfg)
        assert model.flops_per_token(cfg, cell["seq_len"]) > 0
        for l in range(a.layers):
            assert model.layer_leaves(a, l)
            hash(model.layer_kind(a, l))
        assert set(cell["limits"]) >= {"loss_gap", "grad_gap",
                                       "change_gap", "bytes_mismatch"}
        for trace in (False, True):
            for m in reg.metrics(w["name"], trace):
                assert callable(reg.reader(m["name"]))
    for c in reg.bench["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    assert reg.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_added_files_are_found_by_name(tmp_path):
    cfg2 = dict(tiny.CONFIG, name="tiny2", hidden_size=96)
    bench = tiny.make(tmp_path, configs=(("tiny", tiny.CONFIG),
                                         ("tiny2", cfg2)),
                      cells=(("tiny.ssd", "tiny", "ssd"),
                             ("tiny2.ssd", "tiny2", "ssd")))
    (tmp_path / "metrics" / "steps_in_window.py").write_text(
        "def read(rec):\n    return rec['window']['steps']\n")
    b = json.loads(bench.read_text())
    b["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "whole step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["tiny2.ssd"]})
    bench.write_text(json.dumps(b))
    reg = harness.Registry(tmp_path, bench)
    cell = reg.cell("tiny2.ssd")
    assert reg.config(cell["config"])["hidden_size"] == 96
    assert cell["seq_len"] == tiny.TRAFFIC["seq_len"]
    names = [m["name"] for m in reg.metrics("tiny2.ssd", True)]
    assert "steps_in_window" in names
    assert "steps_in_window" not in [
        m["name"] for m in reg.metrics("tiny.ssd", True)]
    rec = {"window": {"steps": 3, "seconds": 2.0}}
    assert reg.reader("steps_in_window")(rec) == 3


def test_missing_names_are_errors(tmp_path):
    reg = harness.Registry(tmp_path, tiny.make(tmp_path))
    with pytest.raises(LookupError):
        reg.cell("no-such-cell")
    with pytest.raises(LookupError):
        reg.config("no-such-config")
    with pytest.raises(LookupError):
        reg.reader("no_such_metric")
    with pytest.raises(LookupError):
        reg.model("no_such_family")
    assert reg.model_of({}) is reg.model("dense")
    with pytest.raises(LookupError):
        reg.peaks("TPU v9 imaginary")


def test_readers_return_nothing_when_there_is_nothing_to_read():
    reg = harness.Registry()
    rec = {"trace": None, "traffic": {"param:cpu->gpu": 5},
           "traffic_per_step": {"param:cpu->gpu": 5},
           "proc_io": {"read_bytes": 0}, "window": {"steps": 1},
           "device": {}}
    for name in ("device_idle_pct", "io.ssd_GB", "io.ssd_dev_read_pct",
                 "hbm_peak_GiB"):
        assert reg.reader(name)(rec) is None
