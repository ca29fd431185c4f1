"""The harness finds configurations, traffic mixes and metrics by name."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import bench.metrics  # noqa: E402
from bench import catalog, system  # noqa: E402
from bench import reference as ref  # noqa: E402


def test_peaks_refuse_an_unknown_device_kind():
    assert catalog.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(catalog.UnknownDevice, match="no peaks for device"):
        catalog.peaks_for("TPU v99")


def test_every_cell_of_the_benchmark_loads_and_reports_its_metrics():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = catalog.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        names = [m["name"] for m in cell.end_to_end + cell.per_layer]
        assert "setup_s" in names
        for name in names:
            assert callable(catalog.metric_reader(name))


def test_a_cell_added_as_files_only_is_found(tmp_path, monkeypatch):
    os.makedirs(tmp_path / "bench" / "configs")
    os.makedirs(tmp_path / "bench" / "traffic")
    shutil.copy(os.path.join(ROOT, "bench", "configs", "lenet300.json"),
                tmp_path / "bench" / "configs" / "tiny.json")
    (tmp_path / "bench" / "traffic" / "b2-closed.json").write_text(
        json.dumps(dict(loop="closed", clients=2, buckets=[2], pool=4,
                        check_sample=4,
                        input=dict(low=0, high=1, field=None, density=1))))
    metrics_dir = tmp_path / "more_metrics"
    metrics_dir.mkdir()
    (metrics_dir / "answered.py").write_text(
        "def read(run):\n    return float(len(run.window.done))\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(
        configs=[dict(name="tiny", file="bench/configs/tiny.json")],
        workloads=[dict(name="tiny-b2", config="tiny", traffic="b2-closed",
                        chips=1)],
        end_to_end=[dict(name="setup_s", unit="s")],
        per_layer=[dict(name="answered.closed", unit="1",
                        workloads=["tiny-b2"]),
                   dict(name="answered.other", unit="1",
                        workloads=["another"])])))
    monkeypatch.setattr(bench.metrics, "__path__",
                        [str(metrics_dir)] + list(bench.metrics.__path__))
    cell = catalog.load_cell("tiny-b2", root=str(tmp_path))
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["answered.closed"]

    class _Window:
        done = [1, 2, 3]

    class _Run:
        window = _Window()

    assert catalog.metric_reader("answered.closed")(_Run()) == 3.0
    with pytest.raises(KeyError, match="no workload"):
        catalog.load_cell("missing", root=str(tmp_path))


@pytest.mark.parametrize("config,program", [("alexnet224", "ALEXNET"),
                                            ("lenet300", "LENET_300_100")])
def test_configs_build_the_programs_published_networks(config, program):
    sys.path.insert(0, system.SRC)
    from repro.models import cnn, mlp
    want = getattr(cnn, program, None) or getattr(mlp, program)
    cfg = catalog.load_json(os.path.join(ROOT, "bench", "configs",
                                     config + ".json"))
    got = system.build_spec(cfg)
    fields = ("layers", "num_classes") + (
        ("input_size", "in_ch") if hasattr(want, "input_size")
        else ("in_features",))
    for f in fields:
        assert getattr(got, f) == getattr(want, f), f
    shapes = ref.layer_inputs(cfg)
    assert shapes[-1] == (cfg["num_classes"],)
