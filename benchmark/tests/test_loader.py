import json
import os

import pytest

from benchmark import harness, loader

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.json")


def test_the_committed_benchmark_loads_and_every_cell_finds_its_files():
    bench = loader.load_benchmark()
    assert bench["paths"] == ["benchmark"]
    for cell in bench["workloads"]:
        loaded = loader.load_cell(cell["name"])
        assert loaded["traffic"]["driver"] in ("train", "serve")
        assert {m["name"] for m in loaded["end_to_end"]} >= {"setup_s"}
        assert len(loaded["end_to_end"]) >= 2 and loaded["per_layer"]
        for m in loaded["per_layer"]:
            assert callable(loader.load_reader(m["reader"]))
            assert m["moves"] in {e["name"] for e in loaded["end_to_end"]}


def test_a_cell_is_added_by_files_and_one_entry():
    """The tiny cells live in files of their own and a benchmark file of their
    own; nothing of the harness names them."""
    loaded = loader.load_cell("tiny-sincos.tiny-train", TINY)
    assert loaded["config"]["model"]["dim"] == 64
    assert loaded["traffic"]["batch_size"] == 4


def test_unknown_workload_is_refused():
    with pytest.raises(loader.BenchmarkError, match="unknown workload"):
        loader.load_cell("no-such.cell")


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "a/b"), ("name", "a,b"), ("name", ".dot"),
    ("name", "x" * 65), ("name", "μs"), ("unit", "tokens per s"),
    ("unit", "μs"), ("unit", ""), ("better", "more"), ("source", "guess")])
def test_bad_names_and_units_are_refused(tmp_path, field, value):
    bench = json.load(open(TINY))
    bench["end_to_end"][0][field] = value
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(loader.BenchmarkError):
        loader.load_benchmark(str(path))


def test_unknown_chip_is_refused():
    with pytest.raises(loader.BenchmarkError, match="not in"):
        loader.load_peaks("TPU v99")
    assert loader.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_the_last_line_has_the_contracts_keys_and_checks_last():
    line = harness.result_line(True, 4, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
                               {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                                "memory_peak_bytes": 1}, {"loss1_gap": {"value": 0, "limit": 1}})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    traced = json.loads(harness.result_line(True, 4, 0, {}, {}, {}, {"device_ops": [], "idle_gaps": []}))
    assert list(traced) == ["correct", "attempted", "failed", "metrics", "device",
                            "breakdown", "checks"]


def test_main_refuses_a_machine_without_the_chip(capsys):
    """``main`` alone looks for the chip, and no switch turns that off."""
    from benchmark import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "xglm-564m.train", "--seed", "1", "--seconds", "1"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""
