"""Every entry of BENCHMARK.json resolves to its files, and the file keeps to
the benchmark's contract."""

import json
import re
import shutil

import pytest

from bench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("work", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(work):
    cell = cells.load_cell(work["name"])
    assert cell.chips in (1, 4)
    for field in cell.config["fields"]:
        params = cells.field_params(cell.mix, field)
        assert ("eb" in params) if cell.mix["codec"] == "tpu-sz" else ("rate" in params)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_reported_metric(metric):
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    for w in metric.get("workloads", []):
        assert w in {x["name"] for x in BENCH["workloads"]}


def test_contract_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, (section, e["name"])
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.loads((cells.ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_missing_mix_is_an_error(tmp_path):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "nyx.gone", "config": "nyx", "traffic": "gone",
                               "chips": 1, "why": "a mix file that does not exist"})
    (tmp_path / "bench").mkdir()
    shutil.copytree(cells.BENCH / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(cells.BENCH / "mixes", tmp_path / "bench" / "mixes")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert cells.load_cell("nyx256.sz_tight", tmp_path).name == "nyx256.sz_tight"
    with pytest.raises(FileNotFoundError):
        cells.load_cell("nyx.gone", tmp_path)
    with pytest.raises(KeyError):
        cells.load_cell("nyx.nothing", tmp_path)
    with pytest.raises(FileNotFoundError):
        cells.reader("no_such_metric")


def test_peaks_known_and_unknown():
    assert cells.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peak("cpu")
