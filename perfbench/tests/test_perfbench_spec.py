"""Cells, configurations, traffic, limits and metric readers are found by
name, and BENCHMARK.json keeps to the shape the harness reads."""
import json
import pathlib
import re
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
from benchlib import spec  # noqa: E402
from benchlib.model import from_doc, layout  # noqa: E402
from benchlib.peaks import UnknownDevice, peaks_for  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_cell_resolves(bench):
    for wl in bench["workloads"]:
        cell = spec.cell(bench, wl["name"], ROOT)
        assert cell.chips in (1, 4)
        assert cell.traffic["kind"] == "train"
        m = from_doc(cell.config)
        assert m.layers == cell.config["num_hidden_layers"]
        assert {"train_tokens_per_s", "setup_s"} <= {
            x["name"] for x in cell.end_to_end}
        assert cell.per_layer, wl["name"]


def test_config_files_list_their_cuts(bench):
    for c in bench["configs"]:
        doc = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("perfbench/configs/")
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert set(doc["published"]) == set(c["reduced"])
        for k in c["reduced"]:
            assert doc[k] != doc["published"][k], k
            assert not re.search(r"(size|_dim|_rank|heads|expand)", k), k


def test_names_units_and_readers(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in metrics + bench["workloads"]
             + bench["configs"]]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {x["name"] for x in bench["end_to_end"]}
    assert "setup_s" in e2e
    for x in metrics:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", cells)) <= cells
    for x in bench["per_layer"]:
        assert x["moves"] in e2e
        assert callable(spec.metric_reader(x["name"], ROOT))
    assert all(0.01 <= x["bound"] <= 0.25 for x in bench["end_to_end"])


def test_unknown_names_are_errors(bench):
    with pytest.raises(spec.SpecError):
        spec.cell(bench, "no-such-cell", ROOT)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric", ROOT)


def test_layout_names_every_weight():
    doc = json.loads((BENCH / "configs" / "qwen1.5-32b-l4.json").read_text())
    names = layout(from_doc(doc))
    assert "lm_head/w" in names and "layers/attn/bk" in names
    doc = json.loads((BENCH / "configs" / "granite-3-8b-l4.json")
                     .read_text())
    names = layout(from_doc(doc))
    assert "lm_head/w" not in names and "layers/attn/bq" not in names


def test_peaks_table():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v4")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
