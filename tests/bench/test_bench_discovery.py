"""A configuration, a cell and a per-layer metric are added by adding
files only: the harness finds each by its name in the benchmark spec."""
import json
import shutil

import pytest

import benchtiny
from bench import harness

READER = '''
def read(run):
    return float(run.rounds)
'''


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """The tiny root with a new configuration file, a new traffic file, a
    new cell and a new metric reader, and nothing else changed."""
    root, spec = benchtiny.make_root(tmp_path_factory.mktemp("bench_grow"))
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny-lm.json").read_text())
    cfg["config"]["num_hidden_layers"] = 2
    (b / "configs" / "tiny-lm-deep.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "tiny_lm.json").read_text())
    tr["dp"] = False
    (b / "traffic" / "tiny_lm_nodp.json").write_text(json.dumps(tr))
    shutil.copy(b / "limits" / "tiny.lm.json", b / "limits" / "tiny.deep.json")
    (b / "metrics" / "rounds_traced.py").write_text(READER)
    spec["configs"].append({"name": "tiny-lm-deep",
                            "file": "bench/configs/tiny-lm-deep.json"})
    spec["workloads"].append({"name": "tiny.deep", "config": "tiny-lm-deep",
                              "traffic": "tiny_lm_nodp", "chips": 1})
    spec["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                              "moves": "round_s", "workloads": ["tiny.deep"]})
    return root, spec


def test_new_cell_is_found_by_name(grown):
    root, spec = grown
    cell = harness.load_cell(spec, root, "tiny.deep")
    assert cell.config["config"]["num_hidden_layers"] == 2
    assert cell.traffic["dp"] is False
    assert "rounds_traced" in [m["name"] for m in cell.per_layer]
    assert "device_idle_frac" in [m["name"] for m in cell.per_layer]
    # a metric that lists its cells is read in those alone
    assert "rounds_traced" not in [
        m["name"] for m in harness.load_cell(spec, root, "tiny.lm").per_layer]
    assert harness.load_reader(root / "bench", "rounds_traced")(
        type("R", (), {"rounds": 3})()) == 3.0


def test_new_cell_runs_correct_with_its_metric(grown):
    out = benchtiny.run(*grown, "tiny.deep", trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["rounds_traced"]["value"] >= 1
    assert list(out)[-1] == "checks"
