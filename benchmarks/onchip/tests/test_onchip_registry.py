"""The harness finds a configuration, a traffic mix, a cell and a metric
by name; adding one is adding a file."""
import json
import os
import shutil

import pytest

from harness import registry as R

BENCH = {
    "workloads": [{"name": "a.cell", "config": "c1", "traffic": "t1",
                   "chips": 1}],
    "end_to_end": [
        {"name": "output_tokens_per_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"},
        {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["other"]}],
    "per_layer": [
        {"name": "round_ms_p50", "moves": "output_tokens_per_s"},
        {"name": "queue_wait_p90_ms", "moves": "ttft_p90_ms"},
        {"name": "mfu", "moves": "output_tokens_per_s",
         "workloads": ["other"]}],
}


def test_the_committed_benchmark_resolves():
    bench = R.load_benchmark()
    for w in bench["workloads"]:
        cell = R.load_cell(R.ROOT, w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        R.load_module(R.ROOT, "references",
                      cell["config_spec"]["reference"])
        for trace in (False, True):
            for m in R.cell_metrics(bench, w["name"], trace):
                assert hasattr(R.load_module(R.ROOT, "metrics", m["name"]),
                               "read")
    names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        spec = json.load(open(os.path.join(R.CHECKOUT, c["file"])))
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
    assert names == {w["config"] for w in bench["workloads"]}


def test_new_files_are_found_without_editing_any(tmp_path):
    root = tmp_path / "bench"
    for kind in ("configs", "traffic", "workloads", "metrics"):
        (root / kind).mkdir(parents=True)
    (root / "configs" / "c1.json").write_text(json.dumps({"name": "c1"}))
    (root / "traffic" / "t1.json").write_text(json.dumps({"arrival": "x"}))
    (root / "workloads" / "a.cell.json").write_text(json.dumps(
        {"config": "c1", "traffic": "t1", "engine": {}}))
    (root / "metrics" / "new_metric.v2.py").write_text(
        "def read(ctx):\n    return 42\n")
    cell = R.load_cell(str(root), "a.cell")
    assert cell["config_spec"] == {"name": "c1"}
    assert cell["traffic_spec"] == {"arrival": "x"}
    assert R.load_module(str(root), "metrics", "new_metric.v2").read(None) \
        == 42
    with pytest.raises(FileNotFoundError):
        R.load_cell(str(root), "missing")
    with pytest.raises(ValueError):
        R.load_json(str(root), "configs", "../c1")


def test_metric_selection_by_cell_and_trace():
    assert [m["name"] for m in R.cell_metrics(BENCH, "a.cell", False)] == \
        ["output_tokens_per_s", "setup_s"]
    # no workloads key: every cell reporting the metric it moves
    assert [m["name"] for m in R.cell_metrics(BENCH, "a.cell", True)] == \
        ["round_ms_p50"]
    assert [m["name"] for m in R.cell_metrics(BENCH, "other", True)] == \
        ["round_ms_p50", "queue_wait_p90_ms", "mfu"]


def test_copied_metric_dir_is_complete(tmp_path):
    """Every metric named in BENCHMARK.json has its reader file."""
    bench = R.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(R.ROOT, "metrics",
                                           m["name"] + ".py")), m["name"]
    shutil.copytree(os.path.join(R.ROOT, "metrics"), tmp_path / "m")
