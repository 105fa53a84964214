"""BENCHMARK.json names resolve to files under bench/, by name alone."""
import json
import re

import pytest

from bench import run

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in SPEC[k]]
    assert len(set(metrics)) == len(metrics)
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_configs_files_and_kinds_exist():
    for c in SPEC["configs"]:
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        assert (run.BENCH / "kinds" / f"{cfg['kind']}.py").is_file()
        assert (run.BENCH / "reference" / f"{cfg['kind']}.py").is_file()
        assert c["file"].startswith("bench/configs/")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = run.load_cell(name)
    assert cell["traffic"]["kind"] == cell["config"]["kind"]
    assert {m["name"] for m in cell["end_to_end"]} >= {"call_ms", "setup_s"}
    moved = {m["moves"] for m in cell["per_layer"]}
    assert moved <= {m["name"] for m in cell["end_to_end"]}
    assert cell["per_layer"]
    kind = run.kind_module(cell["config"]["kind"])
    assert hasattr(kind, "Workload")


def test_every_per_layer_metric_has_a_reader_and_known_move():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    layers = {}
    for m in SPEC["per_layer"]:
        reader = run.load_module(run.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) <= {"device", "loop call", "kernel",
                           "XLA prologue and epilogue", "construction",
                           "op / pack"}


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no-such-cell")
