"""BENCHMARK.json against the benchmark's contract, and the files each of
its entries names."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {w["name"]: w for w in M["workloads"]}
CONFIGS = {c["name"]: c for c in M["configs"]}
WIDTH = re.compile(r"(_size$|_dim$|_rank$|^head|intermediate|latent|"
                   r"expan|experts_per_tok)")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][:2] == ["python3", "bench/run.py"]
    assert all(_line(w) for w in M["command"])
    assert M["paths"] == ["bench"]
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in M["configs"]] + list(CELLS) + \
        [m["name"] for m in M["end_to_end"] + M["per_layer"]] + \
        [w["traffic"] for w in M["workloads"]] + \
        [k for c in M["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)


def test_entries_hold_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and _line(w["why"])
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_exist(cell):
    w = CELLS[cell]
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert (BENCH / "cells" / f"{cell}.json").is_file()
    conf_file = ROOT / CONFIGS[w["config"]]["file"]
    assert conf_file.is_file() and conf_file.parts[-3] == "bench"
    settings = json.loads((BENCH / "cells" / f"{cell}.json").read_text())
    assert settings["check"]["limit"]["logit_gap_mean"] > 0
    pairs = [(x["config"], x["traffic"]) for x in M["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in M["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in M["per_layer"] if cell in m.get("workloads", [cell])]
    assert layer


def test_per_layer_cells_report_what_they_move():
    for m in M["per_layer"]:
        moved = E2E[m["moves"]]
        for cell in m.get("workloads", list(CELLS)):
            assert cell in CELLS
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_layers_are_named_alike():
    layers = {m["layer"] for m in M["per_layer"]}
    assert layers <= {"device", "model step", "kernels", "scheduler"}


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    from bench.observe import load_module
    assert callable(load_module("metrics", metric).read)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_files(name):
    c = CONFIGS[name]
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["name"] == name and conf["reduced"] == c["reduced"]
    for k in c["reduced"]:
        assert k in conf and not WIDTH.search(k), k
    assert conf["hidden_size"] == conf["num_attention_heads"] * \
        conf["head_dim"]
    assert conf["reference"] and (BENCH / "reference" /
                                  f"{conf['reference']}.py").is_file()


def test_no_benchmark_file_lists_cells_configs_or_metrics():
    """A cell, a mix, a configuration or a per-layer metric is added by
    files and BENCHMARK.json entries only: no code names one."""
    names = set(CELLS) | set(CONFIGS) | \
        {w["traffic"] for w in M["workloads"]} | \
        {m["name"] for m in M["per_layer"]}
    for py in BENCH.rglob("*.py"):
        if py.name.startswith("test_") or py.name == "tiny.py":
            continue
        text = py.read_text()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (py, n)
