"""``BENCHMARK.json`` and every file it names hold to the benchmark's
contract, and a cell, a configuration or a metric is added by files
alone."""

import json
import math
import re

import pytest

from bench import core

M = core.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
WIDTH = ("hidden_size", "intermediate_size", "head_dim",
         "moe_intermediate_size", "kv_lora_rank", "q_lora_rank",
         "num_experts_per_tok")
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {w["name"]: w for w in M["workloads"]}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (core.ROOT / p).is_dir()
    cmd = M["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in M["paths"])
            assert (core.ROOT / w).is_file()
    assert len(json.dumps(M)) <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    r = M["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    assert 1 <= len(M["configs"]) <= 24
    used = {w["config"] for w in M["workloads"]}
    files = set()
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = core.load_json(core.ROOT / c["file"])
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert k not in WIDTH and not k.endswith(("_dim", "_rank"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_load_and_agree(cell):
    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and line(w["why"])
    got = core.load_cell(cell)
    body = got["workload"]
    assert (body["config"], body["traffic"], body["chips"]) == \
        (w["config"], w["traffic"], w["chips"])
    assert (core.BENCH / "kinds" / f"{body['kind']}.py").is_file()
    assert (core.BENCH / "traffic"
            / f"{got['traffic']['generator']}.py").is_file()
    assert got["config"]["name"] == w["config"]
    assert body["limits"] and all(v > 0 for v in body["limits"].values())
    reported = core.metrics_for(M, cell, per_layer=False)
    names = {m["name"] for m in reported}
    assert "setup_s" in names and len(names) >= 2
    layer = core.metrics_for(M, cell, per_layer=True)
    assert layer
    for m in layer:
        assert m["moves"] in names, (m["name"], cell)


def test_cells_unique_and_four_chips_within_half():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(CELLS) == len(M["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, math.floor(0.5 * len(M["workloads"])))


def test_metrics():
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in E2E and line(m["layer"])
        layers.setdefault(m["layer"].lower(), m["layer"])
        assert layers[m["layer"].lower()] == m["layer"]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert (core.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_cell_config_and_metric_are_added_by_files_alone(tmp_path):
    """An extra cell in another tree loads through the same code, and a
    manifest entry with its reader is picked up by name."""
    b = tmp_path / "bench"
    for sub in ("workloads", "configs", "traffic", "metrics"):
        (b / sub).mkdir(parents=True)
    cfg = core.load_json(core.BENCH / "configs" / "phi4-mini-3.8b.json")
    cfg["name"] = "extra-model"
    (b / "configs" / "extra-model.json").write_text(json.dumps(cfg))
    (b / "traffic" / "extra-mix.json").write_text(json.dumps(
        {"generator": "open_loop", "rate_per_s": 1.0}))
    (b / "workloads" / "extra.cell.json").write_text(json.dumps(
        {"config": "extra-model", "kind": "serve", "chips": 1,
         "traffic": "extra-mix", "serve": {"slots": 2},
         "limits": {"logit_gap": 1.0}}))
    (b / "metrics" / "extra_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    cell = core.load_cell("extra.cell", root=b)
    assert cell["config"]["name"] == "extra-model"
    assert cell["traffic"]["rate_per_s"] == 1.0
    manifest = {"end_to_end": [], "per_layer": [
        {"name": "extra_metric.x", "workloads": ["extra.cell"]},
        {"name": "other", "workloads": ["elsewhere"]}]}
    picked = core.metrics_for(manifest, "extra.cell", per_layer=True)
    assert [m["name"] for m in picked] == ["extra_metric.x"]
    assert core.load_module("metrics", "extra_metric.x", root=b).read(
        None) == 42.0
    with pytest.raises(SystemExit):
        core.load_cell("missing.cell", root=b)
