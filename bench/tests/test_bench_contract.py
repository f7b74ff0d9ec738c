"""BENCHMARK.json is well formed, and every name in it resolves to a file of
the benchmark: configs, traffic mixes, drivers, metric readers, limits."""

from __future__ import annotations

import os
import re
import statistics

import pytest

from bench import harness, loadgen

B = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head|expan|per_tok)")


def _path(rel):
    return os.path.join(harness.ROOT, rel)


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"] == ["python3", "bench/run.py"]
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    # a full check of 24 cells must fit its time budget
    assert (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(open(_path("BENCHMARK.json"), "rb").read()) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_keys_and_names(section):
    names = [e["name"] for e in B[section]]
    assert len(names) == len(set(names))
    for e in B[section]:
        assert set(e) <= ENTRY_KEYS[section], e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


def test_metric_names_unique_across_sections():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))


def test_end_to_end_bounds_and_sources():
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    path = _path(cfg["file"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json" and os.path.isfile(path)
    data = harness.load_json(path)
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    assert os.path.isfile(_path(f"bench/reference/{data['reference']}.py"))
    assert not any(WIDTH.search(k) for k in cfg["reduced"])
    assert len(cfg["reduced"]) <= 16
    assert any(w["config"] == cfg["name"] for w in B["workloads"])


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda c: c["name"])
def test_cell_files_and_metrics(cell):
    traffic = harness.load_json(_path(f"bench/traffic/{cell['traffic']}.json"))
    assert os.path.isfile(_path(f"bench/drivers/{traffic['driver']}.py"))
    assert os.path.isfile(_path(f"bench/limits/{cell['name']}.json"))
    assert cell["chips"] in (1, 4)
    e2e = {m["name"] for m in harness.metrics_of(B, cell["name"], per_layer=False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of(B, cell["name"], per_layer=True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert os.path.isfile(_path(f"bench/metrics/{m['name']}.py"))


def test_per_layer_metrics_name_their_cells_and_layers():
    cells = {w["name"] for w in B["workloads"]}
    layers: dict[str, set] = {}
    for m in B["per_layer"]:
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    assert all(len(v) == 1 for v in layers.values())   # one spelling per layer


def test_four_chip_cells_within_share():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_length_sets_are_the_declared_traffic():
    gen = harness.load_json(_path("bench/traffic/gen-closed.json"))
    assert loadgen.length_set(gen["prompt_lens"]) == [16, 22, 29, 39, 53, 71, 95, 128]
    outs = loadgen.length_set(gen["output_lens"])
    assert min(outs) >= 256 and max(outs) <= 2048
    # log-uniform quantiles: the geometric mean sits at the range's
    assert abs(statistics.geometric_mean(outs) - (256 * 2048) ** 0.5) < 5
