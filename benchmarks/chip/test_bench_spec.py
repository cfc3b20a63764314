"""BENCHMARK.json keeps the benchmark's rules, and a new configuration,
traffic mix or per-layer metric is found from new files alone."""
import json
import os
import shutil

import pytest

from chip import spec

ROOT = spec.ROOT
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return spec.load(ROOT)


def test_top_level_and_paths(bench):
    assert set(bench) == KEYS
    assert bench["paths"] == ["benchmarks/chip"]
    cmd = bench["command"]
    assert len(cmd) <= 32 and all(isinstance(w, str) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        assert word.startswith("benchmarks/chip/")
        assert os.path.isfile(os.path.join(ROOT, word))


def test_run_seconds_fits_a_full_check(bench):
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/chip/")
        cfg = spec.load_config(bench, c["name"])
        assert cfg["arch"]["name"] == c["name"]
        assert spec.load_reference(cfg).hidden
        assert len(c["reduced"]) <= 16
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        spec.load_traffic(w["traffic"])


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
        for cell in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
        assert callable(spec.load_metric_reader(m["name"]))
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        reported = [m["name"] for m in spec.cell_metrics(bench, cell,
                                                         "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.cell_metrics(bench, cell, "per_layer")


@pytest.mark.parametrize("bad", ["", "a b", "a,b", "a/b", "µs", "-x",
                                 "x" * 65])
def test_names_outside_the_rules_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_name(bad, "test")


def test_new_entries_are_found_from_new_files(tmp_path):
    """A throwaway configuration, traffic mix and per-layer metric, added as
    files and entries with no edit to an existing file, are found."""
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip")
    here = tmp_path / "benchmarks" / "chip"
    b = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = json.loads((here / "configs" / "switch-base-128.json").read_text())
    cfg["arch"]["name"] = "throwaway"
    (here / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "b1-mixed.json").read_text())
    mix["output_len"] = [4, 8]
    (here / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (here / "metrics" / "throwaway_count.b1.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append({"name": "throwaway", "source": "test",
                         "file": "benchmarks/chip/configs/throwaway.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "throwaway.throwaway-mix",
                           "config": "throwaway",
                           "traffic": "throwaway-mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "throwaway_count.b1", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "tpot_ms",
                           "workloads": ["throwaway.throwaway-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    loaded = spec.load(str(tmp_path))
    w = spec.workload(loaded, "throwaway.throwaway-mix")
    assert spec.load_config(loaded, w["config"], str(tmp_path)) == cfg
    assert spec.load_traffic(w["traffic"], str(here))["output_len"] == [4, 8]
    names = [m["name"] for m in spec.cell_metrics(
        loaded, "throwaway.throwaway-mix", "per_layer")]
    assert names == ["throwaway_count.b1"]
    assert spec.load_metric_reader("throwaway_count.b1", str(here))(None) \
        == 42.0
