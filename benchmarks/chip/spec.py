"""``BENCHMARK.json``: loading, name rules, and finding each cell's files.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own, found by name:

    configs/<config>.json       sizes as run, and the reference that checks them
    references/<module>.py      plain float32 reference named by the config
    traffic/<traffic>.json      parameters for the one traffic generator
    metrics/<metric>.py         a reader with ``read(ctx) -> float | None``

so a new cell, mix or metric is new files and new entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names breaks the benchmark's rules."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 "
                        "_ . - and starts with a letter, digit or _")
    return name


def check_line(text: str, what: str) -> str:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        raise SpecError(f"{what}: 1-200 characters on one line, no tab")
    return text


def load(root: str = ROOT) -> dict:
    """Read and check ``<root>/BENCHMARK.json``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        check_name(c["name"], "config")
        for key in c["reduced"]:
            check_name(key, "reduced key")
        check_line(c["why"], f"config {c['name']} why")
    for w in spec["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["config"], "workload config")
        check_name(w["traffic"], "workload traffic")
        check_line(w["why"], f"workload {w['name']} why")
    for m in spec["end_to_end"] + spec["per_layer"]:
        check_name(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better {m['better']!r}")
    for m in spec["per_layer"]:
        check_line(m["layer"], f"metric {m['name']} layer")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        if len(names) != len(set(names)):
            raise SpecError(f"duplicate name in {group}")
    return spec


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(spec: dict, name: str, root: str = ROOT) -> dict:
    return _json(os.path.join(root, config_entry(spec, name)["file"]))


def load_traffic(name: str, here: str = HERE) -> dict:
    return _json(os.path.join(here, "traffic", check_name(name, "traffic")
                              + ".json"))


def _module(path: str, label: str):
    spec_ = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def load_reference(config: dict, here: str = HERE):
    """The plain reference module a configuration file names."""
    name = check_name(config["reference"], "reference")
    return _module(os.path.join(here, "references", name + ".py"),
                   f"chip_reference_{name}")


def load_metric_reader(name: str, here: str = HERE):
    """``read(ctx)`` of one per-layer metric, from ``metrics/<name>.py``."""
    path = os.path.join(here, "metrics", check_name(name, "metric") + ".py")
    return _module(path, "chip_metric_" + name.replace(".", "_")).read


def cell_metrics(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]
