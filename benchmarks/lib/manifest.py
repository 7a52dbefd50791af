"""Reads ``BENCHMARK.json`` and the data files it names.

One cell = one entry of ``workloads``: a configuration (``configs/<name>.json``
at the path the manifest gives), a traffic mix (``traffic/<name>.json``) and
the chips it needs. Per-layer metrics are entries of ``per_layer`` with a
reader spec beside them in ``metrics/<name>.json``. A later PR adds files and
manifest entries; nothing here names a cell, a configuration or a metric.
"""
from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field

NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
CONFIG_KEYS = {"name", "source", "source_lines", "deployment", "reference",
               "volume", "stem", "flags", "cohort", "assumed", "reduced"}
TRAFFIC_KEYS = {"name", "what", "flags", "cohort", "block_rounds"}
METRIC_KEYS = {"name", "what", "reader", "args"}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # manifest entries this cell reports
    per_layer: list = field(default_factory=list)   # (entry, reader spec)

    @property
    def cohort(self) -> dict:
        """The configuration's cohort with the traffic mix's overrides."""
        return {**self.config["cohort"], **self.traffic.get("cohort", {})}


def _load(path: str, keys: set, name: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    unknown = set(doc) - keys
    if unknown:
        raise ValueError(f"{path}: unknown key(s) {sorted(unknown)}")
    if doc.get("name") != name:
        raise ValueError(f"{path}: 'name' is {doc.get('name')!r}, the "
                         f"manifest calls it {name!r}")
    return doc


def load_manifest(path: str) -> dict:
    with open(path) as f:
        manifest = json.load(f)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            if not NAME.match(entry["name"]):
                raise ValueError(f"{path}: bad name {entry['name']!r}")
    return manifest


def reports(entry: dict, cell: str) -> bool:
    """Whether the metric ``entry`` is reported in ``cell``."""
    return cell in entry.get("workloads", [cell])


def load_cell(manifest_path: str, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics. The
    benchmark's directory is the manifest's first ``paths`` entry, beside
    the manifest."""
    manifest = load_manifest(manifest_path)
    base = os.path.dirname(os.path.abspath(manifest_path))
    root = os.path.join(base, manifest["paths"][0])
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise ValueError(f"no workload {name!r} in {manifest_path}; it has "
                         f"{sorted(by_name)}")
    w = by_name[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = _load(os.path.join(base, cfg["file"]), CONFIG_KEYS, cfg["name"])
    traffic = _load(os.path.join(root, "traffic", w["traffic"] + ".json"),
                    TRAFFIC_KEYS, w["traffic"])
    clash = set(config["flags"]) & set(traffic["flags"])
    if clash:
        raise ValueError(f"traffic {w['traffic']!r} sets flag(s) "
                         f"{sorted(clash)} that belong to the configuration")
    cell = Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[e for e in manifest["end_to_end"]
                            if reports(e, name)])
    for entry in manifest["per_layer"]:
        if reports(entry, name):
            spec = _load(os.path.join(root, "metrics", entry["name"] + ".json"),
                         METRIC_KEYS, entry["name"])
            cell.per_layer.append((entry, spec))
    return cell
