"""The manifest checked against the contract, before every run and by
hand (``python benchmark/run.py --check-manifest``). No chip, no JAX.

It reads ``BENCHMARK.json`` and the files it names, and returns every
fault it finds as one line. PR 23 was refused for a per-layer metric
that counted as reported in a cell that lacked the end-to-end metric it
moves: that rule is here, with the others a run can check for itself.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
PROGRAM_IMPORT = re.compile(
    r"^\s*(from|import)\s+elasticsearch_tpu\b", re.MULTILINE)
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def _line(text, what, faults, limit=200):
    if not (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text):
        faults.append(f"{what}: not one line of 1 to {limit} characters")


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def imports_program(path: str) -> bool:
    """Whether a file of the benchmark imports the program under test:
    a reference may not."""
    with open(path, encoding="utf-8") as f:
        return bool(PROGRAM_IMPORT.search(f.read()))


def reporting_cells(metric: dict, cells: list) -> list:
    return metric.get("workloads", [c["name"] for c in cells])


def check(root: str, manifest: dict = None) -> list:
    """Every fault of the manifest at ``root``, as lines; [] is sound."""
    faults = []
    if manifest is None:
        manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    if set(manifest) != TOP_KEYS:
        faults.append(f"top-level keys {sorted(manifest)} are not "
                      f"{sorted(TOP_KEYS)}")
        return faults
    for section, (need, may) in KEYS.items():
        for entry in manifest[section]:
            extra = set(entry) - need - may
            if need - set(entry) or extra:
                faults.append(
                    f"{section} {entry.get('name')!r}: keys missing "
                    f"{sorted(need - set(entry))}, not allowed {sorted(extra)}")
    if faults:
        return faults
    paths, command = manifest["paths"], manifest["command"]
    if not 1 <= len(paths) <= 16 or not all(PATH.match(p) for p in paths):
        faults.append("paths: 1 to 16 relative directories")
    if not 1 <= len(command) <= 32:
        faults.append("command: 1 to 32 strings")
    for word in command:
        _line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command word {word!r} leaves the checkout")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")

    def under_paths(file):
        return any(file.startswith(p.rstrip("/") + "/") for p in paths)

    cells, configs = manifest["workloads"], manifest["configs"]
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    for section, n_max in (("configs", 24), ("workloads", 24),
                           ("end_to_end", 16), ("per_layer", 128)):
        entries = manifest[section]
        if not 1 <= len(entries) <= n_max:
            faults.append(f"{section}: 1 to {n_max} entries")
        names = [e["name"] for e in entries]
        for n in names:
            if not NAME.match(str(n)):
                faults.append(f"{section} name {n!r} is not a name")
        if len(set(names)) != len(names):
            faults.append(f"{section}: a name appears twice")
    metric_names = [m["name"] for m in e2e + layer]
    if len(set(metric_names)) != len(metric_names):
        faults.append("two metrics share a name")

    cell_names = {c["name"] for c in cells}
    config_names = {c["name"] for c in configs}
    files = [c["file"] for c in configs]
    if len(set(files)) != len(files):
        faults.append("two configurations share a file")
    for c in configs:
        _line(c["source"], f"config {c['name']} source", faults)
        _line(c["why"], f"config {c['name']} why", faults)
        if not under_paths(c["file"]):
            faults.append(f"config {c['name']}: file outside paths")
        if not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: no file {c['file']}")
        else:
            held = load_json(os.path.join(root, c["file"]))
            for key in c["reduced"]:
                if key not in held:
                    faults.append(f"config {c['name']}: reduced key {key!r} "
                                  f"is not in {c['file']}")
            gen = os.path.join(root, paths[0], "generators",
                               f"{held.get('generator')}.py")
            if not os.path.isfile(gen):
                faults.append(f"config {c['name']}: no generator {gen}")
            ref = os.path.join(root, paths[0], "references",
                               f"{held.get('reference')}.py")
            if not os.path.isfile(ref):
                faults.append(f"config {c['name']}: no reference {ref}")
            elif imports_program(ref):
                faults.append(f"config {c['name']}: its reference {ref} "
                              f"imports the program")
        if len(c["reduced"]) > 16 or not all(
                NAME.match(k) for k in c["reduced"]):
            faults.append(f"config {c['name']}: reduced is at most 16 names")
        if not any(w["config"] == c["name"] for w in cells):
            faults.append(f"config {c['name']} has no cell")
    pairs = set()
    for w in cells:
        _line(w["why"], f"cell {w['name']} why", faults)
        if w["config"] not in config_names:
            faults.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(str(w["traffic"])):
            faults.append(f"cell {w['name']}: traffic is not a name")
        mix = os.path.join(root, paths[0], "traffic", f"{w['traffic']}.json")
        if not os.path.isfile(mix):
            faults.append(f"cell {w['name']}: no traffic file {mix}")
        if w["chips"] not in (1, 4):
            faults.append(f"cell {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"cell {w['name']}: its config and traffic "
                          f"appear twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        faults.append(f"{four} of {len(cells)} cells ask for 4 chips")

    by_name = {m["name"]: m for m in e2e}
    if "setup_s" not in by_name:
        faults.append("no end-to-end metric setup_s")
    elif "workloads" in by_name["setup_s"]:
        faults.append("setup_s is reported by every cell: no workloads key")
    for m in e2e + layer:
        if not UNIT.match(str(m["unit"])):
            faults.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            faults.append(f"metric {m['name']}: better is lower or higher")
        if m["source"] not in SOURCES:
            faults.append(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cell_names:
                faults.append(f"metric {m['name']}: unknown cell {w}")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m['name']}: source")
        if not (isinstance(m["bound"], (int, float))
                and 0.01 <= m["bound"] <= 0.25):
            faults.append(f"end-to-end metric {m['name']}: bound outside "
                          f"0.01 to 0.25")
        if not reporting_cells(m, cells):
            faults.append(f"end-to-end metric {m['name']}: no cell reports it")
    for m in layer:
        _line(m["layer"], f"metric {m['name']} layer", faults)
        if "workloads" not in m:
            faults.append(f"per-layer metric {m['name']} has no workloads "
                          f"list: it would count as reported in every cell, "
                          f"those of later PRs too")
        if m["moves"] not in by_name:
            faults.append(f"per-layer metric {m['name']} moves "
                          f"{m['moves']!r}, which is no end-to-end metric")
            continue
        moved = set(reporting_cells(by_name[m["moves"]], cells))
        for w in reporting_cells(m, cells):
            if w not in moved:
                faults.append(
                    f"per_layer metric {m['name']} is reported on workload "
                    f"{w}, where {m['moves']}, which it should move, is not")
        if ("roofline" in m["name"] or "mfu" in m["name"]) and m["unit"] != "%":
            faults.append(f"metric {m['name']}: a share's unit is %")
        # its own file and its reader
        own = os.path.join(root, paths[0], "layer_metrics",
                           f"{m['name']}.json")
        if not os.path.isfile(own):
            faults.append(f"per-layer metric {m['name']}: no file {own}")
            continue
        held = load_json(own)
        for key in ("name", "unit", "layer", "moves", "workloads"):
            if held.get(key) != m.get(key):
                faults.append(f"per-layer metric {m['name']}: {key} differs "
                              f"between BENCHMARK.json and {own}")
        reader = os.path.join(root, paths[0], "readers",
                              f"{held.get('reader')}.py")
        if not os.path.isfile(reader):
            faults.append(f"per-layer metric {m['name']}: no reader {reader}")
    for w in cells:
        mine = [m["name"] for m in e2e
                if w["name"] in reporting_cells(m, cells)]
        if len(mine) < 2 or "setup_s" not in mine:
            faults.append(f"cell {w['name']} reports setup_s and one more "
                          f"end-to-end metric at least: it reports {mine}")
        if not any(w["name"] in reporting_cells(m, cells) for m in layer):
            faults.append(f"cell {w['name']} reports no per-layer metric")
    size = os.path.getsize(os.path.join(root, "BENCHMARK.json")) \
        if os.path.isfile(os.path.join(root, "BENCHMARK.json")) else 0
    if size > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    return faults
