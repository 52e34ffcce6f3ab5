"""Checks ``BENCHMARK.json`` and the files it names against the benchmark's
rules: names and units of the allowed characters, every cell, configuration,
traffic driver and layer metric found by name, and every per-layer metric
reported in cells that report the end-to-end metric it moves.

    python3 bench/spec_check.py       # prints the problems; exit 1 if any
"""
from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def _text(v) -> bool:
    return isinstance(v, str) and 1 <= len(v) <= 200 \
        and "\n" not in v and "\t" not in v


def problems(spec: dict, root: str) -> list[str]:
    out = []
    bench = os.path.join(root, "bench")
    if set(spec) != KEYS["top"]:
        out.append(f"top-level keys {sorted(spec)}")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e.get("name") for e in spec[group]]
        if len(set(names)) != len(names):
            out.append(f"{group}: a name repeats")
        for e in spec[group]:
            extra = set(e) - KEYS[group]
            if extra:
                out.append(f"{group} {e.get('name')}: keys {sorted(extra)}")
            if not NAME.match(str(e.get("name"))):
                out.append(f"{group}: bad name {e.get('name')!r}")
            if "unit" in e and not UNIT.match(e["unit"]):
                out.append(f"{e['name']}: bad unit {e['unit']!r}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                out.append(f"{e['name']}: better={e['better']!r}")
            for key in ("why", "layer", "source"):
                if key in e and not _text(e[key]):
                    out.append(f"{e['name']}: {key} is not one line of "
                               "1-200 characters")
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config {c['name']}: no file {c['file']}")
        for k in c["reduced"]:
            if not NAME.match(k):
                out.append(f"config {c['name']}: bad reduced key {k!r}")
    cells = {w["name"]: w for w in spec["workloads"]}
    for w in spec["workloads"]:
        if w["config"] not in configs:
            out.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]):
            out.append(f"cell {w['name']}: bad traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            out.append(f"cell {w['name']}: chips={w['chips']}")
        path = os.path.join(bench, "workloads", w["name"] + ".json")
        if not os.path.isfile(path):
            out.append(f"cell {w['name']}: no file {path}")
            continue
        with open(path) as f:
            cell = json.load(f)
        if cell["config"] != w["config"] or cell["traffic"] != w["traffic"]:
            out.append(f"cell {w['name']}: file and BENCHMARK.json differ")
        if not os.path.isfile(os.path.join(bench, "traffic",
                                           cell["driver"] + ".py")):
            out.append(f"cell {w['name']}: no driver {cell['driver']}")
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    if len(set(pairs)) != len(pairs):
        out.append("a (config, traffic) pair repeats")

    e2e = {m["name"]: m for m in spec["end_to_end"]}
    reports = {c: {n for n, m in e2e.items()
                   if c in m.get("workloads", cells)} for c in cells}
    for m in spec["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"{m['name']}: bound {m['bound']} outside [0.01, 0.25]")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    layered = {c: set() for c in cells}
    for m in spec["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown {m['moves']}")
            continue
        if m["source"] not in ("device_trace", "program_span",
                               "program_counter", "host_clock"):
            out.append(f"{m['name']}: source {m['source']}")
        for c in m.get("workloads", [c for c in cells
                                     if m["moves"] in reports[c]]):
            if c not in cells:
                out.append(f"{m['name']}: unknown cell {c}")
            elif m["moves"] not in reports[c]:
                out.append(f"{m['name']}: cell {c} does not report "
                           f"{m['moves']}")
            else:
                layered[c].add(m["name"])
        if not os.path.isfile(os.path.join(bench, "layer_metrics",
                                           m["name"] + ".py")):
            out.append(f"{m['name']}: no reader layer_metrics/"
                       f"{m['name']}.py")
    for c in cells:
        if "setup_s" not in reports[c] or len(reports[c]) < 2:
            out.append(f"cell {c}: needs setup_s and another end-to-end "
                       "metric")
        if not layered[c]:
            out.append(f"cell {c}: no per-layer metric")
    return out


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        found = problems(json.load(f), root)
    for p in found:
        print(p)
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
