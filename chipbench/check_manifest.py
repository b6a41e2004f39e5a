#!/usr/bin/env python3
"""Check BENCHMARK.json against the rules that refuse a manifest before
any run: the characters and lengths of names, layers, units and one-line
texts, that every cell of a per-layer metric reports the end-to-end metric
it moves, and that every file a cell names exists (its configuration, its
traffic mix, the mix's entry point under entries/, each metric's reader).
Run before every chip call: ``python3 chipbench/check_manifest.py`` exits
non-zero with the faults listed.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def one_line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(r"[\n\r\t]", s)


def find(paths: list, kind: str, file: str):
    """The first <path>/<kind>/<file> that exists, or None."""
    for p in paths:
        path = os.path.join(ROOT, p, kind, file)
        if os.path.isfile(path):
            return path
    return None


def entry_of(mix: str) -> str:
    with open(mix) as f:
        return str(json.load(f).get("entry", ""))


def faults(m: dict) -> list:
    out = []
    bad = lambda what, v: out.append(f"{what}: {v!r}")
    if set(m) != {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}:
        bad("top-level keys", sorted(m))
    for section, keys in KEYS.items():
        for e in m[section]:
            extra = set(e) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            if extra or keys - set(e):
                bad(f"{section} {e.get('name')} keys", sorted(set(e) ^ keys))
            if not NAME.match(str(e.get("name", ""))):
                bad(f"{section} name", e.get("name"))
        names = [e["name"] for e in m[section]]
        if len(names) != len(set(names)):
            bad(f"{section} duplicate names", names)
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad("run_seconds", m["run_seconds"])
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p:
            bad("path", p)
    for word in m["command"]:
        if not one_line(word):
            bad("command word", word)
    under = lambda f: any(f.startswith(p.rstrip("/") + "/") for p in m["paths"])
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        if not (one_line(c["source"]) and one_line(c["why"])):
            bad(f"config {c['name']} source/why", (c["source"], c["why"]))
        if not (PATH.match(c["file"]) and under(c["file"])
                and os.path.isfile(os.path.join(ROOT, c["file"]))):
            bad(f"config {c['name']} file", c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k) for k in c["reduced"]):
            bad(f"config {c['name']} reduced", c["reduced"])
    cells = {w["name"]: w for w in m["workloads"]}
    for w in m["workloads"]:
        if w["config"] not in configs:
            bad(f"cell {w['name']} config", w["config"])
        mix = NAME.match(w["traffic"]) and find(
            m["paths"], "traffic", w["traffic"] + ".json")
        if not mix:
            bad(f"cell {w['name']} traffic file", w["traffic"])
        else:
            entry = entry_of(mix)
            if not NAME.match(entry) or entry.startswith("_") or not find(
                    m["paths"], "entries", entry + ".py"):
                bad(f"cell {w['name']} traffic {w['traffic']} entry file",
                    f"entries/{entry}.py")
        if w["chips"] not in (1, 4) or not one_line(w["why"]):
            bad(f"cell {w['name']} chips/why", (w["chips"], w["why"]))
    for name in set(configs) - {w["config"] for w in m["workloads"]}:
        bad("config without a cell", name)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    if "setup_s" not in e2e:
        bad("end_to_end", "no setup_s")
    reported = lambda metric, cell: cell in metric.get("workloads", cells)
    for section in ("end_to_end", "per_layer"):
        for e in m[section]:
            if not UNIT.match(e["unit"]) or e["better"] not in ("lower", "higher"):
                bad(f"{e['name']} unit/better", (e["unit"], e["better"]))
            if e["source"] not in SOURCES:
                bad(f"{e['name']} source", e["source"])
            for cell in e.get("workloads", []):
                if cell not in cells:
                    bad(f"{e['name']} workloads", cell)
    for e in m["end_to_end"]:
        if e["source"] not in ("host_clock", "device_trace"):
            bad(f"{e['name']} source (end to end)", e["source"])
        if not (isinstance(e["bound"], float) and 0.01 <= e["bound"] <= 0.25):
            bad(f"{e['name']} bound", e["bound"])
    for e in m["per_layer"]:
        if not NAME.match(e["layer"]):
            bad(f"{e['name']} layer", e["layer"])
        if e["moves"] not in e2e:
            bad(f"{e['name']} moves", e["moves"])
            continue
        for cell in e.get("workloads", cells):
            if not reported(e2e[e["moves"]], cell):
                bad(f"{e['name']} moves {e['moves']}, which is not reported in", cell)
        if not find(m["paths"], "layers", e["name"] + ".py"):
            bad(f"{e['name']} reader file", f"layers/{e['name']}.py")
    for cell in cells:
        if not any(reported(e, cell) for e in m["end_to_end"] if e["name"] != "setup_s"):
            bad("cell without an end-to-end metric", cell)
        if not any(reported(e, cell) for e in m["per_layer"]):
            bad("cell without a per-layer metric", cell)
    if sum(w["chips"] == 4 for w in m["workloads"]) > max(1, len(cells) // 2):
        bad("four-chip cells", "more than half")
    return out


def main() -> int:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        print("BENCHMARK.json is over 64 KiB")
        return 1
    with open(path) as f:
        found = faults(json.load(f))
    for line in found:
        print("FAULT", line)
    print(f"BENCHMARK.json: {len(found)} fault(s)")
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
