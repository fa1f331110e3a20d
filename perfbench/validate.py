"""Is BENCHMARK.json consistent with the files under ``perfbench/``?

``problems(bench, root)`` returns what is wrong, as sentences; the tests
hold it at none.  It checks what a later PR can break by adding files:
names and units, that every cell's configuration, traffic mix and
per-layer metric files exist and name code that exists, that a data
file agrees with its entry, and that every cell of a per-layer metric
reports the end-to-end metric it moves.
"""
from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH = re.compile(r"(hidden_size|intermediate|_dim$|_rank$|head_dim|"
                   r"num_experts_per_tok|expansion|latent|state_size|proj)")


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and \
        "\n" not in s and "\t" not in s


def problems(bench: dict, root: str) -> list:
    out = []
    pb = os.path.join(root, "perfbench")

    def need(path, what):
        if not os.path.isfile(path):
            out.append(f"{what}: no file {os.path.relpath(path, root)}")
            return None
        with open(path, encoding="utf-8") as f:
            return json.load(f)

    def code(kind, name, what):
        if not os.path.isfile(os.path.join(pb, kind, f"{name}.py")):
            out.append(f"{what}: no perfbench/{kind}/{name}.py")

    cfgs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        for n in names:
            if not NAME.match(n):
                out.append(f"{group}: bad name {n!r}")
        if len(set(names)) != len(names):
            out.append(f"{group}: a name appears twice")
    both = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(set(both)) != len(both):
        out.append("a metric name is both end-to-end and per-layer")

    for c in bench["configs"]:
        data = need(os.path.join(root, c["file"]), f"config {c['name']}")
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            out.append(f"config {c['name']}: file outside paths")
        if data is None:
            continue
        code("builders", data.get("builder"), f"config {c['name']}")
        if sorted(data.get("reduced", [])) != sorted(c["reduced"]):
            out.append(f"config {c['name']}: 'reduced' differs between "
                       f"BENCHMARK.json and its file")
        for k in c["reduced"]:
            if not NAME.match(k) or WIDTH.search(k):
                out.append(f"config {c['name']}: 'reduced' names {k!r}")
        if "rehearse" not in data:
            out.append(f"config {c['name']}: no 'rehearse' sizes")
        if not _line(c["why"]) or not _line(c["source"]):
            out.append(f"config {c['name']}: why/source not one line of "
                       f"1-200 characters")
        if not any(w["config"] == c["name"] for w in bench["workloads"]):
            out.append(f"config {c['name']}: used by no cell")

    pairs = set()
    for w in bench["workloads"]:
        if w["config"] not in cfgs:
            out.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            out.append(f"cell {w['name']}: bad traffic name")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"cell {w['name']}: config and traffic used before")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"cell {w['name']}: chips {w['chips']}")
        if not _line(w["why"]):
            out.append(f"cell {w['name']}: why not one line of 1-200")
        mix = need(os.path.join(pb, "traffic", w["traffic"] + ".json"),
                   f"cell {w['name']}")
        if mix is not None:
            code("drivers", mix.get("driver"), f"traffic {w['traffic']}")

    def cells_of(m):
        return m.get("workloads", list(cells))

    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            out.append(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"metric {m['name']}: source {m['source']!r}")
        for c in cells_of(m):
            if c not in cells:
                out.append(f"metric {m['name']}: unknown cell {c!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end {m['name']}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.1:
            out.append(f"end-to-end {m['name']}: bound {m['bound']}")
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        out.append("setup_s must be reported by every cell")

    for m in bench["per_layer"]:
        spec = need(os.path.join(pb, "layer_metrics", m["name"] + ".json"),
                    f"per-layer {m['name']}")
        if spec is not None:
            code("readers", spec.get("reader"), f"per-layer {m['name']}")
            for k in ("name", "unit", "better", "source", "layer", "moves",
                      "workloads"):
                if spec.get(k) != m.get(k):
                    out.append(f"per-layer {m['name']}: {k!r} differs "
                               f"between BENCHMARK.json and its file")
        if not _line(m["layer"]):
            out.append(f"per-layer {m['name']}: layer not one line")
        if m["moves"] not in e2e:
            out.append(f"per-layer {m['name']}: moves unknown metric "
                       f"{m['moves']!r}")
            continue
        for c in cells_of(m):
            if c not in cells_of(e2e[m["moves"]]):
                out.append(f"per-layer {m['name']}: cell {c} does not "
                           f"report {m['moves']}")

    for w in bench["workloads"]:
        n_e2e = [m for m in bench["end_to_end"]
                 if w["name"] in cells_of(m) and m["name"] != "setup_s"]
        n_lay = [m for m in bench["per_layer"] if w["name"] in cells_of(m)]
        if not n_e2e or not n_lay:
            out.append(f"cell {w['name']}: needs an end-to-end metric "
                       f"besides setup_s and a per-layer metric")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(len(bench["workloads"]) // 4, 1):
        out.append("more than a quarter of the cells ask for 4 chips")
    return out
