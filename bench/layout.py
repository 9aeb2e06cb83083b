"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here names a cell, a configuration or a metric: a configuration
is ``bench/configs/<config>.json``, a traffic mix ``bench/traffic/<traffic>.json``
and a per-layer metric's reader ``bench/metrics/<quantity>.py``, where the
quantity is the metric's name up to its first dot (``cache_hit_share.get``
and ``cache_hit_share.gather`` share ``cache_hit_share.py``). A later cell,
mix or metric is a new file and a new entry, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

#: the checkout root: the directory that holds ``bench/`` and ``src/``
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class LayoutError(Exception):
    """A name in ``BENCHMARK.json`` has no file, or a file is malformed."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise LayoutError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """Everything one run of ``workload`` reads: the cell, its configuration
    and traffic files, and the metrics it reports under each ``--trace``."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise LayoutError(f"unknown workload {workload!r}; known: "
                          f"{sorted(cells)}")
    cell = cells[workload]
    config = _load_json(os.path.join(root, "bench", "configs",
                                     cell["config"] + ".json"))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json"))

    def reports(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    quantity = name.split(".", 1)[0]
    path = os.path.join(root, "bench", "metrics", quantity + ".py")
    if not os.path.exists(path):
        raise LayoutError(f"metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{quantity}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


#: keys each entry of BENCHMARK.json may have (a metric may add workloads)
_KEYS = {
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                  "workloads"},
}


def validate(root: str = ROOT) -> list[str]:
    """Problems with ``BENCHMARK.json`` and the files its names point to;
    empty when every name resolves and every field is well formed."""
    bench = load_benchmark(root)
    bad: list[str] = []
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for kind, entries in (("config", bench["configs"]),
                          ("workload", bench["workloads"]),
                          ("end_to_end", bench["end_to_end"]),
                          ("per_layer", bench["per_layer"])):
        for e in entries:
            extra = set(e) - _KEYS[kind]
            if extra:
                bad.append(f"{kind} {e.get('name')}: unknown keys {extra}")
            if not NAME_RE.match(e.get("name", "")):
                bad.append(f"{kind} name {e.get('name')!r} is not allowed")
            if "unit" in e and not UNIT_RE.match(e["unit"]):
                bad.append(f"{e['name']}: unit {e['unit']!r} is not allowed")
            if kind in ("end_to_end", "per_layer") and e.get(
                    "better") not in ("lower", "higher"):
                bad.append(f"{e['name']}: better must be lower or higher")
            for w in e.get("workloads", []) if kind != "workload" else []:
                if w not in cells:
                    bad.append(f"{e['name']}: unknown workload {w!r}")
    for c in bench["configs"]:
        for key in c["reduced"]:
            if not NAME_RE.match(key):
                bad.append(f"config {c['name']}: reduced key {key!r}")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
    for name, w in cells.items():
        if w["config"] not in configs:
            bad.append(f"workload {name}: unknown config {w['config']!r}")
        try:
            resolve(name, root)
        except LayoutError as exc:
            bad.append(f"workload {name}: {exc}")
    for m in bench["end_to_end"]:
        if not 0 < m["bound"] <= 0.25:
            bad.append(f"{m['name']}: bound {m['bound']} not in (0, 0.25]")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
            continue
        for w in m.get("workloads", list(cells)):
            if w not in e2e[m["moves"]].get("workloads", [w]):
                bad.append(f"{m['name']}: {w} does not report {m['moves']}")
        try:
            metric_reader(m["name"], root)
        except LayoutError as exc:
            bad.append(str(exc))
    if "setup_s" not in e2e:
        bad.append("no setup_s metric")
    return bad
