"""Cells of the benchmark: ``BENCHMARK.json`` plus the data files it names.

A cell is one entry of ``workloads``: a configuration (``configs/<name>
.json``: the simulated machine's Spec fields, its guarantees and energy
model) under a traffic mix (``traffic/<name>.json``: ``single`` runs of
one Spec, or a ``study`` over a list of points, with the seed mapping).
Everything here is data: a new cell is a new entry and new files.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: the scalars a point may carry besides its names and shapes
SCALARS = ("seed", "n_addrs", "lat", "work", "modify", "backoff",
           "backoff_exp", "net_bw", "hol_block", "zipf_skew")
#: fields only some points read (Zipf skew: zipf_histogram; clusters:
#: cluster2; queue groups: colibri_hier), with a placeholder for the rest
UNREAD = {"zipf_skew": 0, "clusters": 4, "n_groups": 4}


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell ``name`` with its configuration and traffic loaded:
    ``{"name", "chips", "config": {...}, "traffic": {...}, "metrics":
    {"end_to_end": [...], "per_layer": [...]}}`` where the metric lists
    hold the entries of BENCHMARK.json this cell reports."""
    b = benchmark(root)
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {', '.join(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in b["configs"]}[w["config"]]
    return dict(
        name=name, chips=w["chips"],
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".json")),
        metrics={kind: [m for m in b[kind]
                        if name in m.get("workloads", [name])]
                 for kind in ("end_to_end", "per_layer")})


def seeds(traffic: Dict[str, Any], seed: int) -> List[int]:
    """The Spec seeds a run draws from ``--seed``: ``count`` consecutive
    values from ``count * (seed mod modulus)``, so every seed fits the
    engine's int32 seed axis."""
    s = traffic["seeds"]
    base = (seed % s["modulus"]) * s["count"]
    return [base + i for i in range(s["count"])]


def point_fields(cell: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """Flat Spec fields of every point of one pass, in order: the
    configuration's fields, the traffic's, each combination of the
    traffic's axes (first axis outermost), each seed."""
    t = cell["traffic"]
    base = {**cell["config"]["spec"], **t["spec"]}
    combos = [{}]
    if t["mode"] == "study":
        axes = list(t["axes"].values())
        combos = [dict(itertools.chain.from_iterable(d.items() for d in c))
                  for c in itertools.product(*axes)]
    return [{**base, **c, "seed": s} for c in combos
            for s in seeds(t, seed)]


def reference_point(fields: Dict[str, Any], single: bool) -> Dict[str, Any]:
    """The reference's description of one point: names, shapes and
    scalars.  The configuration and traffic files state every field the
    point's protocol, workload and topology read; the only ones a point
    may leave out (``UNREAD``) are read by no part of it, and get a
    placeholder.  A single run folds its address count and skew as
    constants, as the simulator's single-run path does; a study point
    traces them."""
    from reference import bank_count
    if (fields.get("n_workers") or fields.get("telemetry_windows")
            or fields.get("record_trace") or fields.get("faults")):
        raise ValueError("the reference models no workers, telemetry, "
                         "traces or fault plans")
    pt = {**UNREAD, **{k: v for k, v in fields.items() if k in UNREAD}}
    for k in ("protocol", "workload", "topology", "n_cores", "cycles",
              "q_slots") + SCALARS:
        if k not in UNREAD:
            pt[k] = fields[k]
    pt["banks"] = bank_count(fields["n_addrs"])
    pt["static"] = ("n_addrs", "zipf_skew") if single else ()
    return pt
