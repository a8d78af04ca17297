"""The benchmark's data: BENCHMARK.json, the configuration, traffic,
limit and peak files it names, and the checks of their form.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<workload>.json`` and
``metrics/<metric>.py`` under this directory, and the architecture
module that the configuration file names (``arch_module``, a module
under ``archs/``; the contract is in ``archs/__init__.py``).  Adding a
cell, a configuration, an architecture, a traffic mix or a per-layer
metric is adding files and entries; no file here changes.
"""
from __future__ import annotations

import importlib
import json
import pathlib
import re
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MODULE_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*(\.[A-Za-z_][A-Za-z0-9_]*)*$")
# the functions every architecture module has (archs/__init__.py)
ARCH_CONTRACT = ("program_config", "leaf_kind", "row_loss", "matrices",
                 "total_params", "model_flops_per_token", "attn_fwd_cost",
                 "tiny")


class SpecError(ValueError):
    pass


def _load(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def manifest(path: pathlib.Path = MANIFEST) -> Dict[str, Any]:
    return _load(path)


def config(name: str) -> Dict[str, Any]:
    return _load(BENCH_DIR / "configs" / f"{name}.json")


def traffic(name: str) -> Dict[str, Any]:
    return _load(BENCH_DIR / "traffic" / f"{name}.json")


def limits(workload: str) -> Dict[str, Any]:
    return _load(BENCH_DIR / "limits" / f"{workload}.json")


def peaks() -> Dict[str, Any]:
    return _load(BENCH_DIR / "peaks.json")


def arch(c: Dict[str, Any]):
    """The architecture module that configuration ``c`` names with
    ``arch_module`` (a dotted name under the ``bench`` package)."""
    name = c.get("arch_module")
    if not isinstance(name, str) or not MODULE_RE.match(name):
        raise SpecError(f"arch_module {name!r} does not name a module")
    try:
        mod = importlib.import_module(f"bench.{name}")
    except ModuleNotFoundError as e:
        raise SpecError(f"arch_module {name!r}: {e}") from None
    missing = [f for f in ARCH_CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"arch_module {name!r} lacks {', '.join(missing)}")
    return mod


def workload(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json (known: "
                    f"{[w['name'] for w in man['workloads']]})")


def end_to_end_for(man: Dict[str, Any], wl: str) -> List[Dict[str, Any]]:
    return [m for m in man["end_to_end"]
            if wl in m.get("workloads", [wl])]


def per_layer_for(man: Dict[str, Any], wl: str) -> List[Dict[str, Any]]:
    reported = {m["name"] for m in end_to_end_for(man, wl)}
    return [m for m in man["per_layer"]
            if wl in m.get("workloads", [wl]) and m["moves"] in reported]


def validate(man: Dict[str, Any]) -> List[str]:
    """Problems with the manifest's form (empty when it is sound)."""
    bad: List[str] = []

    def name(kind: str, s: Any) -> None:
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{kind} {s!r} is not a name")

    def line(kind: str, s: Any) -> None:
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            bad.append(f"{kind} {s!r} is not one line of 1-200 characters")

    cfgs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        name("config", c["name"])
        line("source", c["source"])
        for k in c["reduced"]:
            name("reduced key", k)
        path = ROOT / c["file"]
        if not path.is_file():
            bad.append(f"config file {c['file']} missing")
            continue
        try:
            arch(_load(path))
        except SpecError as e:
            bad.append(f"config {c['name']}: {e}")
    wls = {w["name"]: w for w in man["workloads"]}
    pairs = set()
    for w in man["workloads"]:
        name("workload", w["name"])
        name("traffic", w["traffic"])
        line("why", w["why"])
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']} names no config")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']} asks for {w['chips']} chips")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            bad.append(f"workload {w['name']} repeats {pair}")
        pairs.add(pair)
    e2e = {m["name"]: m for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    seen = set()
    for m in man["end_to_end"] + man["per_layer"]:
        name("metric", m["name"])
        if m["name"] in seen:
            bad.append(f"metric {m['name']} twice")
        seen.add(m["name"])
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source of {m['name']}")
        for wl in m.get("workloads", []):
            if wl not in wls:
                bad.append(f"metric {m['name']} lists unknown cell {wl}")
    for m in man["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']} takes {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            bad.append(f"bound of {m['name']}")
    for m in man["per_layer"]:
        line("layer", m["layer"])
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        for wl in m.get("workloads", list(wls)):
            if wl not in e2e[m["moves"]].get("workloads", [wl]):
                bad.append(f"{m['name']}: cell {wl} does not report "
                           f"{m['moves']}")
        if not (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file():
            bad.append(f"no reader metrics/{m['name']}.py")
    for w in man["workloads"]:
        own = end_to_end_for(man, w["name"])
        if len(own) < 2 or not per_layer_for(man, w["name"]):
            bad.append(f"cell {w['name']} reports too few metrics")
    return bad
