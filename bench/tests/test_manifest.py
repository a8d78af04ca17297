"""BENCHMARK.json and the files it names have the contract's form."""
import copy
import json

import pytest

from bench import spec


@pytest.fixture
def man():
    return spec.manifest()


def test_manifest_is_sound(man):
    assert spec.validate(man) == []
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(man)) <= 64 * 1024


def test_names_and_units(man):
    for m in man["end_to_end"] + man["per_layer"]:
        assert spec.NAME_RE.match(m["name"]), m["name"]
        assert spec.UNIT_RE.match(m["unit"]), m["unit"]
    for w in man["workloads"]:
        assert spec.NAME_RE.match(w["name"])
        assert spec.NAME_RE.match(w["traffic"])


def test_every_listed_cell_reports_the_moved_metric(man):
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        for wl in m.get("workloads", cells):
            assert wl in e2e[m["moves"]].get("workloads", cells)
    for wl in cells:
        names = {m["name"] for m in spec.end_to_end_for(man, wl)}
        assert "setup_s" in names and len(names) >= 2
        assert spec.per_layer_for(man, wl)


def test_every_cell_has_its_files(man):
    for w in man["workloads"]:
        cfg = spec.config(w["config"])
        t = spec.traffic(w["traffic"])
        assert t["chips"] == w["chips"]
        lim = spec.limits(w["name"])
        assert set(lim) >= {"loss_gap", "grad_gap", "grad_diff", "update_gap"}
        assert cfg["guarantees"]["preserver_eps"] > 0
    for c in man["configs"]:
        f = spec.config(c["name"])
        assert set(c["reduced"]) == set(f["reduced"])
        assert f["source"] == c["source"]


@pytest.mark.parametrize("breakage", [
    ("unit", "tokens per second"),
    ("name", "bad name"),
    ("moves", "nothing"),
])
def test_validate_catches(man, breakage):
    key, value = breakage
    bad = copy.deepcopy(man)
    target = bad["per_layer"][0] if key == "moves" else bad["end_to_end"][0]
    target[key] = value
    assert spec.validate(bad)


def test_a_cell_that_does_not_report_the_moved_metric_is_caught(man):
    bad = copy.deepcopy(man)
    tok = next(m for m in bad["end_to_end"] if m["name"] == "tokens_per_s")
    tok["workloads"] = [bad["workloads"][0]["name"]]
    assert any("does not report" in p for p in spec.validate(bad))


@pytest.mark.parametrize("arch_module,reason", [
    (None, "does not name a module"),
    ("../archs/dense", "does not name a module"),
    ("archs.no_such_arch", "No module named"),
    ("compare", "lacks program_config, leaf_kind"),
    ("archs.dense", "lacks tiny"),
], ids=["missing", "not_a_name", "no_such_module", "not_an_arch",
        "lacks_a_function"])
def test_validate_refuses_a_config_without_its_arch_module(
        man, tmp_path, monkeypatch, arch_module, reason):
    from bench.archs import dense

    c = spec.config(man["configs"][0]["name"])
    c.pop("arch_module")
    if arch_module is not None:
        c["arch_module"] = arch_module
    if reason == "lacks tiny":
        monkeypatch.delattr(dense, "tiny")
    path = tmp_path / "config.json"
    path.write_text(json.dumps(c))
    bad = copy.deepcopy(man)
    bad["configs"][0]["file"] = str(path)
    problems = spec.validate(bad)
    assert len(problems) == 1 and "\n" not in problems[0], problems
    assert problems[0].startswith(f"config {man['configs'][0]['name']}: ")
    assert reason in problems[0]
