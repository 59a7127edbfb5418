"""``BENCHMARK.json`` resolves to files and keeps the benchmark's naming
rules: every cell finds its configuration, traffic mix, limits and the
readers of its per-layer metrics by name; names and units use only the
allowed characters; each per-layer metric's cells report the end-to-end
metric it moves."""
import json
import os
import re

import pytest

from chipbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks/chip"]
    for word in M["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert word.startswith("benchmarks/chip/")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_to_its_files(workload):
    cell = manifest.resolve(workload, M)
    assert cell.config["name"] == next(
        w["config"] for w in M["workloads"] if w["name"] == workload)
    assert cell.traffic["front_door"] in ("run_fl", "run_rounds")
    assert set(cell.limits["numbers"])
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.traffic["rate_metric"] in names
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(manifest.metric_reader(m["name"]))


def test_configuration_files_are_under_paths_and_distinct():
    files = [c["file"] for c in M["configs"]]
    assert len(set(files)) == len(files)
    for c in M["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in M["workloads"])


def test_names_and_units_use_allowed_characters():
    entries = (M["configs"] + M["workloads"] + M["end_to_end"]
               + M["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in M["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [e["name"] for e in M["end_to_end"] + M["per_layer"]]
    assert len(set(names)) == len(names)
    assert len({w["name"] for w in M["workloads"]}) == len(M["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_per_layer_cells_report_what_they_move():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            reported = [x["name"] for x in manifest.resolve(w, M).end_to_end]
            assert m["moves"] in reported, (m["name"], w)


def test_bounds_and_sources():
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m
    assert 1 <= M["run_seconds"] <= 51


@pytest.mark.parametrize("workload", CELLS)
def test_cell_settings_resolve_to_modules(workload):
    cell = manifest.resolve(workload, M)
    ref = manifest.module("reference", cell.traffic["reference"])
    assert callable(ref.check_supported)
    if "family" in cell.config:
        fam = manifest.module("families", cell.config["family"])
        assert fam.forward_flops(cell.config) > 0
        assert callable(manifest.module("reference",
                                        cell.config["family"]).forward)


def _speech(edit):
    cell = manifest.resolve("speech_sync_eafl", M)
    edit(cell.config, cell.traffic)
    return cell


def _reddit(edit):
    cell = manifest.resolve("reddit_select_eafl", M)
    cell.config["fleet"]["n_clients"] = 1024
    edit(cell.config, cell.traffic)
    return cell


# a setting the reference does not implement, or that the program does
# not have, fails when the driver is built, before any call
UNSUPPORTED = {
    "fl_config_not_in_reference":
        (_speech, lambda c, t: t["fl_config"].update(overcommit=1.3)),
    "fl_config_not_a_field":
        (_speech, lambda c, t: t["fl_config"].update(no_such_field=1)),
    "run_fl_async":
        (_speech, lambda c, t: t.update(run_fl={"mode": "async"})),
    "selector_kind":
        (_speech, lambda c, t: t["selector"].update(kind="oort")),
    "model_norm":
        (_speech, lambda c, t: c.update(norm="batch_norm")),
    "fleet_key":
        (_reddit, lambda c, t: c["fleet"].update(diurnal=True)),
    "run_rounds_deadline":
        (_reddit, lambda c, t: t.update(run_rounds={"deadline_s": 60.0})),
    "energy_model":
        (_reddit, lambda c, t: c["energy_model"].update(
            scale_comm_to_capacity=True)),
}


@pytest.mark.parametrize("case", sorted(UNSUPPORTED))
def test_unsupported_setting_fails_at_setup(case):
    from chipbench.drivers import make_driver

    build, edit = UNSUPPORTED[case]
    with pytest.raises((ValueError, TypeError)):
        make_driver(build(edit), 1)


@pytest.mark.parametrize("case", ["speech", "reddit"])
def test_settings_reach_the_program(case):
    """Every setting of the mix and the configuration is in what the
    program is given."""
    from chipbench.drivers import make_driver

    if case == "speech":
        d = make_driver(_speech(lambda c, t: None), 1)
        for k, v in d.fl.items():
            assert getattr(d.base, k) == v, k
        assert d.base.selector.k == d.selector["k"]
        assert d.base.model.blocks_per_stage == d.model["blocks_per_stage"]
    else:
        d = make_driver(_reddit(lambda c, t: None), 1)
        assert d.pop.n == 1024 and d.sel.k == d.selector["k"]
        assert d.em.busy_fraction == d.energy["busy_fraction"]
