"""The harness finds every part of every cell by name, and refuses an
unknown one."""
import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {"clients", "time_limit_s", "walks", "warmup_cuts",
                "due_wait_s"} <= set(cell.traffic)
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.read)


def test_every_named_file_exists(bench):
    root = spec.ROOT
    for c in bench["configs"]:
        assert (root / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for w in bench["workloads"]:
        assert (spec.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_names_units_and_bounds_keep_the_contract(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs_state_their_cut_and_assumptions(bench):
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert set(c["reduced"]) <= set(cfg)
        assert cfg["assumed"] and cfg["precision"] == "float64"


def test_unknown_names_are_refused(tmp_path, bench):
    (tmp_path / "bench").symlink_to(spec.BENCH)
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no-such-cell")
    broken = dict(bench)
    broken["workloads"] = [dict(bench["workloads"][0], traffic="no-such-mix")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(broken))
    with pytest.raises(spec.SpecError, match="no-such-mix"):
        spec.load_cell(broken["workloads"][0]["name"], root=tmp_path)
    broken["workloads"] = [dict(bench["workloads"][0], config="no-such-config")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(broken))
    with pytest.raises(spec.SpecError, match="unknown config"):
        spec.load_cell(broken["workloads"][0]["name"], root=tmp_path)
    broken = dict(bench, per_layer=[dict(bench["per_layer"][0], name="no_such_metric")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(broken))
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.load_cell(bench["workloads"][0]["name"], root=tmp_path)
