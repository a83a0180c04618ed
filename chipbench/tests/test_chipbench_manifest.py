"""BENCHMARK.json keeps to the benchmark's rules, and a run off the TPU
fails before it prints a result."""
import re

import pytest

from chipbench import core

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _man():
    return core.manifest()


def test_names_and_units():
    man = _man()
    names = []
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            assert NAME.match(m["name"]), m["name"]
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
            names.append(m["name"])
    for c in man["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in man["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        names.append(w["name"])
    assert len(names) == len(set(names))


def test_every_cell_has_its_files_and_metrics():
    man = _man()
    for w in man["workloads"]:
        cell = core.load_cell(man, w["name"])
        assert cell["config"]["name"] == w["config"]
        e2e = {m["name"] for m in core.cell_metrics(man, w["name"],
                                                    "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert core.cell_metrics(man, w["name"], "per_layer")
    for m in man["per_layer"]:
        assert (core.HERE / "metrics" / f"{m['name']}.py").exists()


def test_moves_target_is_reported_wherever_the_metric_is():
    man = _man()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = [w["name"] for w in man["workloads"]]
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in target or cell in target["workloads"]


def test_bounds_within_rules():
    man = _man()
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert man["run_seconds"] == int(man["run_seconds"])
    n = 24
    assert (2 + 14 * n) * (man["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200


def test_unknown_device_kind_raises():
    with pytest.raises(core.BenchError):
        core.peaks("TPU v99 imaginary")
    assert core.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_without_a_tpu_fails_without_a_result(capsys):
    from chipbench import run
    with pytest.raises(core.BenchError):
        run.main(["--workload", "smollm-finetune", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""


def test_traffic_kind_without_a_runner_raises():
    man = core.load_json(core.HERE / "tests" / "data" / "BENCHMARK.json")
    cell = core.load_cell(man, "tiny-finetune", core.ROOT,
                          core.HERE / "tests" / "data" / "traffic")
    assert core.cell_runner(cell).__name__ == "chipbench.train_cell"
    cell["traffic"]["kind"] = "no_such_kind"
    with pytest.raises(core.BenchError):
        core.cell_runner(cell)
