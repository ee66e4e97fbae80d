"""Cells, configurations, traffic mixes and metrics are found by name, and
BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
import shutil

import pytest

from perfbench import cells, harness, loadgen

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name):
    cell = cells.find_cell(name)
    t, r, s = loadgen.shape(cell.config, cell.traffic)
    assert min(t, r, s) >= 1
    assert cells.reference(cell.config).matmul
    kinds = {m.kind for m in cell.metrics}
    assert kinds == {"end_to_end", "per_layer"}
    names = {m.name for m in cell.metrics_of("end_to_end")}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.metrics:
        assert callable(cells.reader(m.name))
        if m.kind == "per_layer":
            # the end-to-end metric it moves is reported in this cell
            assert m.moves in names


def test_routed_tokens_come_from_the_configuration():
    cell = cells.find_cell("expert2048.routed")
    assert loadgen.shape(cell.config, cell.traffic) == (384, 2048, 1408)


def test_contract_shape_of_benchmark_json():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[kind]}) == len(BENCH[kind])
    metric_names = [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 2)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/")
        assert set(c["reduced"]) <= set(json.loads(
            (cells.ROOT / c["file"]).read_text()))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m


def test_a_new_cell_metric_and_config_are_found_by_name(tmp_path):
    """A later change adds files and entries, and edits no file."""
    here = tmp_path / "perfbench"
    shutil.copytree(cells.HERE, here,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    config = json.loads((cells.ROOT / bench["configs"][0]["file"]).read_text())
    config["hidden_size"] = 512
    (here / "configs" / "tiny.json").write_text(json.dumps(config))
    (here / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "closed", "tokens": 8, "clients": 2, "operand_sets": 3}))
    (here / "metrics" / "answered_count.py").write_text(
        "def read(run):\n    return len(run.answered)\n")
    bench["configs"].append({"name": "tiny", "source": "x",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "answered_count", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "mac_rate",
                               "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.find_cell("tiny.burst", root=tmp_path, here=here)
    assert loadgen.shape(cell.config, cell.traffic) == (8, 512, 512)
    assert "answered_count" in {m.name for m in cell.metrics}
    read = cells.reader("answered_count", here=here)

    class Run:
        answered = [1, 2, 3]

    assert read(Run()) == 3
    # the metric lists its cells: the others do not report it
    other = cells.find_cell(CELLS[0], root=tmp_path, here=here)
    assert "answered_count" not in {m.name for m in other.metrics}


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(cells.CellError):
        cells.find_cell("no.such.cell")
    with pytest.raises(cells.CellError):
        cells.reader("no_such_metric")


def _cell_with(tmp_path, traffic=None, config=None):
    """BENCHMARK.json's first cell, in a copy whose traffic and
    configuration files hold the keys given."""
    here = tmp_path / "perfbench"
    shutil.copytree(cells.HERE, here,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    w = BENCH["workloads"][0]
    (c,) = [c for c in BENCH["configs"] if c["name"] == w["config"]]
    path = tmp_path / c["file"]
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                **(config or {})}))
    tpath = here / "traffic" / f"{w['traffic']}.json"
    tpath.write_text(json.dumps({**json.loads(tpath.read_text()),
                                 **(traffic or {})}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    return lambda: cells.find_cell(w["name"], root=tmp_path, here=here)


@pytest.mark.parametrize("traffic", [
    {"loop": "open"}, {"rate_per_s": 3.0}, {"tokens": 8, "batch_tokens": 64},
    {"clients": 0},
], ids=["open_loop", "unknown_key", "two_token_counts", "no_clients"])
def test_a_traffic_setting_the_generator_does_not_carry_out_is_refused(
        tmp_path, traffic):
    with pytest.raises(cells.CellError, match="traffic"):
        _cell_with(tmp_path, traffic=traffic)()


def test_the_planner_calibration_is_the_configurations(tmp_path, monkeypatch):
    assert _cell_with(tmp_path)().config["planner_calibration"] in (
        cells.CALIBRATIONS)
    with pytest.raises(cells.CellError, match="planner_calibration"):
        _cell_with(tmp_path / "x", config={"planner_calibration": "cpu"})()
    monkeypatch.setenv("REPRO_CALIBRATION", "off")
    harness.apply_calibration({"planner_calibration": "default"})
    assert "REPRO_CALIBRATION" not in harness.os.environ
    harness.apply_calibration({"planner_calibration": "off"})
    assert harness.os.environ["REPRO_CALIBRATION"] == "off"


@pytest.mark.parametrize("caps,chips,ok", [
    ({"platform": "tpu", "device_count": 1}, 1, True),
    ({"platform": "tpu", "device_count": 4}, 1, False),
    ({"platform": "tpu", "device_count": 1}, 4, False),
    ({"platform": "cpu", "device_count": 1}, 1, False),
    ({"platform": "tpu", "device_count": 4}, None, True),
], ids=["one_on_one", "one_on_four", "four_on_one", "cpu", "rehearsal"])
def test_the_worker_holds_exactly_the_cells_chips(caps, chips, ok):
    if ok:
        harness.check_worker(caps, "tpu", chips)
    else:
        with pytest.raises(harness.RunFailure):
            harness.check_worker(caps, "tpu", chips)
