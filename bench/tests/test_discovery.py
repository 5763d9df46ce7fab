"""Every piece of a cell is found by its name, so that a new
configuration, traffic mix or per-layer metric is new files plus new
entries in BENCHMARK.json, with no edit to the harness."""

import json
import re
import shutil

import pytest

import run

BENCH = run.load_json(run.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    spec = run.cell_spec(BENCH, cell)
    assert (run.BENCH / "drivers" / f"{spec['config']['driver']}.py"
            ).is_file()
    assert spec["end_to_end"] and spec["per_layer"]
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        reader = run.load_module(run.BENCH / "layers" / f"{m['name']}.py")
        assert callable(reader.reduce)


def test_benchmark_file_keeps_to_its_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    names = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and c["name"] in {
            w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(cells) // 2)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= set(cells) and UNIT.match(m["unit"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_new_files_and_entries_are_found_without_an_edit(tmp_path):
    """A copy of the benchmark with a new traffic mix, a new cell and a
    new per-layer metric: the harness finds all three by name."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    traffic = run.load_json(bench_dir / "traffic" / "qd320.json")
    traffic["outstanding_per_tenant"] = 1
    (bench_dir / "traffic" / "qd5.json").write_text(json.dumps(traffic))
    (bench_dir / "layers" / "fabric.waves_seen.py").write_text(
        "def reduce(trace, run):\n    return len(run['wave_bytes'])\n")
    bench = json.loads(json.dumps(BENCH))
    for m in bench["end_to_end"]:
        if m["name"] in ("posts_per_s", "post_p99_ms"):
            m["workloads"].append("paper-mix.qd5")
    bench["workloads"].append({
        "name": "paper-mix.qd5", "config": "tiara-paper-mix",
        "traffic": "qd5", "chips": 1, "why": "one post per tenant"})
    bench["per_layer"].append({
        "name": "fabric.waves_seen", "unit": "waves", "better": "higher",
        "source": "program_counter", "layer": "VM engine",
        "moves": "posts_per_s", "workloads": ["paper-mix.qd5"]})
    spec = run.cell_spec(bench, "paper-mix.qd5", bench_dir)
    assert spec["traffic"]["outstanding_per_tenant"] == 1
    assert spec["config"]["driver"] == "fabric"
    assert "fabric.waves_seen" in {m["name"] for m in spec["per_layer"]}
    got = run.reduce_layers(
        {"per_layer": [m for m in spec["per_layer"]
                       if m["name"] == "fabric.waves_seen"],
         "bench_dir": bench_dir}, None, {"wave_bytes": [1, 2, 3]})
    assert got == {"fabric.waves_seen": {"value": 3.0, "unit": "waves"}}


def test_run_refuses_without_a_chip(capsys):
    assert run.main(["--workload", "paper-mix.qd320", "--seed", "1",
                     "--seconds", "1"]) == 3
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
