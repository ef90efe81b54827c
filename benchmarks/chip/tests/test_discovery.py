"""Cells are found by name: every workload of BENCHMARK.json resolves its
configuration, traffic, limits and metric readers, and a new cell is new
files and entries with no edit to an existing file."""
import json
import shutil

import pytest

import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves(name):
    cell = run.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert cell.traffic["layout"] in ("single", "paired")
    assert cell.limits["wrong"]["limit"] >= 0
    assert {m["name"] for m in cell.end_to_end} >= {"reads_per_s",
                                                    "setup_s"}
    assert cell.metrics, "every cell reports a per-layer metric"
    for m in cell.metrics:
        assert callable(run.reader(m["name"]))


def test_paths_hold_every_file_the_entries_name():
    for c in BENCH["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for w in BENCH["workloads"]:
        assert (run.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (run.HERE / "limits" / f"{w['name']}.json").is_file()


def test_a_new_cell_needs_new_files_only(tmp_path):
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(run.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    (here / "traffic" / "se100.json").write_text(json.dumps(
        dict(json.loads((here / "traffic" / "se150.json").read_text()),
             name="se100", read_len=100)))
    (here / "limits" / "chr21.se100.json").write_text(
        json.dumps({"wrong": {"limit": 0}}))
    (here / "metrics" / "reads_seen.py").write_text(
        "def read(run):\n    return float(run.n_reads)\n")
    bench["workloads"].append(dict(name="chr21.se100", config="grch38_chr21",
                                   traffic="se100", chips=1, why="test"))
    bench["per_layer"].append(dict(
        name="reads_seen", unit="count", better="higher",
        source="program_counter", layer="test", moves="reads_per_s",
        workloads=["chr21.se100"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell("chr21.se100", root=tmp_path, here=here)
    assert cell.traffic["read_len"] == 100
    assert [m["name"] for m in cell.metrics] == ["reads_seen"]
    assert run.reader("reads_seen", here)(
        run.RunData(n_reads=5, paired=False, read_len=100, band=13,
                    spans={}, stats={}, compiles=0, peak_bytes=None,
                    trace=None)) == 5.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no.such.cell")
