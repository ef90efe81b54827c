"""Observability CLI smoke (the ``obs-smoke`` CI job's test): run
``map_fastq --trace-out --metrics-out --log-json`` as a subprocess on
both topologies on a tiny genome, then validate the exported Chrome
trace with the dependency-free checker (B/E balance, numeric pid/tid/
ts/dur) and the metrics JSONL against the checked-in schema at
``schemas/metrics_snapshot.schema.json``."""
import json
import os
import subprocess
import sys

import pytest

from repro.data.genome import (make_reference, sample_reads, write_fasta,
                               write_fastq)
from repro.obs.validate import (load_json, validate_chrome_trace,
                                validate_jsonl)

READ_LEN = 120
N_READS = 24
SCHEMA = os.path.join(os.path.dirname(__file__), "..", "schemas",
                      "metrics_snapshot.schema.json")


@pytest.fixture(scope="module")
def fastq_world(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs_smoke")
    ref = make_reference(5_000, seed=0, repeat_frac=0.02)
    write_fasta(d / "ref.fa", [("chr1", ref)])
    rs = sample_reads(ref, N_READS, read_len=READ_LEN, seed=3,
                      both_strands=True)
    write_fastq(d / "reads.fq", rs.reads, rs.quals,
                [f"read{i}" for i in range(N_READS)])
    return d


def _run_map_fastq(d, *argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch.map_fastq",
           str(d / "ref.fa"), str(d / "reads.fq"), *argv,
           "--chunk-reads", "16"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    return proc.stderr


def _json_lines(text):
    out = []
    for ln in text.splitlines():
        try:
            out.append(json.loads(ln))
        except json.JSONDecodeError:
            continue
    return out


@pytest.mark.parametrize("topology", ["single", "mesh"])
def test_trace_and_metrics_exports(fastq_world, topology):
    d = fastq_world
    tag = topology
    extra = ["--topology", "mesh", "--shards", "1"] \
        if topology == "mesh" else []
    stderr = _run_map_fastq(
        d, "-o", str(d / f"out_{tag}.sam"),
        "--trace-out", str(d / f"trace_{tag}.json"),
        "--metrics-out", str(d / f"metrics_{tag}.jsonl"),
        "--log-json", *extra)

    # Chrome trace: loads, validates, and holds the chunk lifecycle
    trace = load_json(d / f"trace_{tag}.json")
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    expected = ({"dispatch", "d2h"} if topology == "mesh"
                else {"seed", "d2h"})
    assert expected <= names, names
    assert "repro/fastq" in names and "repro/sam" in names
    # spans carry chunk attribution for the viewer
    assert any(e.get("args", {}).get("chunk") is not None
               for e in trace["traceEvents"] if e["ph"] == "X")

    # metrics JSONL: every snapshot line matches the checked-in schema,
    # and counters end up covering the run accounting
    schema = load_json(SCHEMA)
    assert validate_jsonl(d / f"metrics_{tag}.jsonl", schema) == []
    last = [json.loads(ln) for ln in
            open(d / f"metrics_{tag}.jsonl") if ln.strip()][-1]
    counters = last["counters"]
    # dual-strand mesh runs count each strand encoding as a mapped row,
    # so the counter is >= the FASTQ read count on that topology
    assert counters[f'repro_reads_total{{topology="{tag}"}}'] >= N_READS
    assert any(k.startswith("repro_stage_seconds_total") for k in counters)

    # --log-json: launcher progress is one JSON object per line (other
    # stderr writers — jax/absl warnings — may interleave; skip them)
    events = [obj.get("event") for obj in _json_lines(stderr)]
    assert "start" in events and "done" in events and "chunk" in events


def test_trace_durations_match_metrics_counters(fastq_world):
    """The CLI-level acceptance property: the exported trace's summed
    per-stage durations equal the ``repro_stage_seconds_total`` counters
    in the final metrics snapshot — both accrue from the same
    ``streaming.timed`` clock reads (and the counters are
    ``stage_times_s`` by the same construction)."""
    d = fastq_world
    _run_map_fastq(
        d, "-o", str(d / "out_agree.sam"),
        "--trace-out", str(d / "trace_agree.json"),
        "--metrics-out", str(d / "metrics_agree.jsonl"))
    last = [json.loads(ln) for ln in
            open(d / "metrics_agree.jsonl") if ln.strip()][-1]
    st = {k.split('stage="')[1].rstrip('"}'): v
          for k, v in last["counters"].items()
          if k.startswith("repro_stage_seconds_total")}
    assert st, "no per-stage counters in the final snapshot"
    totals = {}
    for e in load_json(d / "trace_agree.json")["traceEvents"]:
        if e["ph"] == "X":
            totals[e["name"]] = totals.get(e["name"], 0.0) + e["dur"] / 1e6
    for k, v in st.items():
        assert totals[k] == pytest.approx(v, rel=1e-6, abs=1e-7), k


def test_build_index_exports(fastq_world, tmp_path):
    d = fastq_world
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch.build_index",
           str(d / "ref.fa"), "-o", str(tmp_path / "ref.idx"),
           "--partitions", "2", "--read-len", str(READ_LEN),
           "--trace-out", str(tmp_path / "trace.json"),
           "--metrics-out", str(tmp_path / "metrics.jsonl"),
           "--log-json"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    trace = load_json(tmp_path / "trace.json")
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"index_scan", "index_partition"} <= names
    assert validate_jsonl(tmp_path / "metrics.jsonl",
                          load_json(SCHEMA)) == []
    events = [o.get("event") for o in _json_lines(proc.stderr)]
    assert "done" in events and "progress" in events
