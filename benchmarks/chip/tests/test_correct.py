"""``correct`` separates sound runs from the control and from faults.

On the CPU, at a size a test run holds, each cell's run with the harness's
look for a chip skipped must come out correct as it stands, and not correct
with:

* the control: the program's own single-strand path (``map_fastq
  --single-strand``, ``both_strands=False``), which breaks the stated
  guarantee that every read is mapped on both strands;
* an answer altered where it is produced: every mapped position moved by
  one base as the mapper returns it;
* half of the batch left out: the second half of every batch the mapper
  returns reported unmapped.

Run as a script, it makes the same runs on the chip at the cell's own size,
to read the control's (or a fault's) numbers over several seeds:

    python3 benchmarks/chip/tests/test_correct.py --workload chr21.se150 \
        --mode control --seeds 11,12,13 --seconds 10
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[3] / "src"),
                str(Path(__file__).resolve().parents[1])]
import run  # noqa: E402

CELLS = ("chr21.se150", "chr21.pe150")


class Faulty:
    """A mapping session whose results pass through ``alter``."""

    def __init__(self, mapper, alter):
        self._m, self._alter = mapper, alter

    def map(self, reads):
        return self._alter(self._m.map(reads))

    def map_pairs(self, reads1, reads2):
        r1, r2 = self._m.map_pairs(reads1, reads2)
        return self._alter(r1), self._alter(r2)

    def close(self):
        self._m.close()


def shift_positions(res):
    pos = np.where(res.mapped, res.position + 1, res.position)
    return dataclasses.replace(res, position=pos)


def drop_half(res):
    half = len(res.position) // 2
    mapped = res.mapped.copy()
    mapped[half:] = False
    pos = np.where(mapped, res.position, -1)
    return dataclasses.replace(res, mapped=mapped, position=pos)


MODES = {
    "sound": {},
    "control": dict(mapper_options=dict(both_strands=False)),
    "shift": dict(fault=lambda m: Faulty(m, shift_positions)),
    "drop_half": dict(fault=lambda m: Faulty(m, drop_half)),
}


def small(cell: run.Cell) -> run.Cell:
    cell.config["reference_bases"] = 300_000
    t = cell.traffic
    t.update({"reads" if t["layout"] == "single" else "pairs":
              2048 if t["layout"] == "single" else 1024, "sample": 128})
    return cell


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("name", CELLS)
def test_correct_separates(name, mode, tmp_path):
    cell = small(run.load_cell(name))
    out = run.run_cell(cell, 3_000_000_019, 2.0, False, require_chip=False,
                       work=tmp_path, **MODES[mode])
    wrong = out["check"]["wrong"]
    if mode == "sound":
        assert out["correct"], wrong
        assert wrong["value"] == 0
    else:
        assert not out["correct"], (mode, wrong)
        assert wrong["value"] >= 3 * max(wrong["limit"], 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=list(MODES), default="control")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    a = ap.parse_args(argv)
    for seed in (int(s) for s in a.seeds.split(",")):
        out = run.run_cell(run.load_cell(a.workload), seed, a.seconds, False,
                           **MODES[a.mode])
        print(json.dumps(dict(workload=a.workload, mode=a.mode, seed=seed,
                              correct=out["correct"],
                              wrong=out["check"]["wrong"]["value"],
                              attempted=out["attempted"])), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
