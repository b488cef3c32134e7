"""The readings the limits were set from, judged by the cell's own limits.

``data/probe_chip.jsonl`` holds what ``probe_limits.py`` read on the v5e
at the cell's own size (PERF.md, section 4), one seed a line.  By the
limits the cell ships with, every sound run is correct, and the int8
control, the planted half batch and the state returned unchanged, each
put in the program's place, are not."""

import json
import os

import pytest

from benchmark.judge import compare
from benchmark.tests import probe_limits, rehearse

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "probe_chip.jsonl")) as f:
    RECORDS = [json.loads(line) for line in f if line.strip()]
LIMITS = rehearse.cell_workload()["check"]["limits"]


def test_there_are_readings_of_the_cells_own_size():
    assert len(RECORDS) >= 3
    assert len({r["seed"] for r in RECORDS}) == len(RECORDS)


@pytest.mark.parametrize("rec", RECORDS, ids=lambda r: str(r["seed"]))
def test_sound_run_is_correct_and_every_stand_in_is_not(rec):
    judged = compare(rec["readings"], LIMITS)
    assert all(c["ok"] for c in judged.values()), judged
    verdicts = probe_limits.judge_stand_ins(rec["readings"], LIMITS)
    for name, verdict in verdicts.items():
        assert not verdict["correct"], name
    assert "score_gap" in verdicts["state_unchanged"]["failed"]
    assert "leaf_value_gap" in verdicts["half_batch"]["failed"]
