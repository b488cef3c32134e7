"""The readings the four-chip cell's limits were set from, judged by the
cell's own limits.

``data/probe_chip_sharded.jsonl`` holds what ``probe_sharded.py`` read on
four v5e chips at the cell's own size (PERF.md, section 4), one seed a
line.  By the limits the cell ships with, every sound run is correct,
and the int8 and float8 controls, one chip's rows left out of the
all-reduce, the planted half batch and the state returned unchanged,
each put in the program's place, are not."""

import json
import os

import pytest

from benchmark.judge import compare
from benchmark.tests import probe_sharded, rehearse_sharded

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "probe_chip_sharded.jsonl")) as f:
    RECORDS = [json.loads(line) for line in f if line.strip()]
LIMITS = rehearse_sharded.cell_workload()["check"]["limits"]


def test_there_are_readings_of_the_cells_own_size():
    assert len(RECORDS) >= 3
    assert len({r["seed"] for r in RECORDS}) == len(RECORDS)
    for rec in RECORDS:
        assert rec["notes"]["shard_gauges"]["shard.rows_real_min"] \
            == rec["notes"]["shard_gauges"]["shard.rows_real_max"] \
            == 13_281_250


@pytest.mark.parametrize("rec", RECORDS, ids=lambda r: str(r["seed"]))
def test_sound_run_is_correct_and_every_stand_in_is_not(rec):
    judged = compare(rec["readings"], LIMITS)
    assert all(c["ok"] for c in judged.values()), judged
    verdicts = probe_sharded.judge_stand_ins(rec["readings"], LIMITS)
    assert set(verdicts) == set(probe_sharded.STAND_INS)
    for name, verdict in verdicts.items():
        assert not verdict["correct"], name
    assert "score_gap" in verdicts["state_unchanged"]["failed"]
    assert {"leaf_count_off", "gain_gap_rms"} \
        <= set(verdicts["shard_out"]["failed"])
    assert "gain_gap_rms" in verdicts["int8_control"]["failed"]
    assert "gain_gap_rms" in verdicts["fp8_control"]["failed"]
