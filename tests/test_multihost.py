"""Multi-host pod-slice training (docs/Sharding.md, multi-controller).

Two layers:

* **Unit tests** (tier-1): the pure pieces of the pod contract — the
  bring-up parameter resolver, the pod row layout (contiguity +
  per-device bucket), the length-prefixed reference broadcast and its
  serialization, the row-span-filtered streaming round, and the
  ack/commit snapshot protocol — all in-process, no jax.distributed.
* **Pod scenarios** (``slow`` + one fast fail-fast case): N real OS
  processes under a localhost coordinator via
  tests/_multihost_worker.py, each rank forcing ``4 // hosts`` CPU
  devices so every leg runs the same 4-device global mesh.  Asserted:
  1-vs-2-vs-4-process tree BYTE-identity under ``grad_quant_bits=8``,
  bagging/feature_fraction host-invariance, mapper-broadcast layout
  equality, kill-one-host -> resume byte-identity, zero warm-window
  retraces per host, and bounded fail-fast on a dead coordinator.

Where the container cannot bring up multi-process jax (gloo missing,
jax.distributed unavailable), the workers report ``{"skip": reason}``
and the tests record it — environmental; the contract is validated on
real pod slices.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import _multihost_worker as mhw   # noqa: E402 — path set above

_WORKER = os.path.join(os.path.dirname(__file__),
                       "_multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_pod(scenario, hosts, outdir, timeout=420,
             expected_exits=None):
    """Launch ``hosts`` worker ranks, wait for all, and return the
    per-rank JSON reports (None for a rank that wrote none, e.g.
    killA's victim).  Skips the calling test if any rank reports an
    environmental bring-up skip."""
    outdir = str(outdir)
    port = _free_port()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = []
    for rank in range(hosts):
        log = open(os.path.join(outdir, f"{scenario}_r{rank}.log"),
                   "w")
        procs.append((rank, log, subprocess.Popen(
            [sys.executable, _WORKER, scenario, str(rank),
             str(hosts), str(port), outdir],
            stdout=log, stderr=subprocess.STDOUT, env=env)))
    deadline = time.monotonic() + timeout
    exits = {}
    try:
        for rank, _, proc in procs:
            left = deadline - time.monotonic()
            exits[rank] = proc.wait(timeout=max(left, 1.0))
    except subprocess.TimeoutExpired:
        for _, _, proc in procs:
            proc.kill()
        raise AssertionError(
            f"pod scenario {scenario} ({hosts} hosts) timed out "
            f"after {timeout}s; see {outdir}/{scenario}_r*.log")
    finally:
        for _, log, _ in procs:
            log.close()
    reports = []
    for rank in range(hosts):
        path = os.path.join(outdir, f"{scenario}_r{rank}.json")
        reports.append(json.load(open(path))
                       if os.path.exists(path) else None)
    for rep in reports:
        if rep and "skip" in rep:
            pytest.skip(rep["skip"])
    expected = expected_exits or {r: 0 for r in range(hosts)}
    for rank, code in exits.items():
        assert code == expected.get(rank, 0), \
            (f"{scenario} rank {rank} exited {code} (expected "
             f"{expected.get(rank, 0)}); see "
             f"{outdir}/{scenario}_r{rank}.log")
    return reports


@pytest.fixture(scope="module")
def pod_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("multihost")
    mhw.write_csv(str(d))
    return d


@pytest.fixture(scope="module")
def baseline(pod_dir):
    """Single-process single_controller leg over the SAME csv/loader —
    the byte-identity reference for every pod leg."""
    sub = pod_dir / "base"
    sub.mkdir()
    os.link(mhw.data_path(str(pod_dir)), mhw.data_path(str(sub)))
    return _run_pod("train", 1, sub)[0]


# ---------------------------------------------------------------------------
# unit layer: bring-up params, row layout, broadcast, filtered round two,
# ack/commit protocol
# ---------------------------------------------------------------------------

def test_multihost_params_resolution(monkeypatch):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.shard import (ENV_HOST_RANK, ENV_NUM_HOSTS,
                                        multihost_params)
    from lightgbm_tpu.utils.log import LightGBMError
    assert multihost_params(Config({})) is None
    cfg = Config({"coordinator_address": "h0:1234", "num_hosts": 4,
                  "host_rank": 3})
    assert multihost_params(cfg) == ("h0:1234", 4, 3)
    # env fallback completes a partial config
    monkeypatch.setenv(ENV_NUM_HOSTS, "2")
    monkeypatch.setenv(ENV_HOST_RANK, "1")
    assert multihost_params(
        Config({"coordinator_address": "h0:1234"})) == ("h0:1234", 2, 1)
    monkeypatch.delenv(ENV_NUM_HOSTS)
    monkeypatch.delenv(ENV_HOST_RANK)
    # partial or malformed triples must raise, not guess
    with pytest.raises(LightGBMError, match="ALL of"):
        multihost_params(Config({"coordinator_address": "h0:1234"}))
    with pytest.raises(LightGBMError, match="out of range"):
        multihost_params(Config({"coordinator_address": "h0:1234",
                                 "num_hosts": 2, "host_rank": 2}))
    with pytest.raises(LightGBMError, match="host:port"):
        multihost_params(Config({"coordinator_address": "h0",
                                 "num_hosts": 2, "host_rank": 0}))


class _FakeDev:
    def __init__(self, pid, did):
        self.process_index = pid
        self.id = did


class _FakeMesh:
    def __init__(self, pids):
        arr = np.empty(len(pids), dtype=object)
        for i, p in enumerate(pids):
            arr[i] = _FakeDev(p, i)
        self.devices = arr


def test_process_row_span_contiguity():
    from lightgbm_tpu.ops.shard import process_row_span
    from lightgbm_tpu.utils.log import LightGBMError
    mesh = _FakeMesh([0, 0, 1, 1])
    assert process_row_span(mesh, 1000, process_index=0) == (0, 2000)
    assert process_row_span(mesh, 1000, process_index=1) == (2000, 4000)
    with pytest.raises(LightGBMError, match="owns no devices"):
        process_row_span(mesh, 1000, process_index=7)
    # interleaved device ownership breaks the streamed-slab contract
    with pytest.raises(LightGBMError, match="not contiguous"):
        process_row_span(_FakeMesh([0, 1, 0, 1]), 1000,
                         process_index=0)


def test_shard_local_rows_covers_global_rows():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.shard import shard_local_rows
    for n, d in [(2500, 4), (100_000, 4), (7, 2), (1, 4)]:
        for extra in ({}, {"grad_quant_bits": 8},
                      {"train_row_bucketing": False}):
            n_loc = shard_local_rows(n, d, Config(extra))
            assert n_loc * d >= n
            assert n_loc % 1 == 0 and n_loc > 0


def test_broadcast_blob_roundtrip(tmp_path):
    import threading
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel.network import broadcast_blob
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    cfg = Config({"network_timeout": 2, "network_retries": 5})
    payload = b"\x00mapper-reference\xff" * 1000
    got = {}

    def peer(rank):
        got[rank] = broadcast_blob(None, address=addr, num_hosts=3,
                                   rank=rank, config=cfg)

    threads = [threading.Thread(target=peer, args=(r,))
               for r in (1, 2)]
    for t in threads:
        t.start()
    out0 = broadcast_blob(payload, address=addr, num_hosts=3, rank=0,
                          config=cfg)
    for t in threads:
        t.join(timeout=30)
    assert out0 == payload
    assert got[1] == payload and got[2] == payload


def test_reference_broadcast_bytes_roundtrip(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.pipeline.bins import (reference_from_bytes,
                                            reference_layout_digest,
                                            reference_to_bytes)
    from lightgbm_tpu.utils.log import LightGBMError
    rng = np.random.default_rng(3)
    x = rng.standard_normal((400, 5))
    ds = BinnedDataset.construct_from_matrix(x, Config({"max_bin": 31}))
    blob = reference_to_bytes(ds, extra={"n_total": 400})
    skel, extra = reference_from_bytes(blob)
    assert extra == {"n_total": 400}
    assert reference_layout_digest(skel) == reference_layout_digest(ds)
    assert [m.num_bin for m in skel.bin_mappers] == \
        [m.num_bin for m in ds.bin_mappers]
    assert [g.feature_indices for g in skel.groups] == \
        [g.feature_indices for g in ds.groups]
    with pytest.raises(LightGBMError, match="magic mismatch"):
        reference_from_bytes(b"garbage-not-a-reference")


def test_round_two_row_span_filter(tmp_path):
    """The filtered round bins exactly the global block [lo, hi) at
    local coordinates, and parses every label."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.data.stream_loader import (_Format, _round_one,
                                                 _round_two)
    csv = str(tmp_path / "mini.csv")
    mhw_rows = 200
    rng = np.random.default_rng(5)
    x = rng.standard_normal((mhw_rows, 4))
    y = (x[:, 0] > 0).astype(float)
    with open(csv, "w") as fh:
        for i in range(mhw_rows):
            fh.write(",".join([repr(float(y[i]))]
                              + [repr(float(v)) for v in x[i]]) + "\n")
    cfg = Config({"two_round": True, "max_bin": 31})
    fmt = _Format(csv, cfg)
    sample, n_total, num_cols = _round_one(csv, fmt, cfg)
    full = BinnedDataset.construct_streaming_begin(
        sample, n_total, num_cols, cfg)
    full_label = _round_two(csv, fmt, full, num_cols, n_total)
    lo, hi = 64, 160
    part = BinnedDataset.construct_streaming_begin(
        np.zeros((0, num_cols)), hi - lo, num_cols, cfg,
        reference=full)
    part_label = _round_two(csv, fmt, part, num_cols, n_total,
                            placement=[(lo, hi, 0)])
    assert np.array_equal(part.binned, full.binned[lo:hi])
    assert np.array_equal(part_label, full_label)
    # a span past the real rows bins nothing but still parses labels
    tail = BinnedDataset.construct_streaming_begin(
        np.zeros((0, num_cols)), 64, num_cols, cfg, reference=full)
    tail_label = _round_two(csv, fmt, tail, num_cols, n_total,
                            placement=[(n_total + 64, n_total + 128, 0)])
    assert not tail.binned.any()
    assert np.array_equal(tail_label, full_label)


def test_pod_ack_commit_protocol(tmp_path):
    from lightgbm_tpu.robust import checkpoint as ck
    from lightgbm_tpu.utils.log import LightGBMError
    path = str(tmp_path / "snap.txt")
    score = np.arange(6, dtype=np.float32).reshape(1, 6)
    digest = ck.pod_state_digest("tree...", score, 3)
    assert digest == ck.pod_state_digest("tree...", score.copy(), 3)
    assert digest != ck.pod_state_digest("tree...", score, 4)
    # happy path: both hosts ack, host 0 commits, peer sees it
    ck.write_pod_ack(path, 0, digest)
    ck.write_pod_ack(path, 1, digest)
    ck.await_pod_acks(path, 2, digest, timeout_s=5.0)
    ck.clear_pod_acks(path, 2)
    ck.commit_pod(path, digest)
    assert ck.has_pod_commit(path)
    ck.await_pod_commit(path, digest, timeout_s=5.0)
    # a commit marker from an OLDER snapshot must not satisfy the wait
    with pytest.raises(LightGBMError, match="commit"):
        ck.await_pod_commit(path, "different-digest", timeout_s=0.3)
    # missing ack: timeout error NAMES the dead host
    os.remove(ck.pod_commit_path(path))
    ck.write_pod_ack(path, 0, digest)
    with pytest.raises(LightGBMError, match=r"no ack from host\(s\) "
                                            r"\[1\]"):
        ck.await_pod_acks(path, 2, digest, timeout_s=0.3)
    # diverged ack: refuse loudly, never time out silently
    ck.write_pod_ack(path, 1, "poisoned-digest")
    with pytest.raises(LightGBMError, match="diverged"):
        ck.await_pod_acks(path, 2, digest, timeout_s=5.0)


def test_multihost_forbids_machine_parallel_learner():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import create_tree_learner
    from lightgbm_tpu.utils.log import LightGBMError
    cfg = Config({"tree_learner": "data", "num_machines": 2,
                  "data_sharding": "multi_controller",
                  "coordinator_address": "h0:1", "num_hosts": 2,
                  "host_rank": 0})
    with pytest.raises(LightGBMError, match="multi_controller"):
        create_tree_learner(cfg, None)


# ---------------------------------------------------------------------------
# pod scenarios (real processes)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_dead_coordinator_fails_fast(tmp_path):
    """A rank whose coordinator never answers raises the bounded
    peer-probe error instead of hanging in initialize."""
    rep = _run_pod("deadcoord", 1, tmp_path, timeout=90)[0]
    assert rep["failfast_error"] is not None
    assert "unreachable" in rep["failfast_error"]
    assert rep["elapsed_s"] < 60.0


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_pod_byte_identity_2proc(pod_dir, baseline):
    reps = _run_pod("train", 2, pod_dir)
    assert reps[0]["trees"] == reps[1]["trees"], \
        "pod hosts emitted different trees"
    assert reps[0]["trees"] == baseline["trees"], \
        "2-process pod diverged from single-process single_controller"
    # mapper broadcast: every host adopted the identical layout
    digests = {baseline["layout_digest"]} | \
        {r["layout_digest"] for r in reps}
    assert len(digests) == 1
    # zero new traces on the warm same-shape window, per host
    assert [r["warm_new_compiles"] for r in reps] == [0, 0]
    assert reps[0]["hosts_gauge"] == 2
    assert (reps[0]["ingest_rows_per_s"] or 0) > 0


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_pod_byte_identity_4proc(pod_dir, baseline):
    reps = _run_pod("train", 4, pod_dir)
    trees = {r["trees"] for r in reps}
    assert len(trees) == 1
    assert trees.pop() == baseline["trees"]
    assert [r["warm_new_compiles"] for r in reps] == [0, 0, 0, 0]


@pytest.mark.slow
@pytest.mark.timeout(900)
def test_pod_bagging_feature_fraction_host_invariant(pod_dir):
    sub = pod_dir / "bagff1"
    sub.mkdir()
    os.link(mhw.data_path(str(pod_dir)), mhw.data_path(str(sub)))
    one = _run_pod("bagff", 1, sub)[0]
    two = _run_pod("bagff", 2, pod_dir)
    assert two[0]["trees"] == two[1]["trees"] == one["trees"], \
        "bagging/feature_fraction draws depend on the host count"


@pytest.mark.slow
@pytest.mark.timeout(1200)
def test_pod_kill_one_host_resume_byte_identical(pod_dir, baseline):
    kill_dir = pod_dir / "kill"
    kill_dir.mkdir()
    os.link(mhw.data_path(str(pod_dir)), mhw.data_path(str(kill_dir)))
    # phase A: last rank dies before acking the iter-4 snapshot
    reps = _run_pod("killA", 2, kill_dir,
                    expected_exits={0: 0, 1: mhw.KILLED_EXIT})
    r0 = reps[0]
    assert r0["commit2"] is True, "iter-2 snapshot never committed"
    assert r0["commit4"] is False, \
        "iter-4 snapshot committed without the victim's ack"
    assert "no ack from host(s) [1]" in r0["ack_timeout_error"]
    # phase B: fresh pod refuses the uncommitted snapshot, resumes the
    # committed one, finishes byte-identical to the uninterrupted run
    reps = _run_pod("killB", 2, kill_dir)
    for rep in reps:
        assert rep["uncommitted_refused"] is True
        assert rep["commit2"] is True and rep["commit4"] is False
        assert rep["trees"] == baseline["trees"], \
            "resume after host death diverged from the straight run"
