"""The growth regimes whose trees are pinned byte for byte.

``tests/data/growth_digests.json`` holds, for every regime below, the
SHA-256 of the model text above its ``parameters:`` block (header and
trees) and of the training-score bytes, recorded on the commit before
PR 30 deleted the ``two_pass`` wave layout and the Pallas histogram
kernel (both layouts gave these bytes there).  ``tests/test_fused_find.py``
holds the one wave that is left to them.

Recording (XLA:CPU, the tier-1 environment)::

    python tests/growth_regimes.py --record tests/data/growth_digests.json

``--modes a,b`` trains every regime once per value of
``find_best_fusion`` and refuses to write unless all agree: how the file
was made on the parent, where that parameter still existed.
"""

import hashlib
import json
import os
import sys

import numpy as np

DIGESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "growth_digests.json")

BASE = {"objective": "binary", "verbosity": -1, "device_growth": "on",
        "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5, "seed": 7}
SAMPLED = {"grad_quant_bits": 8, "feature_fraction": 0.8,
           "bagging_freq": 5, "bagging_fraction": 0.8}


def data(rows=3000, cols=10, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    return x, y


def _striped_data():
    rng = np.random.default_rng(8)
    x = rng.standard_normal((6000, 6)).astype(np.float32)
    y = (x[:, 0] + 2 * (x[:, 1] > 0.3) > 0.5).astype(np.float32)
    return x, y


def train(extra, x, y, n_iters=5, chunk=0):
    from conftest import train_device_booster
    return train_device_booster({**BASE, **extra}, x, y, n_iters,
                                chunk=chunk)


def _striped(extra):
    # the six-column count layout of >= 2^24 rows, forced small
    import lightgbm_tpu.ops.grow as growmod
    old = growmod.COUNT_SPLIT_ROWS
    try:
        growmod.COUNT_SPLIT_ROWS = 5000
        bst = train({"grad_quant_bits": 8, **extra}, *_striped_data(),
                     n_iters=4)
        assert bst._grower.hist_cols == 6
        return bst
    finally:
        growmod.COUNT_SPLIT_ROWS = old


# regime -> trainer(extra params) -> trained GBDT; two regimes that must
# give the same bytes share a digest key (the part before the colon)
REGIMES = {
    "bf16": lambda e: train(e, *data()),
    "bf16_scan:per_iter": lambda e: train(e, *data(seed=4), n_iters=6),
    "bf16_scan:fused": lambda e: train(e, *data(seed=4), n_iters=6,
                                        chunk=3),
    "int8": lambda e: train({"grad_quant_bits": 8, **e}, *data(seed=5)),
    "striped": _striped,
    "sampled_scan:per_iter": lambda e: train({**SAMPLED, **e},
                                              *data(seed=9), n_iters=8),
    "sampled_scan:fused": lambda e: train({**SAMPLED, **e},
                                           *data(seed=9), n_iters=8,
                                           chunk=4),
}
# the "shard" key is recorded by tests/_shard_worker.py's fused_find
# scenario in a process of its own (a forced 4-device host mesh)


def digest_of(bst) -> dict:
    """What a regime is held to: the model text above ``parameters:``
    and the training scores, each as a SHA-256."""
    text = bst.model_to_string().split("\nparameters:", 1)[0]
    score = np.ascontiguousarray(np.asarray(bst.train_score))
    return {"model": hashlib.sha256(text.encode()).hexdigest(),
            "score": hashlib.sha256(score.tobytes()).hexdigest()}


def key_of(regime: str) -> str:
    return regime.split(":", 1)[0]


def load() -> dict:
    with open(DIGESTS) as fh:
        return json.load(fh)


def _shard_digests(extra) -> dict:
    import subprocess
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_shard_worker.py")
    proc = subprocess.run([sys.executable, worker, "fused_find", ".",
                           json.dumps(extra)], env=env,
                          capture_output=True, text=True, check=True)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return {"shard:1": out["single"], "shard:4": out["sharded"]}


def record(path: str, modes) -> dict:
    found = {}
    for mode in modes:
        extra = {"find_best_fusion": mode} if mode else {}
        got = {name: digest_of(fn(extra)) for name, fn in REGIMES.items()}
        got.update(_shard_digests(extra))
        for name, d in got.items():
            prev = found.setdefault(key_of(name), d)
            if prev != d:
                raise SystemExit(f"{name} under {mode or 'default'}: "
                                 f"{d} != {prev}")
            print(f"{mode or 'default':9s} {name:24s} {d['model'][:16]} "
                  f"{d['score'][:16]}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(found, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return found


if __name__ == "__main__":
    args = sys.argv[1:]
    if len(args) < 2 or args[0] != "--record":
        raise SystemExit(__doc__)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    modes = args[3].split(",") if len(args) > 3 and args[2] == "--modes" \
        else [""]
    record(args[1], modes)
