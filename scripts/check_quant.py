#!/usr/bin/env python
"""CI quant-parity smoke: int8 Pallas kernel == int8 einsum, byte-exact.

Fast contract check for the quantized histogram path
(``grad_quant_bits=8``), run by ``scripts/check.sh``:

1. kernel level — ``ops/hist_pallas.wave_hist_pallas`` in interpret
   mode must produce int32 histograms BIT-identical to the einsum
   formulation in ``ops/grow.GrowerPrograms._wave_hist`` (integer
   accumulation is associative, so any mismatch is a real layout or
   masking bug, never rounding);
2. training level — two boosters differing only in
   ``hist_kernel=interpret`` vs ``einsum`` must emit byte-identical
   models under the int32 find-best scan, and the routing counters
   must show the Pallas kernel actually served the pallas leg.

Runs on the CPU backend (interpret mode), so tier-1 CI gates the
contract without a chip; ``bench.py --suite quant`` measures the same
pairing for real on the TPU driver.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("LGBM_TPU_CHUNK", "8192")

ROWS = 3000
FEATURES = 8
PARAMS = {
    "objective": "binary", "verbosity": -1, "device_growth": "on",
    "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
    "grad_quant_bits": 8, "seed": 20260804,
}


def _train(extra):
    import numpy as np

    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    rng = np.random.default_rng(11)
    x = rng.standard_normal((ROWS, FEATURES)).astype(np.float32)
    y = (x[:, 0] + np.abs(x[:, 1]) > 0.5).astype(np.float32)
    cfg = Config({**PARAMS, **extra})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    bst.train_chunked(4, chunk=2)
    bst._flush_pending()
    return bst


def _kernel_parity() -> bool:
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset
    from lightgbm_tpu.ops.grow import DeviceGrower

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, FEATURES)).astype(np.float32)
    cfg = Config({**PARAMS, "hist_kernel": "interpret",
                  "grower_cache": False})
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label((x[:, 0] > 0).astype(np.float32))
    grower = DeviceGrower(ds, cfg)
    progs = grower.programs
    n = progs.n_pad
    w, k = progs.wave_width, progs.hist_cols
    leaf = jnp.asarray(rng.integers(0, w, n).astype(np.int32))
    ghk = jnp.asarray(
        rng.integers(-127, 128, (n, k)).astype(np.int8))
    pending = jnp.arange(w, dtype=jnp.int32)
    got = np.asarray(progs._wave_hist(grower.binned, leaf, ghk, pending,
                                      n)[0])
    progs.use_pallas = False
    ref = np.asarray(progs._wave_hist(grower.binned, leaf, ghk, pending,
                                      n)[0])
    if got.dtype != np.int32 or ref.dtype != np.int32:
        print(f"FAIL kernel parity: expected int32 histograms, got "
              f"pallas={got.dtype} einsum={ref.dtype}")
        return False
    if not np.array_equal(got, ref):
        bad = int((got != ref).sum())
        print(f"FAIL kernel parity: {bad} cells differ between the "
              f"int8 pallas kernel (interpret) and the int8 einsum")
        return False
    print(f"kernel parity: int8 pallas == int8 einsum bit-exact "
          f"({got.shape}, w={w}, k={k})")
    return True


def _training_parity() -> bool:
    from lightgbm_tpu import obs

    obs.configure(enabled=True)
    a = _train({"hist_kernel": "einsum"})
    before = obs.registry().snapshot()["counters"]
    b = _train({"hist_kernel": "interpret"})
    after = obs.registry().snapshot()["counters"]
    pallas_hits = after.get("grow.hist.pallas_int8", 0) \
        - before.get("grow.hist.pallas_int8", 0)
    if pallas_hits <= 0:
        print("FAIL training parity: the pallas leg never routed a "
              "dispatch through the pallas_int8 kernel "
              f"(counters: {after})")
        return False
    sa = a.model_to_string().split("parameters:")[0]
    sb = b.model_to_string().split("parameters:")[0]
    if sa != sb:
        print("FAIL training parity: int8 pallas and int8 einsum "
              "boosters produced different models")
        return False
    if not (a._grower.int_scan and b._grower.int_scan):
        print("FAIL training parity: int32 scan inactive at this shape "
              f"({a._grower.int_scan}, {b._grower.int_scan})")
        return False
    print(f"training parity: models byte-identical, int32 scan active, "
          f"{pallas_hits} pallas_int8 dispatches")
    return True


def main() -> int:
    from lightgbm_tpu.utils.log import set_verbosity

    set_verbosity(-1)
    ok = _kernel_parity()
    ok = _training_parity() and ok
    print("quant smoke: " + ("PASS" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
