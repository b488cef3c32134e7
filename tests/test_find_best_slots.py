"""The find-best of a bundled dataset (``FeatureMeta`` with width classes,
``ops/split.py::scan_features``) against the feature-space scan, which is
its oracle: the same winner, threshold, default direction, gain and left
sums to the bit, on histograms added up from seeded rows over bundled
layouts; and its compiled temporaries, which have to follow the slots the
groups hold and not features x 256."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.split import (F_DEFAULT_LEFT, F_FEATURE, F_GAIN,
                                    F_THRESHOLD, FeatureMeta, SplitHyper,
                                    find_best_split_stack)

STRIDE = 256
NONE, ZERO, NAN = 0, 1, 2


def layout(groups):
    """A dataset's per-feature lookups from ``groups``: lists of
    ``(num_bin, default_bin, missing)``, laid out as
    ``data/dataset.py::FeatureGroupInfo`` does (slot 0 for the rows at
    every default, then a run a feature, the default's slot dropped where
    it is bin 0)."""
    nb, db, miss, grp, off = [], [], [], [], []
    for g, feats in enumerate(groups):
        at = 1
        for n, d, m in feats:
            nb.append(n), db.append(d), miss.append(m)
            grp.append(g), off.append(at)
            at += n - (1 if d == 0 else 0)
        assert at <= STRIDE, "a group holds at most 256 slots"
    nf = len(nb)
    i32 = lambda a: np.asarray(a, np.int32)
    return SimpleNamespace(
        f_num_bin=i32(nb), f_default_bin=i32(db), f_missing_type=i32(miss),
        f_group=i32(grp), f_offset=i32(off),
        f_is_categorical=np.zeros(nf, np.int32),
        monotone_constraints=np.zeros(nf, np.int32),
        feature_penalty=np.ones(nf, np.float64),
        num_groups=len(groups), num_features=nf)


def histograms(ds, rng, leaves, rows, copies=(), integer=False):
    """``(leaves, G * 256, 3)`` histograms and their ``(leaves, 3)``
    totals from ``rows`` seeded rows a leaf: in every group a row sits at
    one feature's non-default bin or at every default (slot 0).
    ``copies``: ``(f, g)`` pairs, feature ``g`` (alone in its group, the
    same bins) made to record what ``f`` records, so both score the same
    gain to the bit.  ``integer``: int32 sums of int8-sized steps, for
    the quantized scan."""
    ng, nf = ds.num_groups, ds.num_features
    by_group = [np.flatnonzero(ds.f_group == g) for g in range(ng)]
    hists = np.zeros((leaves, ng * STRIDE, 3), np.float64)
    totals = np.zeros((leaves, 3), np.float64)
    for leaf in range(leaves):
        if integer:
            gh = np.stack([rng.integers(-127, 128, rows),
                           rng.integers(0, 128, rows),
                           np.ones(rows, np.int64)], 1).astype(np.float64)
        else:
            g = rng.standard_normal(rows).astype(np.float32)
            h = rng.uniform(0.05, 0.25, rows).astype(np.float32)
            gh = np.stack([g, h, np.ones(rows, np.float32)], 1)
        totals[leaf] = gh.astype(np.float32).sum(0, dtype=np.float32)
        bin_of = np.zeros((rows, nf), np.int64)      # kept for the copies
        for g_id, feats in enumerate(by_group):
            which = rng.integers(-1, len(feats), rows)   # -1: all default
            slot = np.zeros(rows, np.int64)
            for k, f in enumerate(feats):
                n, d = int(ds.f_num_bin[f]), int(ds.f_default_bin[f])
                src = dict(copies).get(int(f))
                if src is not None:
                    b = bin_of[:, src]
                    here = b != int(ds.f_default_bin[src])
                else:
                    b = rng.integers(0, n, rows)
                    here = (which == k) & (b != d)
                bin_of[:, f] = np.where(here, b, d)
                slot = np.where(
                    here, int(ds.f_offset[f]) + b - (1 if d == 0 else 0),
                    slot)
            np.add.at(hists[leaf], g_id * STRIDE + slot,
                      gh.astype(np.float32))
    if integer:
        return (jnp.asarray(hists, jnp.int32), jnp.asarray(totals, jnp.int32))
    # float32 sums in row order, as an accumulator would leave them
    return (jnp.asarray(hists.astype(np.float32)),
            jnp.asarray(totals.astype(np.float32)))


ONE_HOT = (2, 0, NONE)
LAYOUTS = {
    # 3 bundles of two-bin columns beside two dense features
    "two_bin": [[ONE_HOT] * 40, [ONE_HOT] * 31, [(200, 0, NONE)],
                [ONE_HOT] * 7, [(37, 0, NONE)]],
    # widths of every class in one bundle, default bins off zero
    "mixed_widths": [[(5, 2, NONE), (2, 0, NONE), (17, 9, NONE), (3, 1, NONE),
                      (64, 0, NONE), (9, 0, NONE)],
                     [(130, 77, NONE)], [(2, 1, NONE), (4, 3, NONE)]],
    # NaN and zero-as-missing features inside a bundle
    "missing": [[(6, 0, NAN), (2, 0, NONE), (12, 4, ZERO), (2, 0, NAN),
                 (9, 0, ZERO), (3, 0, ZERO)],
                [(33, 5, NAN)], [(8, 0, NAN), (8, 3, NAN), (20, 0, ZERO)]],
    # exact ties: features 3 and 4 record what 0 and 1 record
    "ties": [[ONE_HOT, (6, 0, NONE), ONE_HOT], [ONE_HOT], [(6, 0, NONE)],
             [ONE_HOT] * 5],
}
COPIES = {"ties": ((3, 0), (4, 1))}


def both_scans(ds, params, hists, totals, mask, scales=None):
    cfg = Config(params)
    hyper = SplitHyper.from_config(cfg)
    cons = jnp.asarray([-jnp.inf, jnp.inf], jnp.float32)
    out = []
    for by_slots in (False, True):
        meta = FeatureMeta.from_dataset(ds, slot_stride=STRIDE,
                                        by_slots=by_slots)
        assert (meta.slot_feature is not None) == by_slots
        fn = jax.jit(lambda h, t, m, meta=meta: find_best_split_stack(
            h, t, cons, m, meta, hyper, False, scales=scales))
        out.append(jax.tree_util.tree_map(np.asarray,
                                          fn(hists, totals, mask)))
    return out


KNOBS = ("plain", "masked", "hessian_binds")
CASES = [(name, knobs, quant) for name in sorted(LAYOUTS)
         for knobs in KNOBS for quant in (False, True)]


def case_id(name, knobs, quant):
    return f"{name}-{knobs}-{'int32' if quant else 'float32'}"


def run_case(name, knobs, quant):
    """Both scans over one case's histograms: ``(feature-space, by
    slots, feature mask)``, each scan ``(packed (6, 13), cat member,
    int32 left sums or None)``."""
    ds = layout(LAYOUTS[name])
    rng = np.random.default_rng([sorted(LAYOUTS).index(name), 7])
    hists, totals = histograms(ds, rng, leaves=6, rows=1500,
                               copies=COPIES.get(name, ()), integer=quant)
    params = {"min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3,
              "lambda_l2": 0.5}
    mask = np.ones(ds.num_features, bool)
    if knobs == "masked":
        mask[::3] = False
    if knobs == "hessian_binds":
        # a sixth of a leaf's hessian on each side: most one-hot splits
        # fall under it, the dense features' middle thresholds do not
        params["min_sum_hessian_in_leaf"] = 1500 * (0.64 if quant
                                                    else 0.15) / 6
    scales = jnp.asarray([0.02, 0.01], jnp.float32) if quant else None
    by_feature, by_slots = both_scans(ds, params, hists, totals,
                                      jnp.asarray(mask), scales)
    return by_feature, by_slots, mask


# Off the TPU the last bit of a GAIN is the compiler's: XLA:CPU lets LLVM
# contract a multiply and an add into one fused multiply-add wherever the
# instruction selection finds it profitable, which depends on the loop a
# candidate lands in, so one formula over the same sums reads an ulp apart
# in two programs (either scan against plain IEEE arithmetic too).  The
# statement "the same to the bit" is therefore made where there is no FMA
# to contract into (``--xla_cpu_max_isa=SSE4_2``), in a process of its
# own; under the suite's own flags everything but that last bit is held.
STRICT_FLAGS = ("--xla_force_host_platform_device_count=8 "
                "--xla_cpu_max_isa=SSE4_2")


def strict_env():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=STRICT_FLAGS,
                PYTHONPATH=root + os.pathsep + os.environ.get(
                    "PYTHONPATH", ""),
                JAX_ENABLE_COMPILATION_CACHE="false",
                LGBM_TPU_CHUNK=os.environ.get("LGBM_TPU_CHUNK", "8192"))


@pytest.fixture(scope="module")
def strict_results():
    """Every case run with plain IEEE arithmetic, in one child process."""
    import json
    import subprocess
    import sys
    out = subprocess.run([sys.executable, __file__], env=strict_env(),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("name,knobs,quant", CASES,
                         ids=[case_id(*c) for c in CASES])
def test_slot_scan_picks_what_the_feature_scan_picks(strict_results, name,
                                                     knobs, quant):
    got = strict_results[case_id(name, knobs, quant)]
    pk_f = np.asarray(got["feature"], np.uint32).view(np.float32)
    pk_s = np.asarray(got["slots"], np.uint32).view(np.float32)
    assert (pk_f[:, F_GAIN] > -1e29).any(), "no leaf found a split"
    # winner, threshold, default direction, gain, sums and outputs: the
    # whole packed record, bit for bit
    np.testing.assert_array_equal(pk_f.view(np.uint32), pk_s.view(np.uint32))
    if quant:
        assert got["left_int_feature"] == got["left_int_slots"]
    if knobs == "masked":
        mask = np.asarray(got["mask"], bool)
        won = pk_s[pk_s[:, F_GAIN] > -1e29, F_FEATURE].astype(int)
        assert mask[won].all()
    if name == "ties":
        # a copy never beats what it copies: the lower index wins
        assert not np.isin(pk_s[:, F_FEATURE].astype(int), [3, 4]).any()
    if knobs == "hessian_binds":
        plain = strict_results[case_id(name, "plain", quant)]
        assert plain["slots"] != got["slots"], "the bound did not bind"
    assert set(np.unique(pk_s[:, F_DEFAULT_LEFT])) <= {0.0, 1.0}
    assert (pk_s[:, F_THRESHOLD] >= 0).all()


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_all_but_a_gains_last_bit_under_the_suites_flags(name):
    """In this process, FMA contraction and all: every feature's
    threshold, default direction and left sums to the bit, its gain to
    an ulp (see STRICT_FLAGS)."""
    (pk_f, _, _), (pk_s, _, _), _ = run_case(name, "plain", False)
    rest = [c for c in range(13) if c != F_GAIN]
    same = pk_f[:, F_FEATURE] == pk_s[:, F_FEATURE]
    assert same.mean() >= 0.5
    np.testing.assert_array_equal(pk_f[same][:, rest].view(np.uint32),
                                  pk_s[same][:, rest].view(np.uint32))
    np.testing.assert_allclose(pk_f[:, F_GAIN], pk_s[:, F_GAIN], rtol=3e-7)


def test_classes_are_a_static_property_of_the_dataset():
    """One feature a group, or a categorical feature: no classes, so the
    accepted cells' programs are the feature-space scan's as before."""
    plain = layout([[(255, 0, NONE)], [(17, 3, NAN)], [ONE_HOT]])
    assert not FeatureMeta.from_dataset(plain, by_slots=True).classes
    cat = layout(LAYOUTS["two_bin"])
    cat.f_is_categorical[0] = 1
    assert not FeatureMeta.from_dataset(cat, by_slots=True).classes
    meta = FeatureMeta.from_dataset(layout(LAYOUTS["mixed_widths"]),
                                    by_slots=True)
    widths = [int(c.slot_idx.shape[1]) for c in meta.classes]
    assert widths == sorted(set(widths)) == [2, 4, 8, 16, 32, 64, 256]
    # every feature once: the one plain two-bin feature by its slot (slot
    # 5 of group 0, after a run of 5 bins), the others in the classes
    owner = np.asarray(meta.slot_feature)
    assert owner.shape == (3 * STRIDE,) and owner[1 + 5] == 1
    assert (owner >= 0).sum() == 1
    assert sorted(np.concatenate(
        [np.asarray(c.global_id) for c in meta.classes])) == [
            0, 2, 3, 4, 5, 6, 7, 8]
    assert meta.scan_lanes == 3 * STRIDE + 2 + 4 * 2 + 8 + 16 + 32 + 64 + 256
    assert FeatureMeta.from_dataset(plain).scan_lanes == 3 * 256


def test_temporaries_follow_the_slots():
    """ISSUE 34's probe shape: 4,080 two-bin features in 16 bundles, 96
    leaves a stack.  The feature-space scan compiles to 4,592 MiB of
    temporaries there (the parent, CPU backend); by slots it has to stay
    under the 75 MiB that 67 one-feature groups cost."""
    ds = layout([[ONE_HOT] * 255] * 16)
    assert ds.num_features == 4080
    meta = FeatureMeta.from_dataset(ds, slot_stride=STRIDE, by_slots=True)
    assert meta.scan_lanes == 16 * STRIDE and not meta.classes
    hyper = SplitHyper.from_config(Config({"min_sum_hessian_in_leaf": 100}))
    cons = jnp.asarray([-jnp.inf, jnp.inf], jnp.float32)
    fn = jax.jit(lambda h, t, m: find_best_split_stack(
        h, t, cons, m, meta, hyper, False))
    shapes = (jax.ShapeDtypeStruct((96, 16 * STRIDE, 3), jnp.float32),
              jax.ShapeDtypeStruct((96, 3), jnp.float32),
              jax.ShapeDtypeStruct((4080,), bool))
    temp = fn.lower(*shapes).compile().memory_analysis().temp_size_in_bytes
    assert temp <= 75 * 2**20, f"{temp / 2**20:.1f} MiB of temporaries"


if __name__ == "__main__":
    import json
    results = {}
    for case in CASES:
        (pk_f, _, li_f), (pk_s, _, li_s), mask = run_case(*case)
        results[case_id(*case)] = {
            "feature": pk_f.view(np.uint32).tolist(),
            "slots": pk_s.view(np.uint32).tolist(),
            "left_int_feature": None if li_f is None else li_f.tolist(),
            "left_int_slots": None if li_s is None else li_s.tolist(),
            "mask": mask.tolist()}
    print(json.dumps(results))
