"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The reference has no mockable network backend (SURVEY.md §4); here every
distributed mode is exercised deterministically in-process by forcing the CPU
platform with 8 virtual devices.

NOTE: jax may already be imported when this file runs, so env vars alone are
NOT enough; the platform is also overridden through jax.config, which works
until the first backend initialisation.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# device-grower histogram chunk: the wave einsum runs over n_pad =
# ceil(rows, CHUNK) rows, so the production default of 32768 makes every
# small-dataset CPU test pay 32768-row matmuls regardless of its actual
# size — 8192 cuts that ~4x.  Trees are padding-invariant (padded rows
# carry zero weight); only float reduction order shifts, which the
# tolerance-based tests already absorb.
os.environ.setdefault("LGBM_TPU_CHUNK", "8192")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")   # effective even post-import
assert jax.default_backend() == "cpu", "tests must run on the CPU mesh"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"

# persistent compilation cache: the padded-bucket shapes recur across tests,
# so reruns skip nearly all XLA compiles (routed through the library's
# own activation path and resolution rule, so tests exercise what
# production uses: JAX_COMPILATION_CACHE_DIR when set, else the fixed
# <checkout>/.jax_cache; tests that need their OWN cache dir take the
# private_cache_dir fixture)
from lightgbm_tpu import compile_cache  # noqa: E402

compile_cache.configure()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def private_cache_dir(tmp_path, monkeypatch):
    """A compile cache (and stage-plan store) private to one test: the
    environment variable is dropped for the test's duration (it would
    win over any requested dir), and the session-wide directory is
    restored afterwards."""
    prev = compile_cache.cache_dir()
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure(str(tmp_path / "cc"))
    try:
        yield path
    finally:
        # drop the variable again (the test may have set it through the
        # same monkeypatch, which only undoes AFTER this fixture) so the
        # explicit session dir is honoured, not left pointing at tmp
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        compile_cache.configure(prev)


def pytest_configure(config):
    # @pytest.mark.timeout(N) comes from the pytest-timeout plugin (dev
    # extras).  When the plugin is absent the mark must still be KNOWN
    # (no unknown-mark warning) and ENFORCED — the SIGALRM fixture below
    # supplies the enforcement, so the 420 s multiprocess guard exists
    # on bare tier-1 environments too.
    if not config.pluginmanager.hasplugin("timeout"):
        config.addinivalue_line(
            "markers",
            "timeout(seconds): fail the test if it runs longer than "
            "`seconds` (SIGALRM fallback when pytest-timeout is not "
            "installed)")


@pytest.fixture(autouse=True)
def _timeout_guard(request):
    """SIGALRM-based enforcement of @pytest.mark.timeout when the
    pytest-timeout plugin is unavailable (main-thread, POSIX only —
    exactly the tier-1 environment)."""
    marker = request.node.get_closest_marker("timeout")
    if (marker is None
            or request.config.pluginmanager.hasplugin("timeout")):
        yield
        return
    import signal
    import threading
    seconds = int(marker.args[0]) if marker.args else 0
    if seconds <= 0 or threading.current_thread() is not threading.main_thread():
        yield
        return

    def _raise(signum, frame):
        pytest.fail(f"test exceeded the {seconds}s timeout mark",
                    pytrace=False)

    old = signal.signal(signal.SIGALRM, _raise)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def train_device_booster(params, x, y, n_iters, chunk=0, query=None):
    """Construct + train a device-growth booster (shared by the fused
    and quantized parity suites; base params come from the caller)."""
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data.dataset import BinnedDataset

    cfg = Config(dict(params))
    ds = BinnedDataset.construct_from_matrix(x, cfg)
    ds.metadata.set_label(y)
    if query is not None:
        ds.metadata.set_query(query)
    bst = create_boosting(cfg)
    bst.init_train(ds)
    if chunk:
        bst.train_chunked(n_iters, chunk=chunk)
    else:
        for _ in range(n_iters):
            if bst.train_one_iter():
                break
    bst._flush_pending()
    return bst


def assert_models_bit_identical(a, b):
    """Trees, thresholds, leaf values AND final training scores must be
    byte-equal: the fused scan re-draws bagging/feature_fraction masks
    (and int8 quantization noise) on device with the per-iteration
    path's exact seeding, so there is no tolerance to hide behind."""
    assert len(a.models) == len(b.models)
    for i, (ta, tb) in enumerate(zip(a.models, b.models)):
        assert ta.num_leaves == tb.num_leaves, f"tree {i}"
        nl = ta.num_leaves
        np.testing.assert_array_equal(ta.split_feature[:nl - 1],
                                      tb.split_feature[:nl - 1])
        np.testing.assert_array_equal(ta.threshold[:nl - 1],
                                      tb.threshold[:nl - 1])
        np.testing.assert_array_equal(ta.leaf_value[:nl],
                                      tb.leaf_value[:nl])
    np.testing.assert_array_equal(np.asarray(a.train_score),
                                  np.asarray(b.train_score))


# ---------------------------------------------------------------------------
# the three example datasets, made here from fixed seeds at the upstream
# examples' shapes (LightGBM examples/{binary_classification,regression,
# lambdarank}): tier-1 reads nothing outside the checkout.  The
# thresholds of the tests that take them were set on THESE arrays from
# the host serial learner (tree/learner.py), each with its margin in a
# comment beside it.
# ---------------------------------------------------------------------------

def _higgs_like(seed, rows):
    """(rows, 28) float64 features and a latent score: a few strong
    columns, interactions and a long tail of weak ones, like the
    examples' HIGGS sample."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, 28))
    x[:, 20:] = np.abs(x[:, 20:])            # one-sided, like the masses
    f = (0.9 * x[:, 0] - 0.7 * x[:, 1] * (x[:, 2] > 0)
         + 0.6 * np.sin(2.0 * x[:, 3]) + 0.5 * x[:, 4] * x[:, 5]
         + 0.8 * (x[:, 25] - 0.8) + 0.15 * x[:, 6:16].sum(1))
    return x, f, rng


@pytest.fixture(scope="session")
def binary_data():
    """7,000 + 500 rows x 28 columns; labels drawn from the latent score
    through a logistic link, so no model reaches AUC 1."""
    x, f, rng = _higgs_like(20261002, 7500)
    y = (rng.random(7500) < 1.0 / (1.0 + np.exp(-1.2 * f))).astype(
        np.float64)
    return x[:7000], y[:7000], x[7000:], y[7000:]


@pytest.fixture(scope="session")
def regression_data():
    """7,000 + 500 rows x 28 columns; a real-valued target of unit
    variance, a third of it noise."""
    x, f, rng = _higgs_like(20261003, 7500)
    y = f / f.std() * np.sqrt(2.0 / 3.0) \
        + rng.standard_normal(7500) * np.sqrt(1.0 / 3.0)
    return x[:7000], y[:7000], x[7000:], y[7000:]


@pytest.fixture(scope="session")
def rank_data():
    """~3,000 + ~770 rows of 300 sparse columns in 200 + 50 queries of 5
    to 25 documents; relevance 0..4 cut from a latent score that twelve
    of the columns carry."""
    rng = np.random.default_rng(20261004)
    sizes = rng.integers(5, 26, 250)
    n = int(sizes.sum())
    x = rng.random((n, 300)) * (rng.random((n, 300)) < 0.12)
    w = rng.standard_normal(12)
    f = x[:, :12] @ w + 0.35 * rng.standard_normal(n)
    y = np.digitize(f, np.quantile(f, [0.55, 0.8, 0.92, 0.98])).astype(
        np.float64)
    cut = int(sizes[:200].sum())
    return (x[:cut], y[:cut], sizes[:200].astype(np.int64),
            x[cut:], y[cut:], sizes[200:].astype(np.int64))
