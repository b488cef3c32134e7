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

REFERENCE_EXAMPLES = "/root/reference/examples"


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


def load_svmlight(path, n_features=None):
    """Tiny LibSVM reader for the lambdarank fixtures."""
    labels, rows, cols, vals = [], [], [], []
    with open(path) as fh:
        for i, line in enumerate(fh):
            parts = line.strip().split()
            labels.append(float(parts[0]))
            for tok in parts[1:]:
                c, v = tok.split(":")
                rows.append(i)
                cols.append(int(c))
                vals.append(float(v))
    n = len(labels)
    nf = (max(cols) + 1) if n_features is None else n_features
    x = np.zeros((n, nf), np.float64)
    x[rows, cols] = vals
    return x, np.asarray(labels, np.float64)


@pytest.fixture(scope="session")
def regression_data():
    d = np.loadtxt(f"{REFERENCE_EXAMPLES}/regression/regression.train")
    dt = np.loadtxt(f"{REFERENCE_EXAMPLES}/regression/regression.test")
    return d[:, 1:], d[:, 0], dt[:, 1:], dt[:, 0]


@pytest.fixture(scope="session")
def binary_data():
    d = np.loadtxt(f"{REFERENCE_EXAMPLES}/binary_classification/binary.train")
    dt = np.loadtxt(f"{REFERENCE_EXAMPLES}/binary_classification/binary.test")
    return d[:, 1:], d[:, 0], dt[:, 1:], dt[:, 0]


@pytest.fixture(scope="session")
def rank_data():
    base = f"{REFERENCE_EXAMPLES}/lambdarank"
    x, y = load_svmlight(f"{base}/rank.train")
    xt, yt = load_svmlight(f"{base}/rank.test", n_features=x.shape[1])
    q = np.loadtxt(f"{base}/rank.train.query").astype(np.int64)
    qt = np.loadtxt(f"{base}/rank.test.query").astype(np.int64)
    return x, y, q, xt, yt, qt
