"""lightgbm_tpu: a TPU-native gradient-boosted decision tree framework.

Brand-new implementation of the LightGBM v2.2.2 capability surface
(histogram-based leaf-wise GBDT, GOSS/DART/RF, EFB, categorical optimal
splits, monotone constraints, full objective/metric set, feature/data/voting
parallel distributed training) designed for TPU: the binned feature matrix is
HBM-resident, histogram construction and split scanning run as XLA
kernels, and distributed modes use jax.lax collectives over a device mesh.
"""

from .config import Config
from .utils.log import LightGBMError, register_log_callback, set_verbosity

__version__ = "0.1.0"

# public API filled in as layers land; basic/engine/sklearn imported lazily to
# keep `import lightgbm_tpu` light before jax initialisation is needed
__all__ = [
    "Config", "LightGBMError", "register_log_callback", "set_verbosity",
    "Dataset", "Booster", "train", "cv",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
    "PredictionServer",
]


def __getattr__(name):
    if name in ("Dataset", "Booster"):
        from . import basic
        return getattr(basic, name)
    if name == "PredictionServer":
        from .serve import PredictionServer
        return PredictionServer
    if name in ("train", "cv"):
        from . import engine
        return getattr(engine, name)
    if name in ("LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"):
        from . import sklearn
        return getattr(sklearn, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute {name}")
