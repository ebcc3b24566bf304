"""lightgbm_tpu — a TPU-native gradient boosting framework.

A ground-up rebuild of LightGBM v2.3.2's capabilities with the compute plane
designed for TPU: an HBM-resident binned feature matrix, fixed-shape
leaf-wise tree growth under ``jit``, histogram construction as one-hot MXU
matmuls, and distributed modes expressed as ``jax.lax`` collectives over a
``jax.sharding.Mesh``.

The public API mirrors the reference Python package
(reference: python-package/lightgbm/__init__.py).
"""

from .basic import Booster, Dataset
from .config import Config
from .engine import cv, train
from . import ingest
from .utils.log import LightGBMError
from .callback import early_stopping, print_evaluation, record_evaluation, reset_parameter

try:  # sklearn wrappers are optional at import time
    from .sklearn import LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor
    _SKLEARN = ["LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker"]
except ImportError:  # pragma: no cover
    _SKLEARN = []

try:
    from .plotting import create_tree_digraph, plot_importance, plot_metric, plot_split_value_histogram, plot_tree
    _PLOT = ["plot_importance", "plot_metric", "plot_tree", "create_tree_digraph",
             "plot_split_value_histogram"]
except ImportError:  # pragma: no cover
    _PLOT = []

__version__ = "0.1.0"

__all__ = ["Dataset", "Booster", "Config", "train", "cv", "ingest",
           "LightGBMError",
           "early_stopping", "print_evaluation", "record_evaluation",
           "reset_parameter"] + _SKLEARN + _PLOT
