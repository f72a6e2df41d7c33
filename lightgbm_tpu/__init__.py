"""LightGBM-TPU: a TPU-native gradient boosting framework.

A from-scratch reimplementation of the capabilities of LightGBM
(reference: /root/reference, Dec-2016 snapshot) designed TPU-first:

- binned training data lives on device as dense integer arrays
  (features-major), never as floats;
- histogram construction is a batched one-hot contraction on the MXU;
- split finding is a vectorized cumulative scan over (feature, bin);
- the whole tree build is one jitted program (`lax.fori_loop` over
  leaf-wise splits, static shapes throughout);
- distributed training (data/feature/voting parallel) uses
  `jax.lax` collectives (psum / pmax / all_gather) over a
  `jax.sharding.Mesh` instead of sockets/MPI.

Public API mirrors the reference python-package
(`python-package/lightgbm/__init__.py:11-25`).

The package's own import is the process tracer's span `import`, and the
scikit-learn wrappers' its child `import/sklearn` (telemetry/trace.py
PROCESS_TRACER): jax and the backend are not in it when the embedder
imported them first.
"""

import time

_IMPORT_T0 = time.perf_counter()

from .basic import Dataset, Booster, LightGBMError  # noqa: E402
from .engine import train, cv  # noqa: E402
from .callback import (  # noqa: E402
    print_evaluation,
    record_evaluation,
    reset_parameter,
    early_stopping,
    EarlyStopException,
)
from .telemetry.trace import PROCESS_TRACER  # noqa: E402

_sklearn_t0 = time.perf_counter()
try:
    from .sklearn import LGBMModel, LGBMRegressor, LGBMClassifier, LGBMRanker
    SKLEARN_INSTALLED = True
except ImportError:  # pragma: no cover - sklearn is expected in this image
    SKLEARN_INSTALLED = False
_import_t1 = time.perf_counter()
PROCESS_TRACER.add("import/sklearn", _import_t1 - _sklearn_t0)
PROCESS_TRACER.add("import", _import_t1 - _IMPORT_T0)

__version__ = "0.1.0"

__all__ = [
    "Dataset", "Booster", "LightGBMError",
    "train", "cv",
    "print_evaluation", "record_evaluation", "reset_parameter",
    "early_stopping", "EarlyStopException",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
]
