"""synapseml_tpu — a TPU-native framework with the capabilities of SynapseML.

Re-designed from scratch for JAX/XLA/Pallas on TPU: DataFrame-level
``.fit()/.transform()`` pipelines whose execution backend is jit-compiled XLA
over a ``jax.sharding.Mesh`` — histogram GBDT with Pallas kernels + ICI
``psum`` allreduce instead of LightGBM's socket ring, pjit data/tensor
parallel deep learning instead of Horovod/NCCL, ONNX→XLA lowering instead of
ONNX Runtime sessions, and partition→chip placement instead of Spark
executor→GPU placement.
"""

__version__ = "0.1.0"

import os as _os

# One compile cache, placed from outside.  Where JAX_COMPILATION_CACHE_DIR
# is set the persistent XLA compilation cache lives there; where it is not,
# it is ``<checkout>/.jax_cache`` (resolved from this package's own path,
# so every process of one checkout — tests, the benchmark, gang workers,
# which all inherit the environment — shares one directory at a fixed
# path).  This is the only place the package decides the directory; the
# variable is exported so children resolve the same one.
_cache_dir = _os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), ".jax_cache"))

import jax as _jax  # noqa: E402

# jax snapshots the environment when it is first imported: a process that
# imported jax before this package still holds the old value
if _jax.config.jax_compilation_cache_dir != _cache_dir:
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)

from . import resilience, telemetry
from .core.dataset import Dataset
from .core.params import Params
from .core.pipeline import (Estimator, Evaluator, Model, Pipeline,
                            PipelineModel, PipelineStage, Transformer)
from .resilience import (CircuitBreaker, Deadline, RetryPolicy, get_faults)
from .telemetry import get_registry, span

__all__ = [
    "Dataset", "Params", "Estimator", "Evaluator", "Model", "Pipeline",
    "PipelineModel", "PipelineStage", "Transformer", "__version__",
    "telemetry", "get_registry", "span",
    "resilience", "RetryPolicy", "Deadline", "CircuitBreaker", "get_faults",
]
