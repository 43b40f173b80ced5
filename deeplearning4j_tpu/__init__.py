"""deeplearning4j_tpu — a TPU-native deep learning framework.

A from-scratch rebuild of the capability surface of deeplearning4j
(reference: qdh0520/deeplearning4j, a fork of eclipse/deeplearning4j) designed
TPU-first: whole-graph XLA compilation instead of per-op JNI dispatch, SPMD
sharding over a jax device mesh instead of trainer-thread topologies, and a
functional jax core under a familiar stateful API shell.

Layer map (≈ SURVEY.md §1):
  ndarray/    INDArray + Nd4j factory analog           (ref: nd4j-api linalg)
  ops/        op registry + coverage ledger            (ref: libnd4j declarable ops)
  autodiff/   SameDiff analog — symbolic DAG → one jitted XLA module
  nn/         layer configs, MultiLayerNetwork, ComputationGraph (ref: dl4j-nn)
  data/       datasets, iterators, readers, normalizers (ref: datavec, dl4j-data)
  parallel/   SPMD mesh wrapper, ParallelWrapper analog (ref: dl4j-scaleout)
  models/     model zoo                                 (ref: dl4j-zoo)
  imports/    TF frozen-GraphDef → SameDiff, Keras h5   (ref: dl4j-modelimport,
              → MultiLayerNetwork                        samediff-import)
  eval/       Evaluation / ROC / RegressionEvaluation   (ref: nd4j evaluation)
  optimize/   training listeners, early stopping        (ref: dl4j optimize,
                                                         dl4j earlystopping)
  nlp/        Word2Vec / ParagraphVectors / vocab / serde (ref: dl4j-nlp)
  rl/         DQN / replay / policies / MDP envs        (ref: rl4j)
  ui/         StatsListener -> TensorBoard events       (ref: dl4j-ui)
  native/     C++ host-ETL hot loops via ctypes         (ref: libnd4j CPU helpers)
"""

import jax as _jax

# The dtype zoo advertises DOUBLE/INT64/UINT64 as first-class (reference
# DataType set); without x64 jax silently downcasts them to 32-bit. Enable it
# process-wide at import. Defaults stay 32-bit — wide types are used only when
# requested (on TPU, f64 is slow/emulated; the reference's fp64 paths are
# gradient checks, which run on CPU).
_jax.config.update("jax_enable_x64", True)
# The step's named scopes are HLO metadata, and ``OpProfiler.scope_times``
# reads a trace by them. JAX's persistent cache keys an executable WITHOUT
# its metadata by default, so a step cached by an older tree or another
# checkout would be loaded with that tree's names (or none) and the profile
# would be booked to them. Key the cache on the metadata too: an entry then
# serves the source positions it was compiled from (as the steps that hold
# a Pallas kernel always did).
_jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

from .common.dtypes import DataType
from .common.environment import Environment
from .ndarray.ndarray import NDArray
from .ndarray import factory
from .ndarray.rng import get_random, set_default_seed

__version__ = "0.1.0"

__all__ = [
    "DataType",
    "Environment",
    "NDArray",
    "factory",
    "get_random",
    "set_default_seed",
]
