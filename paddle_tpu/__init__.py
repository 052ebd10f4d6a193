"""paddle_tpu: a TPU-native deep-learning framework.

A ground-up JAX/XLA/pjit/Pallas re-design with the capability surface of
classic (pre-Fluid) PaddlePaddle: the layer/projection model zoo, the Python
config DSL and v2 trainer API, padding-free variable-length sequence training
with ``recurrent_group`` and beam-search generation, the optimizer /
regularizer / evaluator suites, data providers, checkpoint/resume, and
SPMD distributed training over TPU meshes.

Reference capability map: see SURVEY.md at the repo root.
"""

from paddle_tpu.version import __version__

from paddle_tpu.core import dtypes
from paddle_tpu.core.sequence import SequenceBatch

from paddle_tpu import ops
from paddle_tpu import layers
from paddle_tpu import optim
from paddle_tpu import data
from paddle_tpu import parallel
from paddle_tpu import evaluators
from paddle_tpu import models
from paddle_tpu import trainer

# v2-style convenience namespace:  paddle_tpu.init(), .layer, .optimizer ...
from paddle_tpu.trainer.api import init, infer
from paddle_tpu.data import reader

layer = layers  # paddle.v2.layer equivalent
optimizer = optim  # paddle.v2.optimizer equivalent

__all__ = [
    "__version__",
    "dtypes",
    "SequenceBatch",
    "ops",
    "layers",
    "layer",
    "optim",
    "optimizer",
    "data",
    "reader",
    "parallel",
    "evaluators",
    "models",
    "trainer",
    "init",
    "infer",
]
