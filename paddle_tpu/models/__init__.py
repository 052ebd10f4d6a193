"""Model zoo — functional TPU-first implementations of the reference's demo
families (demo/mnist, image_classification, seqToseq, sentiment,
recommendation, benchmark/rnn) plus the Transformer stretch config.

The DSL-based demo scripts (v1-config parity) live in /demo; these modules
are the fast path used by benchmark/ and __graft_entry__.py.
"""

from paddle_tpu.models import alexnet
from paddle_tpu.models import googlenet
from paddle_tpu.models import lenet
from paddle_tpu.models import resnet
from paddle_tpu.models import smallnet
from paddle_tpu.models import text_lstm
from paddle_tpu.models import seq2seq
from paddle_tpu.models import transformer
from paddle_tpu.models import recommendation

__all__ = ["alexnet", "googlenet", "lenet", "resnet", "smallnet",
           "text_lstm", "seq2seq", "transformer", "recommendation"]
