"""Pallas TPU kernels for the hot ops.

The reference keeps its hot paths in hand-written CUDA
(cuda/src/hl_cuda_lstm.cu fused LSTM, hl_top_k.cu, hl_cuda_matrix.cu); the
TPU-native equivalents are Pallas kernels where XLA's own fusion isn't
already optimal:

  flash_attention — blocked softmax(QK^T)V with O(T) memory (fwd + bwd
                    kernels, custom_vjp), the MXU/HBM-friendly formulation
                    of attention for the transformer/NMT model families.

  decode_attention — fused slab/paged decode attention for the serving
                    hot path (one KV read per step, block table walked
                    via scalar prefetch; gated by the trace-time
                    `pallas_decode` flag — see that module's docstring
                    and docs/perf.md "Fused decode kernels").

Kernels run on TPU; on CPU they fall back to interpret mode (tests) or the
XLA reference implementation (callers check `use_pallas()`).
"""

import jax

from paddle_tpu.ops.pallas.flash_attention import flash_attention


def use_pallas():
    """True when the default backend compiles Pallas natively (TPU).  A
    backend that fails to initialise raises here — it is not a CPU."""
    return jax.default_backend() == "tpu"


__all__ = ["flash_attention", "use_pallas"]
