"""TPU kernel library (Pallas) for the framework's hot ops.

The serving/training compute path is XLA-compiled JAX; this package holds
the hand-written Pallas TPU kernels for the operations where blockwise
control over VMEM residency beats what the compiler fuses on its own —
starting with causal flash attention (:mod:`client_tpu.ops.flash_attention`),
the transformer family's dominant op; :mod:`client_tpu.ops.paged_decode`
reads a decode tick's paged K/V blocks where they lie (a window layer's
from the lane's first visible position); :mod:`client_tpu.ops.grouped_matmul`
is an expert layer's product of rows sorted by expert, each group against
its own expert's matrix; :mod:`client_tpu.ops.latent_prefill` attends a
prefill chunk over paged latent rows, a group's keys and values rebuilt in
VMEM.
"""

from client_tpu.ops.flash_attention import flash_attention  # noqa: F401
