"""Device compute ops: Pallas TPU kernels + XLA lowerings."""

from .pallas_kernels import pallas_matmul, pallas_mode
