"""Device compute ops: Pallas TPU kernels + XLA lowerings."""

from .pallas_kernels import (decode_use_flash, paged_flash_decode,
                             pallas_enabled, pallas_int8_matmul,
                             pallas_matmul, pallas_mode)
