"""Where a Pallas kernel runs: compiled on TPU, interpreted elsewhere."""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The kernels' one interpret rule.

    An explicit ``interpret`` wins. ``None`` compiles the kernel for the
    TPU when the default backend is a TPU and runs it in interpret mode
    (correct, not fast) anywhere else. A program compiled ahead of time
    for a TPU from a host without one sees the CPU backend here, so such a
    caller passes ``interpret=False`` itself.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# Scoped VMEM the persistent-scan kernels (cell_scan, decoder_scan) may
# claim. Their recurrent weights, weight-gradient accumulators and encoder
# memory stay resident for the whole time grid, which outgrows Mosaic's
# 16 MiB default at the paper's widths (Luong 2x512: 22 MiB forward). A
# v5e TensorCore holds 128 MiB of VMEM; the rest is left to Mosaic.
SCAN_VMEM_LIMIT_BYTES = 100 * 2**20


def scan_compiler_params():
    """Mosaic parameters of the persistent-scan kernels."""
    return pltpu.CompilerParams(vmem_limit_bytes=SCAN_VMEM_LIMIT_BYTES)
