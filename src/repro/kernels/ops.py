"""Jit'd public wrappers for the Pallas kernels (dispatch layer).

On a TPU backend the kernels run compiled; elsewhere they run in
interpret mode, which executes the kernel body on CPU for correctness
(``kernels/backend.py``; an ahead-of-time compile for a TPU passes
``interpret=False``).
``ref.py`` holds the independent pure-jnp oracles used by the tests.
"""
from repro.kernels.cell_scan import cell_scan
from repro.kernels.decoder_scan import decoder_scan
from repro.kernels.gather_matmul import gather_matmul, gather_matmul_stepped
from repro.kernels.lstm_pointwise import lstm_pointwise
from repro.kernels.lstm_scan import lstm_scan
from repro.kernels.slstm_scan import slstm_scan

__all__ = ["cell_scan", "decoder_scan", "gather_matmul",
           "gather_matmul_stepped", "lstm_pointwise", "lstm_scan",
           "slstm_scan"]
