"""Jit'd public wrappers over the Pallas kernels.

``INTERPRET`` is the one host default for Pallas interpret mode: False
on a TPU backend (``pl.pallas_call`` lowers to Mosaic), True elsewhere
(CPU validation). Every ``interpret=None`` default — the kernel wrappers,
``OzakiConfig``/``ModularConfig``, ``PipelinePlan`` and the planners —
resolves to it at launch or plan time; pass ``interpret=True`` to run
the interpreter on a TPU on purpose.
"""
from __future__ import annotations

import jax

from .int8_gemm import (int8_matmul_nt, int8_matmul_nt_batched,
                        int8_matmul_nt_crt,
                        int8_matmul_nt_epilogue_dw,
                        int8_matmul_nt_epilogue_sw,
                        int8_matmul_nt_streaming_dw,
                        int8_matmul_nt_streaming_sw)
from .ozaki_accum import accum_scaled_dw, accum_scaled_sw
from .ozaki_split import fused_split_dw

INTERPRET = jax.default_backend() != "tpu"

__all__ = ["int8_matmul_nt", "int8_matmul_nt_batched",
           "int8_matmul_nt_crt",
           "int8_matmul_nt_epilogue_dw", "int8_matmul_nt_epilogue_sw",
           "int8_matmul_nt_streaming_dw", "int8_matmul_nt_streaming_sw",
           "fused_split_dw", "accum_scaled_dw", "accum_scaled_sw",
           "INTERPRET"]
