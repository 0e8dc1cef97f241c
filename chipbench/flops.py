"""What every model family's counts share: the bytes of a served dtype
and the roofline's least time.  A family module
(``chipbench/families/``) counts its own operations and bytes from
shapes at the published widths (padding the program adds is work it
chose, not work the model needs, so it is not counted).

A multiply-add counts as two operations.  Bytes are bf16 (2 bytes)
unless the configuration serves another dtype.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_time(flops: float, nbytes: float, peak: Dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
