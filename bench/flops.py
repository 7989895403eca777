"""What the chip's roofline takes of a count of operations and bytes.

The counts themselves are the architecture's: each module under
``bench/arch/`` gives its ``Shape`` the FLOPs and bytes of the work it
serves, counting useful work only, so that a share computed from them is
at most the work the device really did.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2  # bytes per element of the served dtype


def roofline_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
