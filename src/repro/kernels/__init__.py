"""Pallas kernels for the paper's compute hot-spots, per backend.

paged_attention/ — fused paged decode attention (the paper's core kernel):
                   paged_attention.py is the TPU lowering (scalar-prefetch
                   block tables, Mosaic), paged_attention_gpu.py the
                   Triton/GPU lowering (in-kernel block-table gathers).
flex_attention/  — flash-style prefill kernel with FlexAttention mask/score
                   mods and BlockMask-driven tile skipping
Each has ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle).

Backend-selection contract: every kernel-facing op takes
``backend=None`` (auto: whatever ``jax.default_backend()`` reports,
falling back to the TPU lowering on CPU hosts) and ``interpret=None``
(auto: interpret mode unless the process runs on the backend the kernel
targets — so CPU CI exercises both lowerings through the Pallas
interpreter while real TPUs/GPUs compile).  On a TPU an interpreted
kernel is an error, never a silent fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.errors import EngineConfigError

BACKENDS = ("tpu", "gpu")


@functools.lru_cache(maxsize=None)
def _on_platform(platform: str) -> bool:
    # Resolved once per process: Pallas kernels compile on their target
    # platform and fall back to interpret mode everywhere else (CPU CI,
    # cross-platform hosts).
    return jax.default_backend() == platform


def resolve_backend(backend: Optional[str]) -> str:
    """``None``/"auto" → the running platform's kernel lowering.

    GPU hosts get the Triton lowering, everything else (TPU and the CPU
    interpret-mode CI) the TPU lowering; explicit names pass through
    (validated).
    """
    if backend is None or backend == "auto":
        return "gpu" if _on_platform("gpu") else "tpu"
    if backend not in BACKENDS:
        raise EngineConfigError(f"backend must be one of {BACKENDS} or "
                                f"None/'auto', got {backend!r}",
                                backend=backend)
    return backend


def resolve_interpret(interpret: Optional[bool],
                      backend: str = "tpu") -> bool:
    """``None`` → auto (interpret iff not running on ``backend``'s
    platform); bools pass through.  On a TPU host an interpreted kernel
    is refused: the interpreter there would hide the device path."""
    if interpret is None:
        interpret = not _on_platform(backend)
    if interpret and _on_platform("tpu"):
        raise EngineConfigError(
            f"Pallas kernel for backend {backend!r} would run in interpret "
            "mode on a TPU; use the TPU lowering compiled (interpret=None)",
            backend=backend)
    return bool(interpret)
