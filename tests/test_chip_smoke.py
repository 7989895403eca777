"""`chip_smoke.run_smoke` at smoke size on the CPU (kernels interpreted).

The chip run serves granite-8b at published widths in bf16; this runs the
same serving function on the reduced config in f32, where the kernel path
must match the jnp path to float32 rounding.
"""

import os
import sys

import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


def test_run_smoke_serves_and_matches_ref_path():
    report = chip_smoke.run_smoke(
        get_config(chip_smoke.ARCH).smoke(), dtype=jnp.float32,
        n_requests=3, prompt_lens=(8, 40), max_new=4, max_slots=4,
        max_seq_len=64, pool_tokens=256, prefill_chunk=16, waves=1,
        tol=1e-4, log=lambda *a: None)
    assert set(report["phases"]) == {"chunked-16", "monolithic"}
    for ph in report["phases"].values():
        assert ph["tokens"] == 3 * 4
        assert ph["logit_rel"] <= 1e-4
