"""DET005 negative: a REGISTERED seam (parity test pinned in
tools/detcheck/parity_registry.py) branches freely."""
import os

import jax


def donation_enabled():
    # registered: PROGRAM_PAIRS `donation-on-vs-off` ->
    # tests/test_mesh_block.py
    return os.environ.get("LGBM_TPU_DONATE", "1") != "0"


def run(x):
    if donation_enabled():
        return jax.jit(lambda v: v + 1.0)(x)
    return x + 1.0
