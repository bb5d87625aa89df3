"""Rehearsal of ``chip_smoke.py`` on 4 forced CPU devices at a tiny size,
with the slab kernels in Pallas interpret mode: the same phases, checks
and oracles as on the chip.  Subprocess-only (XLA_FLAGS):

    PYTHONPATH=src python tests/multidevice/child_chip_smoke.py
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import chip_smoke  # noqa: E402
from repro.core import jax_collectives as jc  # noqa: E402

TINY = {"mixtral-8x7b": 256, "deepseek-moe-16b": 128}

if __name__ == "__main__":
    assert jax.device_count() == 4, jax.devices()
    jc.set_dataplane("interpret")
    chip_smoke.one_chip_phase(jnp.bfloat16, tokens=48, widths=TINY,
                              interpret=True)
    chip_smoke.four_chip_phase(jnp.bfloat16, tokens=40, widths=TINY)
    print("ALL CHIP-SMOKE REHEARSAL CHECKS PASSED")
