"""Test environment: force an 8-device virtual CPU platform before JAX
initializes, so multi-chip sharding tests run without TPU hardware.

Tests run on the CPU (``JAX_PLATFORMS=cpu``); ``jax.config.update`` after
import pins it even where the environment says otherwise.  The chip is
exercised by ``chip_smoke.py`` through the chip tool, and the kernels'
TPU compiles by tests/test_chip_compile.py against a described chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
