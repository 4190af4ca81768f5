"""TPU-hardware test fixtures: ``python -m pytest tests_tpu/ -q``.

Unlike tests/ (which pins the 8-device virtual CPU mesh), this suite
runs against the REAL accelerator and covers the TPU-only branches:
the Mosaic-compiled Pallas bucket kernel, XLA's TPU lowering of the
sort path, and end-to-end workloads on the chip.

It is its own command, run in its own process after any other holder
of the chip (``chip_smoke.py``, ``benchmarks/run.py``) has exited — one
process owns a chip at a time.  On a machine without a TPU the suite FAILS at
collection, naming the platform it found; it never skips.
"""

import jax
import pytest

from dryad_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()


def pytest_collection_modifyitems(config, items):
    if not items:
        return
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu/ needs a TPU; jax found platform={platform!r} "
            f"({jax.devices()[0].device_kind}).  This suite does not "
            "skip: run it where the chip is."
        )
