"""The benchmark's own tests (not tier-1: ``pytest benchmarks/tests``).
They run on four virtual CPU devices; no number they print is a device
number."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from dryad_tpu.parallel.mesh import force_cpu_backend  # noqa: E402

force_cpu_backend(4)
