"""benchmark/tests run on the CPU and can never take a chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from stellar_core_tpu.parallel.device import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()
