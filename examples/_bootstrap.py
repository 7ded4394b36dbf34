"""Shared example bootstrap: import this first in every example.

Makes the repo root importable without installing the package, honors a
virtual-CPU request (``xla_force_host_platform_device_count`` in
``XLA_FLAGS`` means "run on the host CPU", as in ``tests/conftest.py``)
and places the persistent compile cache
(``mercury_tpu.platform.configure_compile_cache``). Imports jax but never
initializes a backend.
"""

import os
import sys

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
)

from mercury_tpu.platform import (  # noqa: E402
    configure_compile_cache,
    select_cpu_if_requested,
)

select_cpu_if_requested()
configure_compile_cache()
