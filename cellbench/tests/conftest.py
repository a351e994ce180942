"""The benchmark's own tests run on the CPU: ``python -m pytest
cellbench/tests -q``. Four virtual devices, so that the mesh path of the
four-chip cell can be driven; set before jax is imported."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
