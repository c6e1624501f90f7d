"""benchmark/tests run by hand, on the CPU with four virtual devices:

    python3 -m pytest benchmark/tests -q

Tier-1 (``pytest tests/``) does not collect this directory."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
