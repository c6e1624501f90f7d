"""Test env: force an 8-device virtual CPU mesh before any backend init.

This is the TPU analog of the reference's localhost-subprocess distributed
tests (SURVEY.md §4): multi-chip sharding is exercised on a fake CPU mesh.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
if "xla_cpu_max_isa" not in flags:
    # The bit-exactness pins (hybrid vs hash placement, mini-passes vs one
    # pass) compare two DIFFERENT XLA programs.  XLA's CPU backend contracts
    # a multiply feeding an add into one FMA wherever the two land in the
    # same fusion, which differs between the programs and moves results by
    # one ulp.  AVX has no FMA, so capping the ISA there leaves the pins
    # testing what they are for: the order of the reductions.
    flags += " --xla_cpu_max_isa=AVX"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection end-to-end test (also marked slow so "
        "tier-1 stays fast; run with -m chaos)",
    )
    config.addinivalue_line(
        "markers",
        "distributed: exercises the multi-process plane (localhost ranks "
        "via paddlebox_tpu.launch); heavy ones are also marked slow — "
        "tier-1 (-m 'not slow') still collects everything here without "
        "needing multi-process JAX",
    )
