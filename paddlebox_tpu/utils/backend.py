"""Process entry points' first contact with JAX.

One rule holds everywhere: a process that could have had the accelerator
never ends up on the CPU unnoticed.  ``--cpu`` is the explicit way off it.
"""

from __future__ import annotations

import os

from paddlebox_tpu.utils.compile_cache import enable_compile_cache


def host_tpu_chips() -> int:
    """TPU chips on this host's PCI bus — the scan JAX itself uses to
    recognise a TPU host; it touches no backend and claims no chip."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def setup_backend(cpu: bool = False) -> None:
    """Entry-point prologue, before the first JAX call.  ``cpu`` is the
    scripts' explicit ``--cpu`` opt-out and pins the CPU backend.
    Otherwise JAX takes the accelerator it finds and keeps what it
    compiles for it in the persistent cache — and on a host that HAS TPU
    chips the platform is pinned to them, so a process that cannot get
    one fails in JAX instead of carrying on with CPU devices."""
    import jax

    if cpu:
        jax.config.update("jax_platforms", "cpu")
        return
    enable_compile_cache()
    if not os.environ.get("JAX_PLATFORMS") and host_tpu_chips():
        jax.config.update("jax_platforms", "tpu")


def claim_devices() -> list:
    """``jax.devices()``, for a process that others may be racing for the
    chip (a serving replica): the backend error of the loser is libtpu's
    ("multi-process lockfile"), so say what it means and exit non-zero."""
    import jax

    try:
        return jax.devices()
    except RuntimeError as e:
        if "tpu" not in str(e).lower():
            raise
        raise SystemExit(
            f"could not get a TPU: {e}\nA chip belongs to one process at a "
            "time — a parent that touched JAX, another replica or another "
            "rank on this host holds it.  One server process serves from "
            "all local chips; several replica processes on one host need "
            "--cpu."
        ) from e
