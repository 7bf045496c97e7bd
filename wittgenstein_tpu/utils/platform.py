"""Backend platform forcing for tests and driver dry runs.

One shared definition of the init-order-sensitive trick used by
tests/conftest.py and __graft_entry__.dryrun_multichip: backends are
initialized lazily, so `jax.config.update("jax_platforms", ...)` still
wins as long as no array has been built, and XLA_FLAGS is read at the
first CPU-client init, which also happens later.
"""

from __future__ import annotations

import os


def force_virtual_cpu(n_devices: int = 8) -> None:
    """Force the CPU platform with `n_devices` virtual devices.

    Must run before the first device/backend use (anything that builds an
    array).  If XLA_FLAGS already carries a device-count flag it is kept
    as-is (callers should assert len(jax.devices()) afterwards when they
    need an exact count).
    """
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
