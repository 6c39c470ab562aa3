"""Run a process on an n-device virtual CPU platform.

Shared by the test conftest, the multi-chip dryrun child, and the
multi-host test children, all of which exercise sharded programs on a
CPU mesh. Two environment settings are the whole job —
`JAX_PLATFORMS=cpu` and XLA's host-platform device count — and jax reads
both when it is first imported, so call these BEFORE importing jax.
"""

from __future__ import annotations

import os
from typing import MutableMapping


def force_cpu_env(
    env: MutableMapping[str, str],
    n_devices: int,
    override: bool = True,
) -> MutableMapping[str, str]:
    """Set JAX_PLATFORMS/XLA_FLAGS for a CPU n-device platform on an env
    mapping (os.environ or a child-process env dict). With
    override=False an already-present device-count flag is honored."""
    flags = env.get("XLA_FLAGS", "")
    if override or "xla_force_host_platform_device_count" not in flags:
        kept = [
            f
            for f in flags.split()
            if "xla_force_host_platform_device_count" not in f
        ]
        kept.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(kept)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def force_cpu_platform(
    n_devices: int | None = None, override: bool = True
) -> None:
    """`force_cpu_env` on this process's own environment; with
    `n_devices=None` the device count is left to the caller's XLA_FLAGS."""
    if n_devices is not None:
        force_cpu_env(os.environ, n_devices, override=override)
    else:
        os.environ["JAX_PLATFORMS"] = "cpu"
