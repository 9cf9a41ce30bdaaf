"""Forced host-device plumbing shared by the mesh-aware benches.

Import-safe before jax: ``force_host_devices`` must run after argparse
but before the first jax touch, so this module must not import jax (or
anything that does — ``benchmarks.common`` pulls in ``repro``).
"""
from __future__ import annotations

import os


def force_host_devices(n: int) -> None:
    """Best-effort: request n host devices before jax backend init.
    A no-op when a force-count is already present in XLA_FLAGS (never
    fight an outer environment's setting)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + flag).strip()


def require_visible(n_dev: int, bench: str) -> int:
    """Return the requested mesh width, or fail when jax exposes fewer
    devices (it may have initialized before ``force_host_devices``, e.g.
    under the run.py aggregator): a bench never measures a smaller mesh
    than it was asked for."""
    import jax                       # initialized by now — safe to touch
    if n_dev > len(jax.devices()):
        raise SystemExit(
            f"{bench}: wanted {n_dev} devices, the platform exposes "
            f"{len(jax.devices())} (jax initialized before the device "
            f"count could be forced?)")
    return n_dev
