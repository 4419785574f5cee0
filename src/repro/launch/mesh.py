"""Production meshes. A FUNCTION, not a module-level constant — importing
this module never touches jax device state (the dry-run sets
``xla_force_host_platform_device_count`` before first jax init)."""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def ensure_fake_cpu_devices(n: int) -> None:
    """Give the CPU platform ``n`` fake devices (mesh runs on dev boxes /
    CI). MUST be called before JAX initializes its backends — before the
    first jax operation; merely importing jax is fine. No-op when the
    flag is already set; harmless on real accelerators (the flag only
    affects the CPU platform's device count)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} " + flags)


def auto_mesh(shape, axes, *, devices=None):
    """THE mesh constructor of this repo: ``jax.make_mesh`` with every
    axis ``Auto``. The model code places data with
    ``with_sharding_constraint`` and ``shard_map`` under the compiler's
    sharding propagation, which needs Auto axes; ``jax.make_mesh``
    itself defaults to Explicit axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)
