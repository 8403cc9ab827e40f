"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls it.

Mesh topology (TPU v5e pods):

* single-pod: 16 x 16 = 256 chips, axes ``(data, model)`` — ``data``
  carries FSDP + batch DP, ``model`` carries TP/SP/EP.
* multi-pod: 2 x 16 x 16 = 512 chips, axes ``(pod, data, model)`` — the
  ``pod`` axis is an outer data-parallel axis crossing the DCN; gradient
  reduction over ``pod`` is hierarchical (reduce within pod over ICI, then
  across pods).
"""

from __future__ import annotations

import os
import re
from typing import Dict, Optional

import jax
from jax.sharding import Mesh

__all__ = ["make_production_mesh", "make_local_mesh", "make_data_mesh",
           "forced_host_devices_env"]


def forced_host_devices_env(n: int, env: Optional[Dict[str, str]] = None
                            ) -> Dict[str, str]:
    """Environment for a child process with ``n`` forced host devices.

    Device count is fixed at jax import, so multi-device CPU runs
    (sharding tests, the serve benchmark) happen in subprocesses; this
    replaces any existing ``--xla_force_host_platform_device_count`` in
    ``XLA_FLAGS`` rather than appending a duplicate.
    """
    env = dict(os.environ if env is None else env)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = " ".join(
        flags.split() + [f"--xla_force_host_platform_device_count={n}"])
    return env


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many devices the host actually has."""
    n = jax.device_count()
    if data * model > n:
        data, model = n, 1
    return _mk((data, model), ("data", "model"))


def make_data_mesh(data: int = 0) -> Mesh:
    """1-D ``("data",)`` mesh over ``min(data, device_count)`` devices.

    The search-plan engine shards CAM gallery rows over this axis (the
    bank level of the paper's §III-B hierarchy); ``data=0`` takes every
    device the host has.  Requests beyond the host's device count clamp
    rather than fail, so a plan compiled for 8-way sharding degrades to
    whatever the machine provides.
    """
    n = jax.device_count()
    data = n if data <= 0 else min(data, n)
    return _mk((data,), ("data",))
