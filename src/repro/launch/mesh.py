"""Production mesh builders (deliverable e) + multi-host fleet bring-up.

Defined as FUNCTIONS so importing this module never touches jax device state.
Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — `pod` is the
extra data-parallel dimension whose gradient reduction crosses the
inter-pod links.

Every mesh is built by ``repro.utils.jax_compat.make_compat_mesh``, which
gives each axis ``AxisType.Auto``.

Fleet bring-up (docs/multihost.md): :func:`init_distributed` resolves the
``coordinator`` string — ``host:port`` means real multi-process jax
(``jax.distributed.initialize``); a filesystem path means the CPU-simulated
fleet, where every host process forces ``num_hosts * devices_per_host``
local host-platform devices (``XLA_FLAGS=--xla_force_host_platform_
device_count=N``, set BEFORE jax import) and coordinates through the shared
directory (``repro.distributed.fleet``). Either way,
:func:`make_fleet_mesh` then builds the global ``(pod, data, model)`` mesh
every process agrees on.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.utils.jax_compat import make_compat_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_compat_mesh(shape, axes)


def make_local_mesh(shape=None, axes=("data", "model")):
    """Whatever the current backend offers (tests / CPU examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return make_compat_mesh(shape, axes)


def init_distributed(coordinator: str, num_processes: int = 1,
                     process_id: int = 0, **overrides):
    """Bring up the multi-host runtime; returns the registered
    :class:`repro.distributed.fleet.FleetContext` (None when single-host).

    ``coordinator`` ``"host:port"`` -> ``jax.distributed.initialize`` (real
    hardware; jax then exposes the other hosts' devices and there is no file
    plane to manage). Anything else is a shared DIRECTORY -> the simulated
    fleet: a FleetContext is built from a validated ``DistributedConfig``
    (``overrides`` forward extra fields, e.g. ``grad_compression``,
    ``dead_after_s``) and registered as the process-global context that
    ``build_pipeline`` picks up.
    """
    if num_processes <= 1:
        return None
    from repro.configs.base import DistributedConfig
    from repro.distributed import fleet

    if ":" in coordinator and "/" not in coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        return None
    cfg = DistributedConfig(
        num_hosts=num_processes, process_id=process_id,
        coordinator=coordinator, **overrides,
    )
    ctx = fleet.ensure_context(cfg)
    ctx.heartbeat(0)
    return ctx


def make_fleet_mesh(num_hosts: int, devices_per_host: int = 0,
                    *, model_parallel: int = 1, devices=None):
    """Global ``(pod, data, model)`` mesh over the fleet's devices.

    Every process must call this with identical arguments and derive the
    identical mesh — the multi-controller SPMD contract. The ``pod`` axis
    has one row per host (row-major ``jax.make_mesh`` ordering puts each
    host's devices in one contiguous block, which is also how
    ``fleet.host_device_groups`` recovers the host groups); ``data`` x
    ``model`` tile within a host. In the CPU-simulated mode each process
    sees all ``num_hosts * devices_per_host`` forced host-platform devices;
    under ``jax.distributed`` the same global device list spans processes.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if devices_per_host == 0:
        if len(devices) % num_hosts:
            raise ValueError(
                f"{len(devices)} devices not divisible by {num_hosts} hosts")
        devices_per_host = len(devices) // num_hosts
    need = num_hosts * devices_per_host
    if need > len(devices):
        raise ValueError(
            f"fleet needs {need} devices, backend offers {len(devices)} "
            "(simulated fleets must set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax import)")
    if devices_per_host % model_parallel:
        raise ValueError(
            f"devices_per_host {devices_per_host} not divisible by "
            f"model_parallel {model_parallel}")
    shape = (num_hosts, devices_per_host // model_parallel, model_parallel)
    return make_compat_mesh(shape, ("pod", "data", "model"),
                            devices=devices[:need])
