"""Device, platform, and mesh/topology introspection.

TPU-native analogue of the reference's device shim (``common/device_utils.py:23-85``:
``get_current_device`` / ``get_current_device_type`` / ``get_local_device_count`` /
``get_distributed_backend`` / ``get_distributed_init_method``) plus the hardware-topology
probing its health checks do via NVML/PCI (``shared_utils/health_check.py:352-465``).
On TPU the probe-able topology is the ICI mesh: per-device chip coordinates and the
host↔chip mapping, read from JAX's device list rather than the PCI tree.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Optional, Sequence

import numpy as np


def platform_kind() -> str:
    """'tpu' | 'gpu' | 'cpu' — the JAX default backend platform.

    ``$JAX_PLATFORMS`` decides which backend that is; unset, it is whatever JAX
    finds. Nothing in this package defaults the platform in code."""
    import jax

    return jax.default_backend()


def local_device_count() -> int:
    import jax

    return jax.local_device_count()


def global_device_count() -> int:
    import jax

    return jax.device_count()


def process_index() -> int:
    import jax

    return jax.process_index()


def process_count() -> int:
    import jax

    return jax.process_count()


def default_device():
    import jax

    return jax.devices()[0]


@dataclasses.dataclass(frozen=True)
class DeviceInfo:
    """One accelerator device and where it lives."""

    device_id: int
    process_index: int
    platform: str
    device_kind: str
    coords: Optional[tuple[int, ...]]  # ICI chip coordinates (TPU only)
    core_on_chip: Optional[int]


@dataclasses.dataclass(frozen=True)
class Topology:
    """Snapshot of the device topology visible to this process' JAX runtime."""

    devices: tuple[DeviceInfo, ...]
    num_processes: int

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    def devices_on_host(self, proc: int) -> list[DeviceInfo]:
        return [d for d in self.devices if d.process_index == proc]

    def host_of_device(self, device_id: int) -> int:
        for d in self.devices:
            if d.device_id == device_id:
                return d.process_index
        raise KeyError(device_id)

    def hosts(self) -> list[int]:
        return sorted({d.process_index for d in self.devices})


def probe_topology() -> Topology:
    """Read the global device topology from JAX."""
    import jax

    infos = []
    for d in jax.devices():
        coords = getattr(d, "coords", None)
        infos.append(
            DeviceInfo(
                device_id=d.id,
                process_index=d.process_index,
                platform=d.platform,
                device_kind=getattr(d, "device_kind", d.platform),
                coords=tuple(coords) if coords is not None else None,
                core_on_chip=getattr(d, "core_on_chip", None),
            )
        )
    return Topology(devices=tuple(infos), num_processes=jax.process_count())


def make_mesh(axis_shapes: dict[str, int], *, devices: Optional[Sequence[Any]] = None):
    """Build a ``jax.sharding.Mesh`` with named axes.

    ``axis_shapes`` maps axis name → size in declaration order, e.g.
    ``{"dp": 2, "tp": 4}``. Uses ``mesh_utils.create_device_mesh`` for an ICI-friendly
    physical layout when possible (keeps collectives riding ICI rather than DCN), falling
    back to a plain reshape for virtual/CPU device sets.
    """
    import jax
    from jax.sharding import Mesh

    names = tuple(axis_shapes.keys())
    shape = tuple(axis_shapes.values())
    devs = list(devices) if devices is not None else jax.devices()
    n = int(np.prod(shape))
    if n != len(devs):
        raise ValueError(f"mesh shape {shape} needs {n} devices, have {len(devs)}")
    try:
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devs)
    except Exception:
        arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, names)


def apply_compile_cache_env() -> None:
    """Run the persistent-compilation-cache integrity sweep and record this
    process's ``compile_cache`` event when ``$JAX_COMPILATION_CACHE_DIR`` is set
    (``platform/compile_cache.py``). One-shot per process; a no-op when the
    variable is unset — the library never invents a cache path."""
    from tpu_resiliency.platform import compile_cache

    compile_cache.apply_from_env()


def warm_runtime() -> dict:
    """Platform-safe runtime warmup for parked warm spares (``launcher/park.py``
    ``--warm-spare-warmup runtime``): pre-pay everything a worker's first
    backend use costs that does NOT touch an accelerator device.

    The hard constraint: a parked spare coexists with the round's live workers
    — and, at promotion time, with the *dying* worker whose device lease is
    still held — so device-grabbing stays strictly post-promotion. Three
    warmup levels, each gated:

    - **plugin discovery**: enumerate (and import, which only *registers*)
      PJRT plugin entry points — never initialize them.
    - **tracing machinery**: a backend-free ``jax.eval_shape`` trace warms
      jaxpr/lowering import chains.
    - **CPU/loopback backend pre-init**: only when ``$JAX_PLATFORMS`` pins the
      workload to ``cpu`` (tests, loopback benches, CPU jobs) — then the
      backend the worker will use is the host CPU, which no dying worker can
      hold a lease on, so full init + one dispatched op is safe and removes
      backend-init from the promoted worker's first step. Under any other
      setting (unset included) the spare stays off every backend: on a TPU
      host the chip belongs to the live worker.

    Must not mutate ``os.environ`` or ``sys.path`` (promotion parity contract).
    Raises on genuine breakage so the shim dies before writing its ready file
    (startup death), rather than parking a half-warm interpreter.
    """
    import jax
    import jax.numpy as jnp

    info: dict[str, Any] = {"plugins": 0, "traced": False, "cpu_init": False}
    try:
        from importlib import metadata

        info["plugins"] = len(metadata.entry_points(group="jax_plugins"))
    except Exception:
        pass  # discovery is best-effort; absence of plugins is normal
    jax.eval_shape(
        lambda x: jnp.tanh(x @ x.T).sum(),
        jax.ShapeDtypeStruct((4, 4), jnp.float32),
    )
    info["traced"] = True
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        apply_compile_cache_env()
        jax.block_until_ready(jnp.zeros((8,), jnp.float32) + 1.0)
        info["cpu_init"] = True
    return info


def device_liveness_probe(timeout: float = 30.0, device=None) -> bool:
    """Check the accelerator still executes and completes work.

    Direct analogue of the reference's ``CudaHealthCheck`` double
    ``torch.cuda.synchronize`` under a timeout thread (``inprocess/health_check.py:70-110``):
    submit a tiny computation twice and ``block_until_ready`` with a watchdog thread, so a
    wedged device (hung ICI collective, dead runtime) turns into a ``False`` rather than a
    forever-block. Device RESOLUTION happens inside the guarded worker too: when the
    runtime is dead enough that backend init itself raises (or blocks), the probe's
    answer is still ``False``, never an exception — health paths must keep running
    on a broken host.
    """
    import jax
    import jax.numpy as jnp

    result: dict[str, bool] = {}

    def _work():
        try:
            dev = device if device is not None else default_device()
            for _ in range(2):
                x = jax.device_put(jnp.ones((8,), jnp.float32), dev)
                jax.block_until_ready(x + 1.0)
            result["ok"] = True
        except Exception:
            result["ok"] = False

    t = threading.Thread(target=_work, name="device-probe", daemon=True)
    t.start()
    t.join(timeout)
    return result.get("ok", False)


def visible_device_env() -> dict[str, str]:
    """Environment variables that pin TPU visibility for spawned worker processes."""
    out = {}
    for key in ("TPU_VISIBLE_DEVICES", "TPU_PROCESS_BOUNDS", "JAX_PLATFORMS", "XLA_FLAGS"):
        if key in os.environ:
            out[key] = os.environ[key]
    return out
