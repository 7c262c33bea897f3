"""Device management (ref: python/paddle/device.py).

On TPU there is no per-op device placement: JAX owns the local devices
(PJRT) and `jit` computations are placed by sharding. `set_device` selects
the default jax platform when called before first use.
"""

from __future__ import annotations

import jax


def get_device() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str):
    """Select the default platform and return `get_device()`.  Raises
    when the platform asked for is not the one JAX ends up on — a
    "tpu" request must never come back as `cpu:0`."""
    dev = device.split(":")[0]
    if dev in ("gpu", "cuda"):
        raise ValueError(
            "paddle_tpu targets TPU (and CPU for testing); GPU is not a "
            "supported backend")
    try:
        jax.config.update("jax_platforms", "cpu" if dev == "cpu" else None)
    except RuntimeError:
        pass  # backend already initialised: the check below decides
    got = jax.default_backend()
    if got != dev:
        raise RuntimeError(
            f"set_device({device!r}): the default JAX backend is {got!r}")
    return get_device()


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def memory_stats(device=None) -> dict:
    """MEASURED per-device memory (ref platform/monitor.h:77 GPU mem
    high-watermark + memory/stats.h): PJRT allocator stats when the
    backend exposes them (`bytes_in_use`, `peak_bytes_in_use`, ...);
    otherwise a live-array census over the device's addressable shards,
    split by memory kind:

      bytes_in_use       device-resident jax array bytes
      host_bytes_in_use  pinned-host-resident bytes (opt-state offload)
      peak_bytes_in_use  allocator high-watermark, or -1 when only the
                         census is available (the host CPU backend has
                         no allocator stats)

    `device`: a jax Device, an integer ordinal, or None (device 0)."""
    if device is None:
        device = jax.devices()[0]
    elif isinstance(device, int):
        device = jax.devices()[device]
    dev_bytes = 0
    host_bytes = 0
    # an array "rests on the device" when it sits in the device's
    # DEFAULT memory space; only non-default host kinds (pinned_host
    # offload) count as host-resident.  Comparing against the default
    # kind matters on CPU backends whose default space is itself named
    # *_host — there every array would otherwise census as offloaded.
    default_kind = device.default_memory().kind
    for arr in jax.live_arrays():
        try:
            kind = getattr(arr.sharding, "memory_kind", None)
            for sh in arr.addressable_shards:
                if sh.device == device:
                    nb = int(sh.data.size) * sh.data.dtype.itemsize
                    if kind and kind != default_kind \
                            and "host" in str(kind):
                        host_bytes += nb
                    else:
                        dev_bytes += nb
        except Exception:  # deleted/donated arrays mid-iteration
            continue
    stats = device.memory_stats() or {}
    if stats.get("bytes_in_use") is not None:
        # allocator stats never cover pinned-host buffers: graft the
        # census host figure so offload stays measurable on real TPUs
        out = dict(stats)
        out.setdefault("host_bytes_in_use", host_bytes)
        return out
    return {"bytes_in_use": dev_bytes, "host_bytes_in_use": host_bytes,
            "peak_bytes_in_use": -1, "source": "live_array_census"}


class CPUPlace:
    def __repr__(self):
        return "Place(cpu)"


class TPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(tpu:{self.device_id})"


# alias kept for scripts written against CUDAPlace
CUDAPlace = TPUPlace
