"""Device resolution and availability probing.

Pipeline configs place stage groups on devices by logical index (or by
explicit ``platform:index`` label). Index ``-1`` means "run on the host"
— used for host-side stages like the aggregator (reference
runner.py:31-44 ran those without CUDA). The availability probe replaces
the reference's py3nvml memory-free check (reference benchmark.py:97-125)
with `jax.devices()` introspection: on TPU the runtime owns every core in
the slice, so existence is the meaningful check.
"""

from __future__ import annotations

import os
from typing import List, Union

DeviceSpecLike = Union[int, str]

HOST_DEVICE_INDEX = -1


class DeviceResolutionError(RuntimeError):
    pass


def accelerator_devices() -> list:
    """Devices of the default JAX backend, in enumeration order.

    Under a TPU runtime this is the TPU cores of the slice; in tests it
    is the virtual CPU devices created by
    ``--xla_force_host_platform_device_count``.
    """
    import jax
    return list(jax.devices())


def keep_host_backend() -> None:
    """Keep the CPU backend beside the accelerator. Host-placed (-1)
    stages run on ``jax.devices("cpu")``, and a platform list that
    names only the accelerator (``JAX_PLATFORMS=tpu``) leaves that
    backend out; appending ``cpu`` changes neither the default
    platform (the first listed) nor the loud failure when the
    accelerator cannot initialize. Only effective before the first
    backend use, so the entry points call it first."""
    import jax
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")


def require_platform(wanted: str = "tpu") -> list:
    """-> ``jax.devices()``, raising unless the default backend is
    ``wanted``. The measurement entry points (chip_smoke.py, the
    benchmark CLI) call this before any work: JAX falls back to
    the CPU when no accelerator initializes, and a run that completed
    on the wrong device must not look like a result."""
    import jax
    keep_host_backend()
    devices = list(jax.devices())
    found = devices[0].platform
    if found != wanted:
        raise DeviceResolutionError(
            "JAX came up on platform %r (%d device(s), kind %r), not "
            "%r; ask for the CPU explicitly (--platform cpu / "
            "RNB_BENCH_PLATFORM=cpu) if that is what you want"
            % (found, len(devices), devices[0].device_kind, wanted))
    return devices


def host_device():
    """The first CPU device — where host-placed (-1) stages run."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise DeviceResolutionError(
            "a stage is placed on the host (device -1) but this "
            "process has no CPU backend — JAX_PLATFORMS=%r excludes "
            "it; leave JAX_PLATFORMS unset or add ',cpu'"
            % os.environ.get("JAX_PLATFORMS", "")) from e


class DeviceSpec:
    """A resolved placement: one JAX device plus a stable log label."""

    def __init__(self, spec: DeviceSpecLike):
        self.spec = spec
        self._device = None  # resolved lazily so parsing needs no backend

    @property
    def is_host(self) -> bool:
        return self.spec == HOST_DEVICE_INDEX

    def resolve(self):
        """Return the jax.Device this spec names (cached)."""
        if self._device is not None:
            return self._device
        import jax
        if isinstance(self.spec, int):
            if self.spec == HOST_DEVICE_INDEX:
                self._device = host_device()
            else:
                devices = accelerator_devices()
                if not 0 <= self.spec < len(devices):
                    raise DeviceResolutionError(
                        "pipeline configuration names device %d but only %d "
                        "devices are visible (%s)"
                        % (self.spec, len(devices),
                           [str(d) for d in devices]))
                self._device = devices[self.spec]
        elif isinstance(self.spec, str):
            platform, _, idx = self.spec.partition(":")
            try:
                candidates = jax.devices(platform)
            except RuntimeError as e:
                raise DeviceResolutionError(
                    "no %r backend available for device spec %r"
                    % (platform, self.spec)) from e
            index = int(idx) if idx else 0
            if not 0 <= index < len(candidates):
                raise DeviceResolutionError(
                    "device spec %r out of range: %d %s devices visible"
                    % (self.spec, len(candidates), platform))
            self._device = candidates[index]
        else:
            raise DeviceResolutionError(
                "unsupported device spec %r (want int or 'platform:idx')"
                % (self.spec,))
        return self._device

    @property
    def label(self) -> str:
        """Stable string used in TimeCard device trails and log names."""
        if self.is_host:
            return "host"
        if isinstance(self.spec, int):
            d = self.resolve()
            return "%s:%d" % (d.platform, d.id)
        return str(self.spec)

    def __repr__(self):
        return "DeviceSpec(%r)" % (self.spec,)

    def __eq__(self, other):
        return isinstance(other, DeviceSpec) and other.spec == self.spec

    def __hash__(self):
        return hash(self.spec)


def check_devices(specs: List[DeviceSpec]) -> None:
    """Resolve every spec, raising DeviceResolutionError for bad ones."""
    for spec in specs:
        spec.resolve()


#: bytes_in_use above this before we allocate anything suggests another
#: client holds buffers on the chip (the TPU runtime itself keeps a few
#: hundred KiB resident, so 0 is never the idle reading)
BUSY_BYTES_THRESHOLD = 16 * 1024 * 1024


def probe_busy_devices(specs: List[DeviceSpec]) -> List[str]:
    """Best-effort "device already in use" warning list.

    The reference refused to start unless every requested GPU reported
    zero bytes of used memory (reference benchmark.py:97-125). A TPU
    runtime owns the whole slice so exact parity is impossible, but
    ``Device.memory_stats()`` — where the backend implements it —
    exposes ``bytes_in_use`` before this job allocates anything; a
    non-trivial figure means something already holds buffers on the
    chip — an earlier job in this process keeps its cached weights
    there, for one. Unlike the reference this returns warnings instead
    of aborting: less free memory does not make the run incorrect.
    """
    warnings: List[str] = []
    seen = set()
    for spec in specs:
        if spec.is_host:
            continue
        try:
            device = spec.resolve()
        except DeviceResolutionError:
            continue  # best-effort: resolution errors are check_devices' job
        if device in seen:
            continue
        seen.add(device)
        try:
            stats = device.memory_stats()
        except Exception:
            continue  # backend without memory introspection
        if not stats:
            continue
        in_use = stats.get("bytes_in_use", 0)
        if in_use > BUSY_BYTES_THRESHOLD:
            warnings.append(
                "device %s already has %.1f MiB in use before this job "
                "allocated anything (an earlier job in this process, or "
                "another client, holds memory there)"
                % (spec.label, in_use / (1024.0 * 1024.0)))
    return warnings
