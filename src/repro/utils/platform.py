"""Process-level platform setup: XLA flags, x64, emulated device counts.

One place for the env mangling that used to be copy-pasted ad hoc into
benchmark drivers, conftest and the dry-run launcher.  Everything here is
import-light: ``jax`` is imported lazily inside the functions that need it,
so the flag setters can run *before* jax initialises — which is the only
time they have any effect (jax locks the platform and the host device count
on first init).

Typical uses::

    from repro.utils import platform as rplat
    rplat.set_host_device_count(8)      # BEFORE the first jax import/init
    import jax                          # sees 8 emulated CPU devices

    rplat.enable_x64()                  # float64 for reference numerics
    rplat.set_platform("cpu")           # force CPU even on an accelerator
    rplat.enable_compile_cache()        # persistent compile cache (see below)

CI and test runs opt into device emulation with the ``REPRO_EMULATED_DEVICES``
environment variable (see :func:`emulated_device_count` /
:func:`apply_emulated_devices`); tests/conftest.py applies it before jax
loads, replacing per-job ``XLA_FLAGS`` string surgery.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count"

# Environment knob: number of emulated host (CPU) devices a test/bench
# process should see.  "" / unset / "0" means "leave jax alone".
EMULATED_DEVICES_VAR = "REPRO_EMULATED_DEVICES"


def _merge_xla_flag(flag: str, value: str) -> None:
    """Set ``flag=value`` in XLA_FLAGS, replacing any previous setting of
    the same flag and preserving every other flag already there."""
    existing = [
        tok for tok in os.environ.get("XLA_FLAGS", "").split()
        if not tok.startswith(flag + "=")
    ]
    existing.append(f"{flag}={value}")
    os.environ["XLA_FLAGS"] = " ".join(existing)


def set_host_device_count(n: int) -> None:
    """Make the CPU backend expose ``n`` emulated devices.

    Must run before jax initialises — jax locks the device count on first
    init; calling this afterwards is a silent no-op for the current process
    (the flag still propagates to subprocesses).
    """
    _merge_xla_flag(_DEVCOUNT_FLAG, str(int(n)))


def emulated_device_count(default: int = 0) -> int:
    """The requested emulated host device count (``REPRO_EMULATED_DEVICES``),
    or ``default`` when unset/empty/invalid."""
    raw = os.environ.get(EMULATED_DEVICES_VAR, "").strip()
    if not raw:
        return default
    try:
        return max(int(raw), 0)
    except ValueError:
        return default


def apply_emulated_devices(default: int = 0) -> int:
    """Honour ``REPRO_EMULATED_DEVICES`` if set: force that many emulated
    host devices (before jax init!).  Returns the applied count (0 = left
    untouched)."""
    n = emulated_device_count(default)
    if n > 0:
        set_host_device_count(n)
    return n


def set_platform(platform: Optional[str] = None) -> None:
    """Pick the jax backend: "cpu", "gpu", "tpu", or None for jax's default.

    Safe to call before first use of jax (lazily imports it)."""
    import jax

    jax.config.update("jax_platform_name", platform)


def enable_x64(use_x64: bool = True) -> None:
    """Toggle float64/int64 as the default wide types (off = jax default).

    Reference numerics (e.g. float64-folded sweep scales) flip this per
    computation instead via ``jax.experimental.enable_x64``; this is the
    process-wide switch for scripts."""
    import jax

    jax.config.update("jax_enable_x64", use_x64)


# JAX reads this variable itself; when it is set, it names the cache.
COMPILE_CACHE_VAR = "JAX_COMPILATION_CACHE_DIR"

# The source checkout holding this module (src/repro/utils/platform.py).
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set the cache stays where it says
    and no directory is set here.  Otherwise the cache is the fixed
    ``.jax_cache`` of this source checkout: it never depends on a
    temporary name, a pid or the time.  Every program is cached however
    fast it compiled.  Call before the first compile: JAX decides once per
    process whether the cache is used.
    """
    import jax

    path = os.environ.get(COMPILE_CACHE_VAR, "")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def describe() -> dict:
    """A record of the effective platform config (for bench artifacts)."""
    import jax

    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "emulated_devices": emulated_device_count(),
    }
