"""Process-level JAX set-up shared by every entry point.

- :func:`init_compile_cache` — where the persistent compilation cache
  lives. Called once by each entry point that builds an engine, before the
  first compile.
- :func:`on_tpu` — the one platform test behind every "compiled Pallas
  kernel or not" decision (engine ``attn_impl="auto"``, the kernels'
  ``interpret`` default, ``__graft_entry__.entry``).
- :func:`force_cpu` / :func:`cpu_env` — the CPU rig of the test suite and
  the multi-chip dry run (N virtual host devices).

XLA parses ``--xla_force_host_platform_device_count`` once per process, at
first backend creation: growing the device count after a backend exists is
impossible in-process. :func:`force_cpu` therefore reports whether the live
process satisfies the request so callers can re-exec in a fresh interpreter
when it does not.
"""

from __future__ import annotations

import os
import re
from typing import Optional

_FLAG = "xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The persistent compile cache directory: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else ``<checkout>/.jax_cache``. The path
    is part of every cache key, so it is never a temp name, pid or
    timestamp. Does not import jax (a parent that must stay off the chip
    can still report the directory)."""
    return os.environ.get(_CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def init_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on for this process and return its
    directory (None when it stays off). With ``JAX_COMPILATION_CACHE_DIR``
    set jax already reads the path from the environment and no path is set
    in code. Otherwise the in-checkout path is set — unless the process was
    told to run on the CPU (``JAX_PLATFORMS=cpu``, the test rig): CPU
    compiles are cheap, and XLA:CPU logs a machine-feature error for every
    entry it loads back. The minimum compile time is dropped to zero so
    every bucket program is kept (the default keeps only compiles over a
    second), and call-stack frames are kept out of program locations so a
    program's key does not depend on who traced it. Touches no backend, so
    it is safe before
    ``jax.distributed.initialize``; call it before the first compile."""
    import jax

    if not os.environ.get(_CACHE_ENV):
        if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Pallas kernel is serialised into its custom call together with its
    # MLIR locations, and by default those carry up to ten Python frames of
    # the tracing call stack — which reaches into the entry point. Left on,
    # the same bucket program keys differently under cli.run, cli.worker and
    # a library caller, and a cache filled by one is cold for the others
    # (measured on the chip: 15 of 15 bucket programs missed, PERF.md PR 21).
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return compile_cache_dir()


def on_tpu(device=None) -> bool:
    """Is ``device`` (default: this process's first device) a TPU?

    Keyed on the device itself — ``platform`` or, for a backend registered
    under another platform name, a ``device_kind`` that says TPU — never on
    ``jax.default_backend()``'s name, so a renamed backend cannot silently
    select interpreted kernels or dense attention."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return (device.platform == "tpu"
            or device.device_kind.upper().startswith("TPU"))


def _xla_flags_with_count(n: int) -> str:
    """``XLA_FLAGS`` from the environment, raised to >= n host devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if not m:
        return (flags + f" --{_FLAG}={n}").strip()
    if int(m.group(1)) < n:
        return re.sub(rf"--{_FLAG}=\d+", f"--{_FLAG}={n}", flags)
    return flags


def cpu_env(n: int) -> dict:
    """Environment of a CPU process with >= n virtual devices. Env-only —
    safe before jax is imported, and what a parent hands a child it
    re-executes on the CPU rig."""
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": _xla_flags_with_count(n)}


def cpu_env_ready(n: int) -> bool:
    """Does this process's environment already equal :func:`cpu_env`? Lets
    a parent decide to re-exec without importing jax."""
    return all(os.environ.get(k) == v for k, v in cpu_env(n).items())


def force_cpu(n_devices: int = 1) -> bool:
    """Ask for the cpu platform with >= n_devices virtual devices.

    Returns True when this process now sees enough CPU devices; False when a
    backend was already initialized on another platform or with fewer
    devices (the flag is parsed once per process — the caller must re-exec
    in a fresh interpreter, which inherits the environment set here).
    """
    import jax

    os.environ.update(cpu_env(n_devices))
    # jax read JAX_PLATFORMS when it was imported; a live backend ignores
    # the update and keeps its devices
    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    return devices[0].platform == "cpu" and len(devices) >= n_devices
