"""Platform plumbing shared by the dryrun and test harnesses: virtual CPU
devices and the persistent compile cache.

One home for the "N virtual CPU devices" recipe (the reference's analog is
`local[N]` Spark in `BaseSparkTest.java:89`): XLA_FLAGS gets
`--xla_force_host_platform_device_count=N` and the platform is forced to CPU.
A process that must not take the accelerator (tests, any
child of a parent that already holds the chip) forces the CPU this way; jax
config beats the environment, so the in-process variant calls
`jax.config.update("jax_platforms", "cpu")` BEFORE the first `jax.devices()`.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

__all__ = ["child_env_with_virtual_devices", "provision_virtual_devices",
           "enable_compilation_cache", "COMPILE_CACHE_DIR"]

# <checkout>/.jax_cache — fixed, because the directory is part of the cache
# key's environment: a path that moves (home, temp name, pid) never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_FLAG_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def _with_flag(flags: str, n_devices: int) -> str:
    """Ensure XLA_FLAGS requests at least n_devices virtual devices — an
    existing smaller count is raised (leaving it would make provisioning
    N devices silently impossible); a larger one is kept."""
    m = _FLAG_RE.search(flags)
    if m:
        if int(m.group(1)) >= n_devices:
            return flags
        return _FLAG_RE.sub(
            f"--xla_force_host_platform_device_count={n_devices}", flags)
    return (flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()


def child_env_with_virtual_devices(n_devices: int,
                                   base: Optional[Dict[str, str]] = None
                                   ) -> Dict[str, str]:
    """A copy of the environment configured so a CHILD process sees
    `n_devices` virtual CPU devices. Does not mutate os.environ."""
    env = dict(os.environ if base is None else base)
    env["XLA_FLAGS"] = _with_flag(env.get("XLA_FLAGS", ""), n_devices)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def provision_virtual_devices(n_devices: int) -> bool:
    """Make THIS process see >= n_devices devices, forcing the virtual CPU
    platform when needed. Returns True on success, False if the jax backend
    was already initialized with too few devices (caller must re-exec with
    `child_env_with_virtual_devices`). Restores os.environ afterwards — the
    backend snapshots flags at initialization, so later subprocesses are not
    silently pinned to CPU."""
    old_flags = os.environ.get("XLA_FLAGS")
    old_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["XLA_FLAGS"] = _with_flag(old_flags or "", n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax

        # config wins over JAX_PLATFORMS; once the backend is initialized
        # the update has no effect and the device count below says so
        jax.config.update("jax_platforms", "cpu")
        return len(jax.devices()) >= n_devices
    finally:
        for key, old in (("XLA_FLAGS", old_flags),
                         ("JAX_PLATFORMS", old_platforms)):
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    Call before the first compile; importing the package sets nothing.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    function sets no directory. Where it is not, the cache goes to
    `COMPILE_CACHE_DIR` inside the checkout. A cache that cannot be enabled
    raises (OSError from the directory) — nothing downgrades silently."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
