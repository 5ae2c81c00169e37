"""Process-level JAX setup: the persistent compile cache for every process
that steps the kernel, and the CPU pin for the harnesses that ask for one.

Nothing here chooses a platform on a program's behalf. A served member
(`python -m etcd_tpu --engine-groups ...`) runs on whatever backend JAX
finds — the TPU where there is one — and fails at start-up where JAX
cannot reach it. `force_cpu` is for the test suite and the explicit CPU
harnesses (virtual-device dry runs, soaks): they call it before anything
touches a backend.
"""
from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# Persistent XLA compile cache, shared by every process started from this
# checkout. The path is part of nothing's key but must not move between
# runs, so it is fixed (and git-ignored) — never a temp/pid/time path.
# One TPU compile of a serving step takes ~85 s at G=12,500 (CHANGES.md,
# PR 21), and an engine compiles up to three variants.
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX has already read it into
    its config and no directory is set in code; otherwise the cache lives
    in <checkout>/.jax_cache. A cache that cannot be set up is an error.
    Safe to call more than once, before or after backend init."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def set_host_device_count(n: int) -> None:
    """Set (or raise to n) the virtual CPU device count in XLA_FLAGS.
    Only effective before the CPU backend is instantiated."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags[:m.start(1)] + str(n) + flags[m.end(1):]
    os.environ["XLA_FLAGS"] = flags


def force_cpu(n_devices: int = 1):
    """Pin JAX to >= n_devices virtual CPU devices and return them. For
    tests and explicit CPU harnesses only; call it before anything
    instantiates a backend (the device count is read at CPU-client
    creation). The env var covers subprocesses, the config update covers
    a jax that was imported before this ran."""
    set_host_device_count(n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise RuntimeError(
            f"cannot get {n_devices} cpu devices: have "
            f"{len(devs)} x {devs[0].platform} (a backend was "
            "instantiated before force_cpu ran)")
    return devs
