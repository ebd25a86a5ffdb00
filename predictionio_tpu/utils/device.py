"""What the program asks the accelerator, in one place.

Nothing here falls back: a question the backend cannot answer raises, so
a run that was meant for the chip never carries on somewhere else.
"""

from __future__ import annotations

import os
import sys
from typing import List, Optional, Sequence

#: persistent XLA compilation cache when the environment names none — a
#: fixed path inside the checkout (the directory is part of what makes a
#: later process find the entries again, so never a temp dir or a pid)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _set_jax_option(name: str, value) -> None:
    """Set a JAX config option for this process and its children: through
    the environment (read when jax is first imported, and inherited by
    every process spawned from here), and through ``jax.config`` when
    jax is already imported and has stopped reading the environment."""
    os.environ[name.upper()] = str(value)
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update(name, value)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory a later
    process will find again, and return that directory. Call before the
    process compiles anything (CLI group, driver entry).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the code
    sets no other directory. Unset: `DEFAULT_COMPILE_CACHE_DIR`.

    The minimum compile time for an entry is set to 0 (JAX's default of
    1 s would skip exactly the programs this repo compiles most of: one
    sub-second top-k program per serving batch bucket). Every fresh
    machine starts cold, the entries are small, and a write costs far
    less than the compile it saves.
    """
    _set_jax_option("jax_persistent_cache_min_compile_time_secs", 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _set_jax_option("jax_compilation_cache_dir",
                        DEFAULT_COMPILE_CACHE_DIR)
    return compile_cache_dir()


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lies, or would lie, for
    this process: `enable_compile_cache`'s answer without turning the
    cache on. What the program writes beside its compiled programs
    (`obs/profiler`'s scope tables) goes under it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE_DIR


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU — the one predicate
    behind every TPU-only code path (Pallas kernels, bf16 MXU operands)."""
    import jax

    return jax.default_backend() == "tpu"


def describe_devices(devices: Sequence) -> str:
    """``platform=tpu device_kind='TPU v5 lite' n_devices=1`` — the
    device as JAX reports it, for log lines a run is judged by."""
    first = devices[0]
    return (f"platform={first.platform} device_kind={first.device_kind!r} "
            f"n_devices={len(devices)}")


def bytes_in_use(devices: Sequence) -> List[Optional[int]]:
    """Per-device allocator bytes in use, in `devices` order (None where
    the backend keeps no statistics, as the CPU client does not)."""
    out: List[Optional[int]] = []
    for d in devices:
        stats = d.memory_stats()
        out.append(int(stats["bytes_in_use"]) if stats else None)
    return out


def memory_limit_bytes(devices: Sequence) -> int:
    """The smallest per-device memory limit among `devices`, as each
    device reports it. Raises where a device reports none: a budget
    guessed for an unknown chip is how a model lands on the wrong path."""
    limits = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "bytes_limit" not in stats:
            raise RuntimeError(
                f"{d.platform} device {d.id} ({d.device_kind}) reports no "
                "memory limit (memory_stats() has no bytes_limit)")
        limits.append(int(stats["bytes_limit"]))
    return min(limits)
