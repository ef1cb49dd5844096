"""Process-environment helpers for launch scripts. NO jax imports here —
these must run *before* the first jax import to have any effect.

The trap this module exists for: ``XLA_FLAGS`` is a single
space-separated string, so the obvious

    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --flag=N"

appends a duplicate every invocation (re-exec, test re-import, a wrapper
script that already set the flag), and XLA's flag parser rejects or
silently last-wins on duplicates depending on version.  And a plain
``setdefault`` of the whole string silently drops the new flag when the
variable exists with *other* flags in it.  :func:`set_xla_flag` is the
per-flag setdefault both launch CLIs and the examples should use.

:func:`use_compile_cache` places JAX's persistent compilation cache the
same way for every entry point: where the caller's environment names a
directory, that one; otherwise one fixed directory in the checkout."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["set_xla_flag", "force_host_devices", "use_compile_cache"]

# <checkout>/.cache/jax-compile — .gitignore lists .cache/.  A fixed
# path: the cache directory is part of what a later run looks up, so a
# temp name, pid or timestamp would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".cache" / \
    "jax-compile"


def set_xla_flag(name: str, value, env=os.environ) -> bool:
    """Idempotent per-flag setdefault into ``XLA_FLAGS``.

    Adds ``--<name>=<value>`` unless a ``--<name>=...`` entry is already
    present (any value — an existing caller-chosen value wins, matching
    ``setdefault`` semantics).  Returns True if the flag was added.
    Must be called before the first jax import."""
    prefix = f"--{name}="
    existing = env.get("XLA_FLAGS", "")
    if any(tok.startswith(prefix) for tok in existing.split()):
        return False
    env["XLA_FLAGS"] = f"{existing} {prefix}{value}".strip()
    return True


def force_host_devices(n: int, env=os.environ) -> bool:
    """Force ``n`` virtual CPU devices (the multidevice-on-CPU harness
    every launch CLI exposes as ``--force-host-devices``).  No-op when
    the flag is already set, so wrappers and re-imports stay safe."""
    return set_xla_flag("xla_force_host_platform_device_count", int(n),
                        env=env)


def use_compile_cache(env=os.environ) -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (left
    untouched), else at :data:`COMPILE_CACHE_DIR`.  JAX reads the
    variable when it is first imported, so call this before that.
    Returns the directory in use."""
    return env.setdefault("JAX_COMPILATION_CACHE_DIR", str(COMPILE_CACHE_DIR))
