"""Process-level backend configuration, applied BEFORE JAX initialises.

JAX reads ``XLA_FLAGS`` / ``JAX_PLATFORMS`` once, when the first backend is
created, and locks them for the life of the process.  Every entry point that
needs a non-default backend setup (the dry-run's 512 fake host devices, a
bench pinned to CPU, an experiment flipping an XLA knob) therefore has to
mutate ``os.environ`` before anything imports-and-uses jax — which each
script used to do ad hoc at the top of the file.

:class:`BackendConfig` centralises that dance: declare the platform, host
device count and extra XLA flags, then ``apply()`` exactly once, first thing
in ``main``.  ``apply`` refuses to run after JAX has initialised (a silent
no-op there is the worst failure mode: flags that look set but never reached
the compiler) and merges with any flags already in the environment — the
caller's CI matrix can still inject ``XLA_FLAGS`` from outside.

CLI entry points get the standard trio of arguments via :func:`add_args` /
:func:`from_args`::

    ap = argparse.ArgumentParser()
    backend.add_args(ap)
    args = ap.parse_args()
    backend.from_args(args).apply()
    import jax  # first jax use AFTER apply()
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Mapping, MutableMapping


def jax_initialised() -> bool:
    """Whether this process already created a JAX backend (flags locked)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return bool(getattr(xb, "_backends", None))


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Declarative XLA/JAX process setup.

    ``platform`` pins ``JAX_PLATFORMS`` ("cpu", "tpu", "gpu", or a
    comma-list of fallbacks); ``host_device_count`` is the dry-run's
    ``--xla_force_host_platform_device_count`` (fake CPU devices for mesh
    experiments); ``xla_flags`` are raw ``--xla_*`` strings appended last,
    so they win over both defaults and the inherited environment.
    """

    platform: str | None = None
    host_device_count: int | None = None
    xla_flags: tuple[str, ...] = ()

    def merged_xla_flags(self, env: Mapping[str, str]) -> str:
        """Inherited ``XLA_FLAGS`` + this config's flags (ours last)."""
        parts = [f for f in env.get("XLA_FLAGS", "").split() if f]
        if self.host_device_count is not None:
            parts = [
                f
                for f in parts
                if not f.startswith("--xla_force_host_platform_device_count=")
            ]
            parts.append(
                f"--xla_force_host_platform_device_count={self.host_device_count}"
            )
        parts.extend(self.xla_flags)
        return " ".join(parts)

    def apply(self, env: MutableMapping[str, str] | None = None) -> None:
        """Write the config into the process environment (idempotent).

        Raises if a JAX backend already exists: flags set now would be
        silently ignored, which is strictly worse than failing loudly.
        """
        if jax_initialised():
            raise RuntimeError(
                "BackendConfig.apply() called after JAX initialised a "
                "backend; XLA_FLAGS/JAX_PLATFORMS are already locked. "
                "Apply the config before the first jax use."
            )
        env = os.environ if env is None else env
        flags = self.merged_xla_flags(env)
        if flags:
            env["XLA_FLAGS"] = flags
        if self.platform is not None:
            env["JAX_PLATFORMS"] = self.platform


def add_args(ap) -> None:
    """Attach the standard backend CLI arguments to ``ap``."""
    ap.add_argument(
        "--platform",
        default=None,
        help="pin JAX_PLATFORMS (cpu | tpu | gpu | comma-list of fallbacks)",
    )
    ap.add_argument(
        "--xla-flag",
        action="append",
        default=[],
        metavar="--xla_...=v",
        help="extra XLA flag (repeatable); appended after inherited XLA_FLAGS",
    )
    ap.add_argument(
        "--host-device-count",
        type=int,
        default=None,
        help="fake host-platform device count (mesh dry-runs)",
    )


def from_args(args) -> BackendConfig:
    return BackendConfig(
        platform=args.platform,
        host_device_count=args.host_device_count,
        xla_flags=tuple(args.xla_flag),
    )


def use_compile_cache(default_dir: str) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself).  Otherwise the cache goes to ``default_dir`` — a fixed path,
    because the path is part of what a later process must find again.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
