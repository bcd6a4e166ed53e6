"""Single-machine multi-host simulation: the XLA host-device-count preamble.

JAX locks the device count at first backend initialisation, so the
``--xla_force_host_platform_device_count=N`` flag MUST be in ``XLA_FLAGS``
before anything touches a backend (importing jax is fine; calling
``jax.devices()`` is not).  Every simulated-topology entry point used to
copy-paste that two-line trap; this module is the one place it lives:

* ``force_host_device_count(n)``   — in-process: mutate ``XLA_FLAGS`` (call
  it before importing anything that initialises jax — module top, like
  ``launch/dryrun.py``).
* ``simulated_env(n)``             — subprocess: a patched environment for
  worker processes (used by ``tests/test_multidevice.py`` /
  ``tests/test_multihost.py`` and the scaling bench's CI job).

Stdlib-only on purpose: importing this module never imports jax, so the
flag always lands before the backend can come up.
"""
from __future__ import annotations

import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def host_device_flags(n: int, base: str = "") -> str:
    """``base`` XLA_FLAGS with the host-device-count flag forced to ``n``."""
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = [f for f in base.split() if not f.startswith(_FLAG + "=")]
    flags.append(f"{_FLAG}={n}")
    return " ".join(flags)


def forced_host_device_count(env=None) -> int | None:
    """The forced count already present in ``XLA_FLAGS``, or None."""
    env = os.environ if env is None else env
    for flag in env.get("XLA_FLAGS", "").split():
        if flag.startswith(_FLAG + "="):
            try:
                return int(flag.split("=", 1)[1])
            except ValueError:
                return None
    return None


def force_host_device_count(n: int) -> None:
    """Make this process see ``n`` simulated CPU devices.

    Must run before the first jax backend init.  If jax is already imported
    the call can still be fine (import alone does not lock the count), but a
    backend that already came up ignores the flag — raise loudly in the one
    detectable slice of that window instead of silently simulating nothing.
    """
    jaxlib = sys.modules.get("jax")
    if jaxlib is not None:
        try:
            backends = sys.modules["jax._src.xla_bridge"]._backends  # type: ignore[union-attr]
        except (KeyError, AttributeError):
            backends = None
        if backends:
            raise RuntimeError(
                "force_host_device_count called after a jax backend "
                "initialised; set XLA_FLAGS before first device use "
                "(see launch/dryrun.py for the import-order contract)"
            )
    os.environ["XLA_FLAGS"] = host_device_flags(
        n, os.environ.get("XLA_FLAGS", "")
    )


def simulated_env(n: int, base_env=None, *, pythonpath: str | None = None):
    """A subprocess environment simulating ``n`` host devices.

    Copies ``base_env`` (default ``os.environ``), pins the child to the CPU
    backend (``JAX_PLATFORMS=cpu``: a simulated child must never reach for
    an accelerator its parent may hold), forces the device count in
    ``XLA_FLAGS``, and optionally prepends ``pythonpath`` — the exact recipe
    the multi-device test harnesses spawn workers with.
    """
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = host_device_flags(n, env.get("XLA_FLAGS", ""))
    if pythonpath is not None:
        old = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = (
            pythonpath + os.pathsep + old if old else pythonpath
        )
    return env
