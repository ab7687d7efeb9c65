"""Virtual CPU devices for child processes.

The multi-device tools (tools/graphlint.py, tools/graphcheck.py,
tools/chaos.py, ``__graft_entry__.dryrun_multichip``) certify sharded
programs on N virtual CPU devices. A process gets those from its
environment alone — ``JAX_PLATFORMS=cpu`` plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — so a parent that
needs them decides from the environment and spawns a child with it set.
It must not ask ``jax.devices()`` first: that initializes the backend, and
on a TPU host the parent would then hold the chip."""

from __future__ import annotations

import os
import re
import subprocess
import sys

_DEVICE_COUNT_FLAG = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def has_virtual_cpu_devices(n_devices: int) -> bool:
    """Whether this process's environment already provides at least
    ``n_devices`` CPU devices (read from the environment, never from jax)."""
    if n_devices > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    count = _DEVICE_COUNT_FLAG.search(os.environ.get("XLA_FLAGS", ""))
    return n_devices <= (int(count.group(1)) if count else 1)


def run_with_virtual_devices(n_devices: int, args, **run_kwargs) -> subprocess.CompletedProcess:
    """``subprocess.run`` of this interpreter on ``args`` in an environment
    that gives it ``n_devices`` virtual CPU devices; ``run_kwargs`` pass
    through (cwd, timeout, ...)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = _DEVICE_COUNT_FLAG.sub("", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={n_devices}".strip()
    return subprocess.run([sys.executable, *args], env=env, **run_kwargs)


def ensure_cli_virtual_devices(n_devices: int, script: str) -> None:
    """Re-run the CLI ``script`` with ``n_devices`` virtual CPU devices,
    forwarding ``sys.argv[1:]``, and exit with the child's return code; no-op
    when the environment already provides them (which is also what stops the
    child from respawning)."""
    if has_virtual_cpu_devices(n_devices):
        return
    raise SystemExit(
        run_with_virtual_devices(n_devices, [os.path.abspath(script), *sys.argv[1:]]).returncode
    )
