"""Mesh construction.  Everything is a function — importing this module never
touches jax device state (jax locks the device count on first backend init,
and the dry-run needs to set XLA_FLAGS before that happens).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The production target: one v5e-class pod = a (16, 16) slice with axes
    (data, model); two pods add a leading "pod" axis over DCI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh with the same axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_worker_mesh(n_devices: int = 0, *, multi_pod: bool = False):
    """A mesh for the shard_map CoDA executor on whatever devices exist.

    All available devices (or the first ``n_devices``) go to the worker-
    carrying axes: ``(data, model=1)`` single-pod, ``(2, n/2, 1)`` multi-pod.
    On CPU hosts, set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (or use ``force_host_device_count``) *before* jax initialises its
    backend to get N > 1.
    """
    n = n_devices or len(jax.devices())
    if multi_pod:
        if n % 2:
            raise ValueError(f"multi_pod needs an even device count, got {n}")
        return jax.make_mesh((2, n // 2, 1), ("pod", "data", "model"))
    return jax.make_mesh((n, 1), ("data", "model"))


def force_host_device_count(n: int) -> None:
    """Ask XLA for ``n`` host (CPU) devices.  Must run before the first
    backend touch — jax locks the device count on first init, so drivers
    call this at the top of main() (see launch/train.py, benchmarks/run.py).
    """
    import os
    import re
    import sys

    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "--xla_force_host_platform_device_count" in flags:
        new = re.sub(r"--xla_force_host_platform_device_count=\d+", flag,
                     flags)
        if new != flags:
            print(f"warning: XLA_FLAGS already forced a host device count; "
                  f"overriding to {n}", file=sys.stderr)
            os.environ["XLA_FLAGS"] = new
    else:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


def abstract_mesh(shape, axis_names):
    """A device-free ``AbstractMesh`` with these axis sizes and names."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axis_names))


def coda_worker_axes(policy: str, multi_pod: bool):
    """Which mesh axes the CoDA worker (replica) axis is sharded over.

    * replica — every worker is one `model`-axis group: K = pod × data.
    * fsdp    — the giant-MoE policy: a worker spans (data × model); only the
      pod axis carries workers (K = 2 multi-pod, K = 1 single-pod = PPD-SG).
    """
    if policy == "replica":
        return ("pod", "data") if multi_pod else ("data",)
    if policy == "fsdp":
        return ("pod",) if multi_pod else ()
    raise ValueError(policy)


def n_workers(mesh, policy: str) -> int:
    axes = coda_worker_axes(policy, multi_pod="pod" in mesh.axis_names)
    k = 1
    for a in axes:
        k *= mesh.shape[a]
    return max(k, 1)
