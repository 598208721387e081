"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init; smoke
tests see the default 1 device).

Topology (TPU v5e-class):
  single-pod:  (16, 16)    = ("data", "model")   — 256 chips
  multi-pod:   (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
               axis is an outer data-parallel axis whose collectives cross
               the (slower, DCN-class) inter-pod links. Keeping "model"
               innermost aligns tensor-parallel collectives with the
               fastest ICI dimension.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            f"sets this before importing jax)")
    return jax.make_mesh(shape, axes, devices=devs[:need])


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    devs = jax.devices()
    d = data if data is not None else max(1, len(devs) // model)
    need = d * model
    return Mesh(np.asarray(devs[:need]).reshape(d, model), ("data", "model"))


def make_shard_mesh(shards: int, replicas: int = 1):
    """Mesh for sharded GNN serving (DESIGN.md §12, §15).

    `replicas=1` (the default) builds the 1-D ("shard",) mesh — one device
    per graph shard. `replicas=R > 1` builds the R x S replica-group mesh
    ("replica", "shard"): R concurrent batches of the SAME shard layout,
    each replica row owning its own S-device column set, so halo psums
    (over "shard") stay within a replica. Raises when the host exposes too
    few devices (the sharded plan then falls back to a vmap-simulated
    axis, which computes the identical collective math on one device —
    CI's multi-device leg runs the real SPMD placement under
    XLA_FLAGS=--xla_force_host_platform_device_count=8).
    """
    devs = jax.devices()
    need = shards * replicas
    if len(devs) < need:
        raise RuntimeError(
            f"shard mesh needs {need} devices, found {len(devs)}")
    if replicas == 1:
        return Mesh(np.asarray(devs[:shards]), ("shard",))
    return Mesh(np.asarray(devs[:need]).reshape(replicas, shards),
                ("replica", "shard"))
