"""Meshes for the scan: a small test mesh over whatever devices exist, and
the disjoint device groups that multi-replica serving carves from one mesh.
Building a mesh is a FUNCTION call, never a module-level constant, so
importing this module does not touch jax device state."""

from __future__ import annotations

from typing import List

import jax
import numpy as np


def make_test_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0 and n >= 8
        return jax.make_mesh((2, 2, n // 4), ("pod", "data", "model"))
    if n == 1:
        return jax.make_mesh((1, 1), ("data", "model"))
    return jax.make_mesh((2, n // 2), ("data", "model"))


def split_mesh(mesh, n_replicas: int) -> List[jax.sharding.Mesh]:
    """Carve ``mesh`` into ``n_replicas`` DISJOINT sub-meshes (multi-replica
    serving: each replica's executor row-shards the corpus over its own
    device group, so per-replica ADC scans never contend for a chip).

    The leading mesh axis is split when divisible; otherwise the device
    array is flattened and re-folded so any ``n_replicas`` dividing the
    device count works.  Every sub-mesh keeps the parent's axis names
    (sharding rules and ``corpus``-axis specs stay valid unchanged)."""
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    if n_replicas == 1:
        return [mesh]
    devs = np.asarray(mesh.devices)
    total = devs.size
    if total % n_replicas:
        raise ValueError(
            f"cannot split {total} devices into {n_replicas} replicas")
    per = total // n_replicas
    if devs.shape[0] % n_replicas == 0:
        groups = np.split(devs, n_replicas, axis=0)
    else:                      # re-fold: (n_replicas, 1, ..., per)
        shape = (1,) * (devs.ndim - 1) + (per,)
        groups = [g.reshape(shape)
                  for g in np.split(devs.reshape(-1), n_replicas)]
    return [jax.sharding.Mesh(g, mesh.axis_names) for g in groups]


def recarve_mesh(mesh, n_groups: int) -> List[jax.sharding.Mesh]:
    """Re-carve ``mesh`` into ``n_groups`` disjoint sub-meshes for an
    ELASTIC replica set (serve/autoscaler.py): unlike :func:`split_mesh`,
    ``n_groups`` need not divide the device count — the flattened device
    list is cut into contiguous near-equal groups (sizes differ by at
    most one), so the autoscaler can move 8 devices between 3 and 4
    replicas without a rebuild.  Equal divisions keep :func:`split_mesh`
    semantics exactly (same grouping, same axis folding).  Every sub-mesh
    keeps the parent's axis names, so ``corpus``-axis sharding specs stay
    valid; an executor re-attached to its new group
    (``QueryExecutor.attach_mesh``) re-places its HBM shard on the next
    dispatch."""
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    devs = np.asarray(mesh.devices)
    total = devs.size
    if n_groups > total:
        raise ValueError(
            f"cannot carve {total} device(s) into {n_groups} groups")
    if total % n_groups == 0:
        return split_mesh(mesh, n_groups)
    flat = devs.reshape(-1)
    base, extra = divmod(total, n_groups)
    groups, at = [], 0
    for gi in range(n_groups):
        size = base + (1 if gi < extra else 0)
        shape = (1,) * (devs.ndim - 1) + (size,)
        groups.append(flat[at:at + size].reshape(shape))
        at += size
    return [jax.sharding.Mesh(g, mesh.axis_names) for g in groups]
