"""Logical sharding rules mapped onto physical mesh axes.

The framework uses a 2-D single-pod mesh ``("data", "model")`` and a 3-D
multi-pod mesh ``("pod", "data", "model")``.  Model code never names physical
axes directly; it asks the active :class:`Rules` for a logical axis:

  * ``batch``  — data parallel (pod x data on multi-pod meshes)
  * ``fsdp``   — weight sharding axis #1 (ZeRO-3 style; the "data" axis)
  * ``tensor`` — weight sharding axis #2 / sequence parallel axis ("model")
  * ``expert`` — expert parallel axis (aliases "tensor")
  * ``corpus`` — ANNS corpus row shards (all axes; the paper's pinned-HBM tier)

This keeps every model definition mesh-shape agnostic: the same code lowers on
1-device CPU test meshes, the 256-chip single pod and the 512-chip 2-pod mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical → physical axis mapping."""

    batch: Axis = "data"
    fsdp: Axis = "data"
    tensor: Axis = "model"
    expert: Axis = "model"
    corpus: Axis = ("data", "model")

    def spec(self, *logical: Optional[str]) -> P:
        """Build a PartitionSpec from logical axis names (None = replicated)."""
        out = []
        for name in logical:
            if name is None:
                out.append(None)
            else:
                out.append(getattr(self, name))
        return P(*out)


SINGLE_POD_RULES = Rules(
    batch="data",
    fsdp="data",
    tensor="model",
    expert="model",
    corpus=("data", "model"),
)

MULTI_POD_RULES = Rules(
    batch=("pod", "data"),
    fsdp="data",
    tensor="model",
    expert="model",
    corpus=("pod", "data", "model"),
)

# Single-device (tests / examples): everything replicated but specs stay valid
# because a (1, 1) mesh carries both axis names.
LOCAL_RULES = SINGLE_POD_RULES


def rules_for_mesh(mesh: Mesh) -> Rules:
    return MULTI_POD_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES


def local_rules_for_mesh(mesh: Mesh) -> Rules:
    """Rules used inside shard_map bodies (same mapping; kept for symmetry)."""
    return rules_for_mesh(mesh)


def shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the bodies
    here all_gather and merge explicitly)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def constrain(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that is a no-op outside jit-with-mesh."""
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
