"""Logical sharding rules mapped onto physical mesh axes.

A single-host mesh is ``("data", "model")`` and a multi-pod mesh is
``("pod", "data", "model")``.  Scan code never names physical axes
directly; it asks the active :class:`Rules` for a logical axis:

  * ``batch``  — query batch (pod x data on multi-pod meshes)
  * ``corpus`` — ANNS corpus row shards (all axes; the paper's pinned-HBM tier)

so the same code lowers on 1-device CPU test meshes and on any chip mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    """Logical → physical axis mapping."""

    batch: Axis = "data"
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
    corpus=("data", "model"),
)

MULTI_POD_RULES = Rules(
    batch=("pod", "data"),
    corpus=("pod", "data", "model"),
)


def rules_for_mesh(mesh: Mesh) -> Rules:
    return MULTI_POD_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + logical rules threaded through the scan code (None = local)."""

    mesh: Optional[Mesh] = None
    rules: Rules = Rules()

    def constrain(self, x, *logical):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, self.rules.spec(*logical)))


LOCAL_CTX = ShardCtx()


def shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the bodies
    here all_gather and merge explicitly)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
