"""Multi-host launch glue for real TPU pods.

On a v5e pod each host runs this same program; `init_distributed()` wires
jax.distributed from the scheduler environment (GKE/TPU-VM metadata or
explicit env), after which `jax.devices()` spans the pod.  Per-host data
sharding follows `host_batch_slice`.

This container has a single process; the functions degrade to no-ops so
every launcher works unchanged locally (unit-tested)."""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Initialise jax.distributed from args or environment.

    Env fallbacks: COORDINATOR_ADDRESS, NUM_PROCESSES, PROCESS_ID (generic),
    or TPU-VM metadata handled natively by jax when nothing is set."""
    coordinator = coordinator or os.environ.get("COORDINATOR_ADDRESS")
    num = num_processes or int(os.environ.get("NUM_PROCESSES", "0")) or None
    pid = process_id if process_id is not None else (
        int(os.environ["PROCESS_ID"]) if "PROCESS_ID" in os.environ else None)
    if coordinator is None and num is None:
        return                      # single-process (local/dev)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num, process_id=pid)


def host_batch_slice(global_batch: int) -> Tuple[int, int]:
    """(start, size) of this host's slice of the global batch — the data
    pipeline loads only its slice (per-host sharded input)."""
    n_proc = jax.process_count()
    assert global_batch % n_proc == 0, (global_batch, n_proc)
    per = global_batch // n_proc
    return jax.process_index() * per, per


def local_device_put_sharded(global_arrays, shardings):
    """Place per-host array slices as a global jax.Array
    (jax.make_array_from_process_local_data wrapper)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.make_array_from_process_local_data(s, x),
        global_arrays, shardings)


def is_coordinator() -> bool:
    return jax.process_index() == 0
