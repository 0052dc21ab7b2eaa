"""``ShardCtx``: the mesh and logical rules the scan code reads.  With no
mesh it is a no-op; on a mesh the executor shards the PQ codes' rows over
every axis the ``corpus`` rule names."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.sharding import (LOCAL_CTX, MULTI_POD_RULES, SINGLE_POD_RULES,
                            ShardCtx, rules_for_mesh)


def test_local_ctx_has_no_mesh_and_constrain_is_identity():
    assert LOCAL_CTX.mesh is None and LOCAL_CTX == ShardCtx()
    x = jnp.arange(12.0).reshape(3, 4)
    assert LOCAL_CTX.constrain(x, "corpus", None) is x


def test_rules_follow_the_mesh_axes():
    dev = np.asarray(jax.devices()[:1])
    single = Mesh(dev.reshape(1, 1), ("data", "model"))
    pod = Mesh(dev.reshape(1, 1, 1), ("pod", "data", "model"))
    assert rules_for_mesh(single) == SINGLE_POD_RULES
    assert rules_for_mesh(pod) == MULTI_POD_RULES
    for mesh in (single, pod):
        # the corpus is row-sharded over every axis of the mesh
        assert rules_for_mesh(mesh).spec("corpus", None)[0] == \
            mesh.axis_names


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses, json
import numpy as np
from repro.configs.anns_datasets import SIFT_SMALL
from repro.core.engine import FusionANNSIndex
from repro.data.synthetic import clustered_vectors
from repro.launch.mesh import make_test_mesh

rng = np.random.default_rng(0)
cfg = dataclasses.replace(SIFT_SMALL, n_vectors=401, dim=16, pq_m=4,
                          n_posting_fraction=0.02)
index = FusionANNSIndex.build(clustered_vectors(rng, 401, 16, n_clusters=4),
                              cfg)
ex = index.executor
mesh = make_test_mesh(2)
ex.attach_mesh(mesh)
corpus = ex.ctx.rules.corpus
placed = ex._device_codes(index.codes)
rows = sorted(s.data.shape[0] for s in placed.addressable_shards)
print(json.dumps({
    "n_shards": [ex._n_shards(), int(np.prod([mesh.shape[a] for a in corpus]))],
    "rows": [rows, [201, 201]],
    "spec": [list(placed.sharding.spec[0]), list(corpus)],
}))
"""


@pytest.fixture(scope="module")
def on_two_devices():
    """Two forced host devices need the flag before jax is imported."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # a CPU rehearsal: never reach for a chip
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, os.path.abspath(src)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_executor_shard_count_is_the_corpus_axes_product(on_two_devices):
    got, want = on_two_devices["n_shards"]
    assert got == want == 2


@pytest.mark.parametrize("key", ["rows", "spec"])
def test_codes_land_row_sharded(on_two_devices, key):
    """401 code rows pad to 402 and split 201 / 201 along the corpus axes."""
    got, want = on_two_devices[key]
    assert got == want
