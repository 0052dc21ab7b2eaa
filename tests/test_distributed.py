"""Multi-device semantics: runs a subprocess with 8 forced host devices and
asserts sharded results equal single-device references."""

import json
import os
import subprocess
import sys

import pytest

# full 8-device subprocess (LM train step, MoE, GNN, elastic ckpt): minutes
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.topk import sharded_topk
from repro.core.distributed import sharded_adc_topn, sharded_adc_topn_batch
from repro.kernels.pq_adc.ref import pq_adc_ref
from repro.models.layers import ShardCtx
from repro.sharding.spec import rules_for_mesh
from repro.launch.mesh import make_test_mesh

out = {}
mesh = make_test_mesh(8)
ctx = ShardCtx(mesh=mesh, rules=rules_for_mesh(mesh))
rng = np.random.default_rng(0)

# --- sharded_topk == global top_k ---
scores = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
s_sh = jax.device_put(scores, NamedSharding(mesh, P("data", "model")))
with mesh:
    v, i = jax.jit(lambda s: sharded_topk(s, 8, ctx, shard_axes="model",
                                          batch_axes="batch"))(s_sh)
rv, ri = jax.lax.top_k(scores, 8)
out["topk_vals_match"] = bool(np.allclose(np.asarray(v), np.asarray(rv), atol=1e-6))
gather_check = np.take_along_axis(np.asarray(scores), np.asarray(i), axis=1)
out["topk_ids_valid"] = bool(np.allclose(gather_check, np.asarray(rv), atol=1e-6))

# --- sharded ADC scan == reference scan ---
codes = jnp.asarray(rng.integers(0, 256, (1024, 8)), jnp.uint8)
lut = jnp.asarray(rng.random((8, 256)), jnp.float32)
codes_sh = jax.device_put(codes, NamedSharding(mesh, P(("data", "model"), None)))
with mesh:
    dv, di = jax.jit(lambda c, l: sharded_adc_topn(c, l, 32, ctx))(codes_sh, lut)
ref = np.asarray(pq_adc_ref(codes, lut))
out["adc_vals_match"] = bool(np.allclose(np.sort(np.asarray(dv)), np.sort(ref)[:32], rtol=1e-5))
out["adc_ids_match"] = bool(np.allclose(np.sort(ref[np.asarray(di)]), np.sort(ref)[:32], rtol=1e-5))

# --- batched scan ---
luts = jnp.asarray(rng.random((3, 8, 256)), jnp.float32)
with mesh:
    bv, bi = jax.jit(lambda c, l: sharded_adc_topn_batch(c, l, 16, ctx))(codes_sh, luts)
ok = True
for b in range(3):
    refb = np.sort(np.asarray(pq_adc_ref(codes, luts[b])))[:16]
    ok = ok and np.allclose(np.sort(np.asarray(bv[b])), refb, rtol=1e-5)
out["adc_batch_match"] = bool(ok)

# --- MoE under mesh == local ---
from repro.models import layers as L
from repro.configs.qwen3_moe_30b_a3b import REDUCED as moecfg
import dataclasses
cfg = dataclasses.replace(moecfg, capacity_factor=8.0)
x = jnp.asarray(rng.standard_normal((4, 8, cfg.d_model)), jnp.float32)
router = jnp.asarray(rng.standard_normal((cfg.d_model, cfg.n_experts)), jnp.float32)
w1 = jnp.asarray(0.1 * rng.standard_normal((cfg.n_experts, cfg.d_model, 2 * cfg.moe_d_ff)), jnp.float32)
w2 = jnp.asarray(0.1 * rng.standard_normal((cfg.n_experts, cfg.moe_d_ff, cfg.d_model)), jnp.float32)
local = L.moe_block(x, router, w1, w2, None, None, cfg=cfg, ctx=L.LOCAL_CTX)
x_sh = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
with mesh:
    dist = jax.jit(lambda *a: L.moe_block(*a, None, None, cfg=cfg, ctx=ctx))(x_sh, router, w1, w2)
out["moe_match"] = bool(np.allclose(np.asarray(local), np.asarray(dist), rtol=5e-4, atol=5e-4))

# replicated (decode) MoE mode
with mesh:
    x_rep = jax.device_put(x[:, :1], NamedSharding(mesh, P("data", None, None)))
    dist2 = jax.jit(lambda *a: L.moe_block(*a, None, None, cfg=cfg, ctx=ctx,
                                           seq_sharded=False))(x_rep, router, w1, w2)
local2 = L.moe_block(x[:, :1], router, w1, w2, None, None, cfg=cfg, ctx=L.LOCAL_CTX)
out["moe_decode_match"] = bool(np.allclose(np.asarray(local2), np.asarray(dist2), rtol=5e-4, atol=5e-4))

# --- dst-partitioned GNN == baseline full-graph forward ---
from repro.models import gnn
from repro.data.partition import partition_edges_by_dst
from repro.data.graphs import random_graph
from repro.configs.graphsage_reddit import REDUCED as gcfg
g = random_graph(rng, 64, 200, 16, 4)
params_g = gnn.init_sage(jax.random.key(1), gcfg, d_feat=16, n_classes=4)
feats = jnp.asarray(g["features"])
base = gnn.sage_forward_full(params_g, feats, jnp.asarray(g["edges"]), gcfg)
pe, pw = partition_edges_by_dst(g["edges"], 64, 8)
pe_sh = jax.device_put(jnp.asarray(pe), NamedSharding(mesh, P(("data", "model"), None)))
pw_sh = jax.device_put(jnp.asarray(pw), NamedSharding(mesh, P(("data", "model"))))
with mesh:
    dstp = jax.jit(lambda p, f, e, w: gnn.sage_forward_full_dstpart(
        p, f, e, w, gcfg, ctx))(params_g, feats, pe_sh, pw_sh)
# h1 crosses the mesh as bf16 bit-patterns (iteration B2) -> bf16 tolerance
out["gnn_dstpart_match"] = bool(np.allclose(np.asarray(base), np.asarray(dstp),
                                            rtol=3e-2, atol=3e-2))

# --- blocked batched ADC scan == per-query map ---
with mesh:
    bv2, bi2 = jax.jit(lambda c, l: sharded_adc_topn_batch(
        c, l, 16, ctx, blocked=True))(codes_sh, luts)
ok2 = True
for b in range(3):
    refb = np.sort(np.asarray(pq_adc_ref(codes, luts[b])))[:16]
    ok2 = ok2 and np.allclose(np.sort(np.asarray(bv2[b])), refb, rtol=1e-5)
out["adc_blocked_match"] = bool(ok2)

# --- elastic resharding: checkpoint under (2,4), restore under (4,2) ---
import tempfile
from repro.train import checkpoint as ckpt
from jax.sharding import Mesh
big = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
mesh_a = jax.make_mesh((2, 4), ("data", "model"))
mesh_b = jax.make_mesh((4, 2), ("data", "model"))   # data axis grew 2x
x_a = jax.device_put(big, NamedSharding(mesh_a, P("data", "model")))
d = tempfile.mkdtemp()
ckpt.save(d, 3, {"w": x_a})
proto = jax.eval_shape(lambda: {"w": big})
restored, step = ckpt.restore(
    d, proto, shardings={"w": NamedSharding(mesh_b, P("data", "model"))})
out["elastic_values_equal"] = bool(np.allclose(np.asarray(restored["w"]),
                                               np.asarray(big)))
out["elastic_resharded"] = bool(
    restored["w"].sharding.mesh.shape["data"] == 4 and step == 3)

# --- sharded LM train step runs + loss matches local ---
from repro.models.api import build_cell, realize
cell_l = build_cell("qwen3-0.6b", "train_4k", mesh=None, reduced=True)
args_l = realize(cell_l)
_, m_l = jax.jit(cell_l.fn)(*args_l)
cell_d = build_cell("qwen3-0.6b", "train_4k", mesh=mesh, reduced=True)
args_d = realize(cell_d)
args_d = jax.tree_util.tree_map(
    lambda a, s: jax.device_put(a, s) if s is not None else a,
    args_d, cell_d.in_shardings,
    is_leaf=lambda v: v is None or isinstance(v, jax.sharding.NamedSharding))
with mesh:
    _, m_d = jax.jit(cell_d.fn, in_shardings=cell_d.in_shardings)(*args_d)
out["lm_loss_match"] = bool(abs(float(m_l["loss"]) - float(m_d["loss"])) < 0.05)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # a CPU rehearsal: never reach for a chip
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, os.path.abspath(src)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("key", [
    "topk_vals_match", "topk_ids_valid", "adc_vals_match", "adc_ids_match",
    "adc_batch_match", "adc_blocked_match", "gnn_dstpart_match",
    "moe_match", "moe_decode_match", "lm_loss_match",
    "elastic_values_equal", "elastic_resharded",
])
def test_distributed(results, key):
    assert results[key], f"{key} failed: {results}"
