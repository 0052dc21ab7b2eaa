"""Multi-device semantics: runs a subprocess with 8 forced host devices and
asserts sharded results equal single-device references."""

import json
import os
import subprocess
import sys

import pytest

# full 8-device subprocess (sharded top-k and ADC scans)
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
from repro.sharding import ShardCtx, rules_for_mesh
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

# --- blocked batched ADC scan == per-query map ---
with mesh:
    bv2, bi2 = jax.jit(lambda c, l: sharded_adc_topn_batch(
        c, l, 16, ctx, blocked=True))(codes_sh, luts)
ok2 = True
for b in range(3):
    refb = np.sort(np.asarray(pq_adc_ref(codes, luts[b])))[:16]
    ok2 = ok2 and np.allclose(np.sort(np.asarray(bv2[b])), refb, rtol=1e-5)
out["adc_blocked_match"] = bool(ok2)

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
    "adc_batch_match", "adc_blocked_match",
])
def test_distributed(results, key):
    assert results[key], f"{key} failed: {results}"
