"""Compile the serving path's programs for a described TPU v5e, with no chip.

Nothing here runs: each test lowers and compiles one program at the
deployment's widths (SIFT: 128-d, M = 32; SPACEV M = 25; DEEP M = 24;
K = 256) for a chip of a described ``v5e:2x2`` topology, and checks what
the TPU compiler says about it — that it compiles, what it keeps in device
memory, which collectives the mesh programs use.

The topology is described inside a module fixture and never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9            # one v5e chip
N_CODES = 1_000_000               # the smoke deployment (SIFT1M)
B = 8                             # ServingStackConfig.scan_window
# dense-path candidate bucket: a SIFT1M scan window's union is ~3.5k rows
# (XLA:CPU rehearsal over 256 queries), padded to a power of two
BUCKET = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:       # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("m,s", [(32, 1024), (32, 8192), (25, 1024),
                                 (24, 1024)])
def test_fused_rows_scan_compiles(one_chip, m, s):
    from repro.kernels.pq_adc.ops import _fused_rows_scan
    compiled = _fused_rows_scan.lower(
        _spec((N_CODES, m), jnp.uint8, one_chip),
        _spec((16, m, 256), jnp.float32, one_chip),
        _spec((16, s), jnp.int32, one_chip), topk=512).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_dense_bucket_scan_fits_one_chip(one_chip):
    from repro.kernels.pq_adc.ops import pq_adc_topk_batch
    compiled = pq_adc_topk_batch.lower(
        _spec((BUCKET, 32), jnp.uint8, one_chip),
        _spec((B, 32, 256), jnp.float32, one_chip), 512,
        mask=_spec((B, BUCKET), jnp.bool_, one_chip),
        use_kernel=False).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_pq_encode_compiles_at_sift1m(one_chip):
    from repro.core import pq
    compiled = pq._encode.lower(
        _spec((32, 256, 4), jnp.float32, one_chip),
        _spec((N_CODES, 128), jnp.float32, one_chip)).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("fn", ["_nearest", "_polish_sums"])
def test_build_assignment_compiles(one_chip, fn):
    """The build's N x C passes, at SIFT1M's 20,000 posting lists."""
    from repro.core import clustering
    blk = _spec((clustering._ROWS, 128), jnp.float32, one_chip)
    cents = _spec((20_000, 128), jnp.float32, one_chip)
    if fn == "_nearest":
        lowered = clustering._nearest.lower(blk, cents, r=8)
    else:
        lowered = clustering._polish_sums.lower(
            blk, _spec((clustering._ROWS,), jnp.bool_, one_chip), cents)
    assert _device_bytes(lowered.compile()) < HBM_BYTES


def _mesh_ctx(mesh):
    from repro.sharding import ShardCtx, rules_for_mesh
    return ShardCtx(mesh=mesh, rules=rules_for_mesh(mesh))


def test_dense_window_scan_fits_one_chip(one_chip):
    """The executor's one-device dense program: bucket gather from the
    resident codes, scan, per-query top-n."""
    from repro.core.distributed import _window_scan_one
    compiled = _window_scan_one.lower(
        _spec((N_CODES, 32), jnp.uint8, one_chip),
        _spec((BUCKET,), jnp.int32, one_chip),
        _spec((B, 32, 256), jnp.float32, one_chip),
        _spec((B, BUCKET), jnp.bool_, one_chip),
        top_n=512, axes=(), n_shards=1).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_window_scan_compiles_on_2x2(mesh):
    """The dense path on a mesh: each shard gathers the bucket rows it
    holds from its own codes, scans, and all-gathers (dist, position)."""
    from repro.core.distributed import sharded_adc_topn_window
    ctx = _mesh_ctx(mesh)
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(
        lambda c, r, l, k: sharded_adc_topn_window(c, r, l, k, 512, ctx)
    ).lower(
        _spec((N_CODES, 32), jnp.uint8,
              NamedSharding(mesh, P(ctx.rules.corpus, None))),
        _spec((BUCKET,), jnp.int32, rep),
        _spec((B, 32, 256), jnp.float32, rep),
        _spec((B, BUCKET), jnp.bool_, rep)).compile()
    text = compiled.as_text()
    assert "all-gather" in text
    # the codes never move: no collective carries a code row
    assert "u8[" not in "".join(l for l in text.splitlines()
                                if "all-gather" in l or "all-reduce" in l)
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_rows_scan_compiles_on_2x2(mesh):
    from repro.core.distributed import sharded_adc_topn_rows
    ctx = _mesh_ctx(mesh)
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(
        lambda c, q, cb, r: sharded_adc_topn_rows(c, q, cb, r, 512, ctx)
    ).lower(
        _spec((N_CODES, 32), jnp.uint8,
              NamedSharding(mesh, P(ctx.rules.corpus, None))),
        _spec((B, 128), jnp.float32, rep),
        _spec((32, 256, 4), jnp.float32, rep),
        _spec((B, 8192), jnp.int32, rep)).compile()
    assert "all-gather" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_l2dist_kernel_compiles_for_the_chip(one_chip, monkeypatch):
    """The exact-L2 Pallas kernel does compile: off the CPU it runs as a
    real TPU kernel, not in the interpreter."""
    from repro.kernels.l2dist.l2dist import l2dist
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(l2dist).lower(
        _spec((128, 128), jnp.float32, one_chip),
        _spec((4096, 128), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
