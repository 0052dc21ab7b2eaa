"""Per-kernel shape/dtype sweeps: Pallas (the CPU interpreter) vs the
pure-jnp oracle, and the fence that keeps the ADC kernels off other
backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pq_adc import pq_adc, pq_adc_topk, pq_adc_ref
from repro.kernels.pq_adc.pq_adc import pq_adc_scan, pq_adc_scan_topk
from repro.kernels.l2dist import l2_distances, l2dist_ref
from repro.kernels.l2dist.l2dist import l2dist


@pytest.mark.parametrize("n,m,block", [
    (64, 8, 64), (256, 16, 64), (1000, 32, 128), (4096, 25, 1024),
    (100, 8, 1024),   # n < block
])
def test_pq_adc_matches_ref(rng, n, m, block):
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    lut = jnp.asarray(rng.random((m, 256)), jnp.float32)
    out = pq_adc(codes, lut, block_n=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(pq_adc_ref(codes, lut)), rtol=1e-6)


@pytest.mark.parametrize("k_entries", [16, 64, 256])
def test_pq_adc_lut_widths(rng, k_entries):
    # nbits < 8 style LUTs (fewer centroids) must still index correctly
    n, m = 128, 8
    codes = jnp.asarray(rng.integers(0, k_entries, (n, m)), jnp.uint8)
    lut = jnp.asarray(rng.random((m, k_entries)), jnp.float32)
    out = pq_adc_scan(codes, lut, block_n=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(pq_adc_ref(codes, lut)), rtol=1e-6)


@pytest.mark.parametrize("n,m,topk,block", [
    (256, 8, 10, 64), (1024, 16, 50, 256), (555, 8, 10, 128),
])
def test_pq_adc_topk_fused(rng, n, m, topk, block):
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    lut = jnp.asarray(rng.random((m, 256)), jnp.float32)
    vals, ids = pq_adc_topk(codes, lut, topk, block_n=block)
    ref = np.asarray(pq_adc_ref(codes, lut))
    ref_sorted = np.sort(ref)[:topk]
    np.testing.assert_allclose(np.sort(np.asarray(vals)), ref_sorted,
                               rtol=1e-5)
    # ids must actually achieve those distances
    np.testing.assert_allclose(np.sort(ref[np.asarray(ids)]), ref_sorted,
                               rtol=1e-5)


def test_pq_adc_topk_padding_block_does_not_evict(rng):
    """ISSUE-6 regression: a final block that is MOSTLY padding (more
    padding rows than topk) must not evict genuine candidates — the pad
    mask has to run inside each block BEFORE its partial top-k."""
    n, m, block, topk = 2048 + 7, 8, 2048, 32   # final block: 2041 pads
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    # zero LUT rows for code 0 would hide the bug (pads score 0 and win);
    # random LUTs + offset make padding rows score LOW so eviction shows
    lut = jnp.asarray(rng.random((m, 256)) + 1.0, jnp.float32)
    vals, ids = pq_adc_topk(codes, lut, topk, block_n=block)
    ref_v, ref_i = pq_adc_topk(codes, lut, topk, use_kernel=False)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(ref_v),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_i))


@pytest.mark.parametrize("n,topk", [(5, 16), (1, 8), (100, 256)])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_pq_adc_topk_n_below_topk_returns_only_real_rows(rng, n, topk,
                                                         use_kernel):
    """ISSUE-6 regression: with n < topk the output is truncated to n —
    all distances finite, every id a real row (no +inf padding ids can
    leak into a rerank candidate list)."""
    m = 8
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    lut = jnp.asarray(rng.random((m, 256)), jnp.float32)
    vals, ids = pq_adc_topk(codes, lut, topk, use_kernel=use_kernel)
    assert vals.shape == (min(topk, n),)
    assert np.all(np.isfinite(np.asarray(vals)))
    assert np.all((np.asarray(ids) >= 0) & (np.asarray(ids) < n))


def test_smallest_k_orders_ties_by_id(rng):
    """The scans' top-n is the k smallest (dist, id) pairs: among equal
    distances the lower id wins, whatever order the ids arrive in."""
    from repro.kernels.pq_adc.ops import smallest_k
    vals = rng.integers(0, 5, (3, 200)).astype(np.float32)
    ids = np.stack([rng.permutation(200) for _ in range(3)]).astype(np.int32)
    v, i = smallest_k(jnp.asarray(vals), jnp.asarray(ids), 37)
    for q in range(3):
        order = np.lexsort((ids[q], vals[q]))[:37]
        np.testing.assert_array_equal(np.asarray(v[q]), vals[q][order])
        np.testing.assert_array_equal(np.asarray(i[q]), ids[q][order])


_N_FENCE, _M_FENCE, _B_FENCE = 72, 8, 3     # shapes no other test traces


@pytest.mark.parametrize("entry,kernel", [
    ("pq_adc", "pq_adc_scan"),
    ("pq_adc_batch", "pq_adc_scan_batch"),
    ("pq_adc_topk", "pq_adc_scan_topk"),
    ("pq_adc_topk_batch", "pq_adc_scan_batch"),
    ("pq_adc_fused_topk", "pq_adc_scan_fused"),
])
def test_adc_kernel_refused_off_cpu(rng, monkeypatch, entry, kernel):
    """On a backend other than the CPU, ``use_kernel=True`` raises an error
    naming the kernel: it never reaches the Pallas interpreter and never
    falls back to the jnp form."""
    import importlib
    import repro.kernels.pq_adc as ops
    # the package re-exports a function named like the kernel module
    kmod = importlib.import_module("repro.kernels.pq_adc.pq_adc")

    def interpreted(*a, **kw):
        raise AssertionError("pallas_call reached off the CPU")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kmod.pl, "pallas_call", interpreted)
    n, m, b = _N_FENCE, _M_FENCE, _B_FENCE
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    lut = jnp.asarray(rng.random((m, 256)), jnp.float32)
    luts = jnp.asarray(rng.random((b, m, 256)), jnp.float32)
    calls = {
        "pq_adc": lambda: ops.pq_adc(codes, lut),
        "pq_adc_batch": lambda: ops.pq_adc_batch(codes, luts),
        "pq_adc_topk": lambda: ops.pq_adc_topk(codes, lut, 9),
        "pq_adc_topk_batch": lambda: ops.pq_adc_topk_batch(codes, luts, 9),
        "pq_adc_fused_topk": lambda: ops.pq_adc_fused_topk(
            codes, jnp.zeros((b, m * 4), jnp.float32),
            jnp.zeros((m, 256, 4), jnp.float32),
            jnp.zeros((b, 16), jnp.int32), 9),
    }
    with pytest.raises(NotImplementedError,
                       match=rf"{kernel} does not compile for this chip"):
        calls[entry]()


def _fused_rows_case(rng, n, m, b, S, k=256, dsub=4):
    codes = jnp.asarray(rng.integers(0, k, (n, m)), jnp.uint8)
    cb = jnp.asarray(rng.standard_normal((m, k, dsub)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, m * dsub)), jnp.float32)
    rows = np.full((b, S), -1, np.int32)
    for i in range(b):
        cnt = int(rng.integers(1, min(n, S) + 1))
        rows[i, :cnt] = np.sort(rng.choice(n, cnt, replace=False))
    return codes, cb, q, jnp.asarray(rows)


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("n,b,S,topk", [
    (555, 3, 64, 16), (2048, 4, 128, 128), (300, 2, 512, 16),
])
def test_pq_adc_fused_topk_matches_rows_ref(rng, use_kernel, n, b, S, topk):
    """Fused LUT→ADC→top-k vs the segmented jnp oracle: identical
    distances and ids (incl. (+inf, -1) at empty slots) on both the
    Pallas interpret path and the jnp hot path."""
    from repro.kernels.pq_adc import (build_luts_ref, pq_adc_fused_topk,
                                      pq_adc_rows_ref)
    codes, cb, q, rows = _fused_rows_case(rng, n, 8, b, S)
    luts = build_luts_ref(cb, q)
    d_ref = np.asarray(pq_adc_rows_ref(codes, luts, rows))
    order = np.argsort(d_ref, axis=1, kind="stable")[:, :topk]
    ref_v = np.take_along_axis(d_ref, order, axis=1)
    ref_i = np.take_along_axis(np.asarray(rows), order, axis=1)
    ref_i[~np.isfinite(ref_v)] = -1
    vals, ids = pq_adc_fused_topk(codes, q, cb, rows, topk,
                                  use_kernel=use_kernel)
    fin = np.isfinite(np.asarray(vals))
    np.testing.assert_array_equal(fin, np.isfinite(ref_v))
    np.testing.assert_allclose(np.asarray(vals)[fin], ref_v[fin], rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids), ref_i)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_pq_adc_fused_topk_int8_lut_tolerance(rng, use_kernel):
    """fig10 int8-LUT accuracy level: quantized distances stay within the
    asymmetric-quantization error bound of the fp32 oracle (per-element
    max error is scale/2 per subquantizer, fp32 merge adds m of them)."""
    from repro.kernels.pq_adc import build_luts_ref, pq_adc_fused_topk
    n, m, b, S, topk = 800, 8, 3, 128, 32
    codes, cb, q, rows = _fused_rows_case(rng, n, m, b, S)
    luts = np.asarray(build_luts_ref(cb, q))
    # bound: sum over m of (per-table scale)/2
    scale = (luts.max(-1) - luts.min(-1)) / 255.0          # (b, m)
    bound = (scale / 2).sum(-1).max() + 1e-5
    v32, i32 = pq_adc_fused_topk(codes, q, cb, rows, topk,
                                 use_kernel=use_kernel)
    v8, i8 = pq_adc_fused_topk(codes, q, cb, rows, topk,
                               use_kernel=use_kernel, lut_int8=True)
    fin = np.isfinite(np.asarray(v32))
    np.testing.assert_array_equal(fin, np.isfinite(np.asarray(v8)))
    assert np.max(np.abs(np.asarray(v8)[fin] - np.asarray(v32)[fin])) \
        <= bound
    # near-lossless at these shapes: top-k sets overlap almost entirely
    for qi in range(b):
        a = set(np.asarray(i32)[qi][fin[qi]].tolist())
        c = set(np.asarray(i8)[qi][np.isfinite(np.asarray(v8))[qi]].tolist())
        inter = len(a & c) / max(len(a), 1)
        assert inter >= 0.9, (qi, inter)


@pytest.mark.parametrize("b,n,d,dtype", [
    (1, 64, 32, jnp.float32), (8, 256, 96, jnp.float32),
    (16, 100, 128, jnp.bfloat16), (128, 1000, 100, jnp.float32),
])
def test_l2dist_matches_ref(rng, b, n, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, d)), dtype)
    out = l2_distances(q, v, block_q=32, block_n=128)
    ref = l2dist_ref(q, v)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_l2dist_self_distance_zero(rng):
    v = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    d = np.asarray(l2_distances(v, v, block_q=32, block_n=32))
    np.testing.assert_allclose(np.diag(d), 0.0, atol=1e-3)


@pytest.mark.parametrize("n,b,m", [(512, 4, 8), (1000, 8, 16), (2048, 16, 32)])
def test_pq_adc_batch_matches_ref(rng, n, b, m):
    from repro.kernels.pq_adc import pq_adc_batch, pq_adc_batch_ref
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.uint8)
    luts = jnp.asarray(rng.random((b, m, 256)), jnp.float32)
    out = pq_adc_batch(codes, luts, block_n=256)
    ref = pq_adc_batch_ref(codes, luts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
