"""PQ properties (paper §2.2): codebook training, encode/decode, ADC."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propshim import given, settings, strategies as st

from repro.core import pq


def _data(rng, n=512, d=32):
    return jnp.asarray(rng.standard_normal((n, d)), jnp.float32)


# (dim, M) of the tier-1 shape and of the two the chip serves: SIFT's
# 128-d with 32-byte codes, DEEP's 96-d with 24-byte codes
SHAPES = [(32, 8), (128, 32), (96, 24)]
SERVED = SHAPES[1:]


@pytest.mark.parametrize("d, m", SHAPES)
def test_encode_shape_dtype(rng, d, m):
    data = _data(rng, d=d)
    cb = pq.train_codebooks(jax.random.key(0), data, m=m, nbits=8, iters=4)
    codes = pq.encode(cb, data)
    assert codes.shape == (512, m) and codes.dtype == jnp.uint8


def test_adc_equals_exact_on_centroids(rng):
    """A vector that IS a reconstruction has ADC distance == exact distance
    to the query (both measure query-to-centroid)."""
    data = _data(rng, 256, 16)
    cb = pq.train_codebooks(jax.random.key(0), data, m=4, nbits=4, iters=6)
    codes = pq.encode(cb, data)
    recon = pq.decode(cb, codes)
    q = jnp.asarray(rng.standard_normal(16), jnp.float32)
    lut = pq.adc_lut(cb, q)
    adc = pq.adc_distances_ref(lut, codes)
    exact_recon = pq.exact_l2(q, recon)
    np.testing.assert_allclose(np.asarray(adc), np.asarray(exact_recon),
                               rtol=1e-4, atol=1e-4)


def test_quantization_error_decreases_with_m(rng):
    data = _data(rng, 512, 32)
    errs = []
    for m in (2, 8, 32):
        cb = pq.train_codebooks(jax.random.key(0), data, m=m, iters=6)
        recon = pq.decode(cb, pq.encode(cb, data))
        errs.append(float(jnp.mean(jnp.sum((recon - data) ** 2, -1))))
    assert errs[0] >= errs[1] >= errs[2]


@pytest.mark.parametrize("d, m", [(32, 16), *SERVED])
def test_adc_preserves_neighbor_ranking(rng, d, m):
    """PQ distances must correlate with exact distances (rank quality)."""
    data = _data(rng, 512, d)
    cb = pq.train_codebooks(jax.random.key(0), data, m=m, iters=8)
    codes = pq.encode(cb, data)
    q = np.asarray(data[0])
    lut = pq.adc_lut(cb, jnp.asarray(q))
    adc = np.asarray(pq.adc_distances_ref(lut, codes))
    exact = np.asarray(pq.exact_l2(jnp.asarray(q), data))
    # top-10 exact neighbours should mostly be in ADC top-50
    top_exact = set(np.argsort(exact)[:10].tolist())
    top_adc = set(np.argsort(adc)[:50].tolist())
    assert len(top_exact & top_adc) >= 7


@settings(max_examples=20, deadline=None)
@given(m=st.sampled_from([2, 4, 8]), d_mult=st.integers(2, 6),
       seed=st.integers(0, 2 ** 16))
def test_lut_is_subspace_distance(m, d_mult, seed):
    """Property: LUT[i, j] == squared L2 between query sub-vector i and
    centroid j of sub-space i."""
    _check_lut(m, d_mult, seed)


@pytest.mark.parametrize("d, m", SERVED)
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_lut_is_subspace_distance_at_served_shapes(d, m, seed):
    _check_lut(m, d // m, seed)


def _check_lut(m, d_mult, seed):
    d = m * d_mult
    rng = np.random.default_rng(seed)
    data = jnp.asarray(rng.standard_normal((64, d)), jnp.float32)
    cb = pq.train_codebooks(jax.random.key(seed), data, m=m, nbits=4,
                            iters=2)
    q = jnp.asarray(rng.standard_normal(d), jnp.float32)
    lut = np.asarray(pq.adc_lut(cb, q))
    qs = np.asarray(q).reshape(m, d_mult)
    cbn = np.asarray(cb.codebooks)
    for i in range(m):
        ref = np.sum((cbn[i] - qs[i]) ** 2, -1)
        np.testing.assert_allclose(lut[i], ref, rtol=1e-4, atol=1e-4)


def test_codes_are_nearest_centroids(rng):
    data = _data(rng, 128, 16)
    cb = pq.train_codebooks(jax.random.key(0), data, m=4, iters=4)
    codes = np.asarray(pq.encode(cb, data))
    sub = np.asarray(data).reshape(128, 4, 4).transpose(1, 0, 2)
    cbs = np.asarray(cb.codebooks)
    for i in range(4):
        d2 = ((sub[i][:, None] - cbs[i][None]) ** 2).sum(-1)
        np.testing.assert_array_equal(codes[:, i], np.argmin(d2, -1))
