"""The corpus and the pool of held-out queries, made on the device from
``--seed``.

Both are drawn from one Gaussian mixture, as
``repro.data.synthetic.clustered_vectors`` draws them (the generator
``chip_smoke.py`` used): cluster centres from a standard normal, one
centre per point drawn uniformly, plus ``spread`` times standard normal
noise.  It is restated here so that no change to the program can move the
benchmark's inputs.  A configuration may ask for unit-norm vectors
(``normalize``), as DEEP1B's are.

The corpus belongs to the configuration (``corpus_seed``), as a public
dataset does; ``--seed`` draws the queries from the same mixture.  Corpora
drawn from the run's seed gave the index a different structure to build
and search in every run, and with it a different amount of work (PERF.md,
section 6); with one corpus, runs differ in their queries, not in their
work.

The draw runs in one jitted call, block by block, so its temporaries stay
near one block; the same seed gives the same vectors.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK_ROWS = 1 << 17


def seed_words(seed: int, purpose: int = 0) -> np.ndarray:
    """Two 32-bit words from any non-negative integer seed and a purpose
    (JAX's own seed argument keeps only the low 32 bits of a large one)."""
    return np.random.SeedSequence([seed, purpose]).generate_state(
        2, np.uint32)


@functools.partial(jax.jit,
                   static_argnames=("n_blocks", "dim", "n_clusters",
                                    "spread", "normalize"))
def _mixture(centres_key, rows_key, *, n_blocks: int, dim: int,
             n_clusters: int, spread: float, normalize: bool):
    centres = jax.random.normal(jax.random.wrap_key_data(centres_key),
                                (n_clusters, dim), jnp.float32)
    k_rows = jax.random.wrap_key_data(rows_key)

    def block(i):
        ka, kn = jax.random.split(jax.random.fold_in(k_rows, i))
        assign = jax.random.randint(ka, (_BLOCK_ROWS,), 0, n_clusters)
        x = centres[assign] + spread * jax.random.normal(
            kn, (_BLOCK_ROWS, dim), jnp.float32)
        if normalize:
            x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        return x

    out = jax.lax.map(block, jnp.arange(n_blocks))
    return out.reshape(n_blocks * _BLOCK_ROWS, dim)


def _draw(corpus_cfg: Dict, n_clusters: int, dim: int, rows: int,
          rows_words: np.ndarray) -> np.ndarray:
    dev = _mixture(jnp.asarray(seed_words(int(corpus_cfg["corpus_seed"]))),
                   jnp.asarray(rows_words),
                   n_blocks=-(-rows // _BLOCK_ROWS), dim=dim,
                   n_clusters=n_clusters, spread=float(corpus_cfg["spread"]),
                   normalize=bool(corpus_cfg.get("normalize", False)))
    host = np.asarray(dev)[:rows]
    del dev
    return host


def make(corpus_cfg: Dict, n: int, dim: int, n_pool: int,
         seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(corpus (n, dim) from the configuration's ``corpus_seed``, query pool
    (n_pool, dim) from ``seed``), float32 on the host."""
    n_clusters = max(16, n // int(corpus_cfg["points_per_cluster"]))
    corpus = _draw(corpus_cfg, n_clusters, dim, n,
                   seed_words(int(corpus_cfg["corpus_seed"]), 1))
    return corpus, _draw(corpus_cfg, n_clusters, dim, n_pool,
                         seed_words(seed, 2))
