"""Where a Pallas kernel may run.

The ADC kernels in ``kernels/pq_adc`` are refused by the TPU compiler
(Mosaic supports only 2-D gathers, and the batched and fused forms fail its
index-shape check), so they run only in the Pallas interpreter on the CPU,
where the parity tests hold them to the jnp references.  On any other
backend a call raises; it never interprets and never falls back.
"""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Interpret mode on the CPU, compiled on every other backend."""
    return jax.default_backend() == "cpu"


def interpret_or_refuse(kernel: str) -> bool:
    """Interpret mode for a kernel the TPU compiler refuses: True on the
    CPU; anywhere else raises ``NotImplementedError`` naming ``kernel``."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas kernel {kernel} does not compile for this chip "
        f"(backend {backend!r}); the serving path runs the XLA scan "
        f"(use_kernel=False)")
