"""Pallas TPU kernels for the compute hot-spots the paper optimizes:

* ``aidw``  — Stage-2 tiled weighted interpolation (paper's shared-memory tiling)
* ``knn``   — blocked brute-force kNN (the 'original' baseline's hot loop)
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode for a kernel launch.

    ``None`` (every wrapper's default) derives it from the backend: compiled
    Mosaic kernels on a TPU, the interpreter everywhere else.  An explicit
    bool is kept, so tests can still force either mode."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
