"""Query points uniform at random in [0.01, 0.99]^2, the paper's
interpolated points (Mei, Xu & Xu 2016, section 5).  No parameters."""

from __future__ import annotations

import numpy as np

from bench import data


def stream(gen: np.random.Generator):
    """``next(n)``: n fresh uniform queries, (n, 2) float32."""
    return lambda n: data.inner(gen.random((n, 2)))
