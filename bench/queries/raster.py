"""The output grid of a raster interpolation, pixel by pixel in row order,
as a DEM is made from scattered samples.  GDAL ``gdal_grid`` works that
way: ``gdal_grid_lib.cpp``'s ``ProcessLayer`` widens the output band's
block to a work buffer of about 16 MB, which for a raster a few thousand
float32 pixels wide is a strip of whole rows, and fills each block row by
row, the blocks in row order; so the pixels go row-major over the whole
raster.

Parameters: ``height`` and ``width`` (pixels).  Pixel ``(i, j)`` sits at
``x = 0.01 + 0.98 (j + 0.5 + ox) / width`` and
``y = 0.01 + 0.98 (i + 0.5 + oy) / height``, inside ``[0.01, 0.99]^2``.  The
seed draws the lattice's sub-pixel origin ``ox, oy`` in ``[-0.5, 0.5)`` and
the row to start from, so each seed's queries lie elsewhere while every
seed does the same work.  ``next(n)`` returns the next ``n`` pixels in
row-major order and wraps at the end of the raster: a call of ``n`` that is
a multiple of ``width`` is a strip of ``n / width`` whole rows.
"""

from __future__ import annotations

import numpy as np


def stream(gen: np.random.Generator, *, height: int, width: int):
    if height < 1 or width < 1:
        raise ValueError(f"raster {height}x{width} has no pixels")
    ox, oy = gen.random(2) - 0.5
    pixels = height * width
    start = int(gen.integers(height)) * width    # the next pixel to send

    def next_queries(n: int) -> np.ndarray:
        nonlocal start
        if n < 1:
            raise ValueError(f"a call of {n} queries")
        p = (start + np.arange(n)) % pixels
        start = (start + n) % pixels
        x = 0.01 + 0.98 * (p % width + 0.5 + ox) / width
        y = 0.01 + 0.98 * (p // width + 0.5 + oy) / height
        return np.stack([x, y], axis=1).astype(np.float32)

    return next_queries
