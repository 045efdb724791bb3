"""Plain reference of the montage-2mass mosaic, in numpy float64.

Written from Montage's description and importing nothing of the program
or of the tools: shift each raw tile onto the mosaic grid by bilinear
interpolation, fit a plane to each overlap's difference by least squares,
solve for per-tile planes whose differences match the fits (zero sum),
subtract them, and average the tiles where they overlap.

``precision="bf16"`` is the control: every array the pipeline makes is
rounded to bfloat16, the step below the float32 the configuration states.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from benchmarks.chip.configs.montage_2mass import sky


def _identity(x):
    return np.asarray(x, np.float64)


def _bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


ROUND = {"f64": _identity, "bf16": _bf16}


def shift(raw, dy, dx):
    """Value at raw position (i - dy, j - dx), rows and columns before
    the first clamped to the first."""
    up = np.vstack([raw[:1], raw[:-1]])
    left = np.hstack([raw[:, :1], raw[:, :-1]])
    upleft = np.hstack([up[:, :1], up[:, :-1]])
    return ((1 - dy) * (1 - dx) * raw + dy * (1 - dx) * up
            + (1 - dy) * dx * left + dy * dx * upleft)


def plane_fit(d, y0, x0, scale):
    """(A, B, C) of the least-squares plane A + B X/scale + C Y/scale
    through ``d``, whose pixel (0, 0) sits at mosaic (y0, x0)."""
    yy, xx = np.mgrid[0:d.shape[0], 0:d.shape[1]]
    design = np.stack([np.ones(d.size), (x0 + xx.ravel()) / scale,
                       (y0 + yy.ravel()) / scale], 1)
    return np.linalg.lstsq(design, d.ravel(), rcond=None)[0]


def run(tiles, cfg: dict, rows: int, cols: int,
        precision: str = "f64") -> dict:
    """The mosaic and the per-tile planes from ``tiles``: per tile, row
    major, the raw image and its (dy, dx) offset."""
    rnd = ROUND[precision]
    scale = float(cfg["coordinate_scale_px"])
    org = sky.origins(cfg, rows, cols)
    proj = []
    for img, off in tiles:
        dy, dx = np.asarray(off, np.float64)
        proj.append(rnd(shift(rnd(img), dy, dx)))
    pairs = sky.pairs(rows, cols)
    n = rows * cols
    m = np.zeros((len(pairs) + 1, n))
    rhs = np.zeros((len(pairs) + 1, 3))
    for i, (t1, t2) in enumerate(pairs):
        y0, x0, y1, x1 = sky.overlap(cfg, org[t1], org[t2])
        a = proj[t1][y0 - org[t1][0]:y1 - org[t1][0],
                     x0 - org[t1][1]:x1 - org[t1][1]]
        b = proj[t2][y0 - org[t2][0]:y1 - org[t2][0],
                     x0 - org[t2][1]:x1 - org[t2][1]]
        rhs[i] = rnd(plane_fit(rnd(a - b), y0, x0, scale))
        m[i, t1], m[i, t2] = 1.0, -1.0
    m[-1] = 1.0
    planes = rnd(np.linalg.lstsq(m, rhs, rcond=None)[0])
    h, w = sky.mosaic_shape(cfg, rows, cols)
    total, count = np.zeros((h, w)), np.zeros((h, w))
    tr, tc = cfg["tile_rows"], cfg["tile_cols"]
    yy, xx = np.mgrid[0:tr, 0:tc]
    for t in range(n):
        y, x = org[t]
        a, b, c = planes[t]
        bg = rnd(proj[t] - (a + b * (x + xx) / scale + c * (y + yy) / scale))
        total[y:y + tr, x:x + tc] += bg
        count[y:y + tr, x:x + tc] += 1
    return {"mosaic": rnd(total / count), "planes": planes}
