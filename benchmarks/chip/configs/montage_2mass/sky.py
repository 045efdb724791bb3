"""The sky of the montage-2mass deployment, made from a seed on the chip.

A grid of ``rows x cols`` tiles of ``tile_rows x tile_cols`` pixels laid
on the mosaic with ``overlap_px`` of overlap.  Each raw tile samples one
star field at its own sub-pixel offset and adds its own background plane
and noise.  The tools and the plain reference both start from
:func:`raw_tiles`; only its result is shared.
"""
from __future__ import annotations

from functools import lru_cache
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def origins(cfg: dict, rows: int, cols: int) -> np.ndarray:
    """(tiles, 2) mosaic (y, x) of each tile's pixel (0, 0), row-major."""
    sy = cfg["tile_rows"] - cfg["overlap_px"]
    sx = cfg["tile_cols"] - cfg["overlap_px"]
    return np.array([(r * sy, c * sx) for r in range(rows)
                     for c in range(cols)], np.int64)


def mosaic_shape(cfg: dict, rows: int, cols: int) -> Tuple[int, int]:
    o = origins(cfg, rows, cols)
    return (int(o[:, 0].max()) + cfg["tile_rows"],
            int(o[:, 1].max()) + cfg["tile_cols"])


def pairs(rows: int, cols: int) -> List[Tuple[int, int]]:
    """8-neighbour overlap pairs (t1 < t2 in row-major order)."""
    out = []
    for r in range(rows):
        for c in range(cols):
            t = r * cols + c
            for dr, dc in ((0, 1), (1, -1), (1, 0), (1, 1)):
                rr, cc = r + dr, c + dc
                if 0 <= rr < rows and 0 <= cc < cols:
                    out.append((t, rr * cols + cc))
    return out


def overlap(cfg: dict, o1, o2) -> Tuple[int, int, int, int]:
    """Mosaic (y0, x0, y1, x1) shared by tiles at origins o1 and o2."""
    y0, x0 = max(o1[0], o2[0]), max(o1[1], o2[1])
    y1 = min(o1[0], o2[0]) + cfg["tile_rows"]
    x1 = min(o1[1], o2[1]) + cfg["tile_cols"]
    return int(y0), int(x0), int(y1), int(x1)


def neighbours(rows: int, cols: int) -> np.ndarray:
    """(tiles, 9) row-major index of each tile's 3 x 3 neighbourhood,
    -1 off the grid."""
    out = np.full((rows * cols, 9), -1, np.int32)
    for r in range(rows):
        for c in range(cols):
            k = 0
            for dr in (-1, 0, 1):
                for dc in (-1, 0, 1):
                    rr, cc = r + dr, c + dc
                    if 0 <= rr < rows and 0 <= cc < cols:
                        out[r * cols + c, k] = rr * cols + cc
                    k += 1
    return out


@lru_cache(maxsize=8)
def _tile_fn(frozen):
    """One jitted program renders any tile of any grid: its stars, and
    its neighbours' stars that reach into it, each tile's from its own
    stream of the key."""
    cfg = dict(frozen)
    tr, tc = cfg["tile_rows"], cfg["tile_cols"]
    k = cfg["stars_per_tile"]
    sig = cfg["psf_sigma_px"]
    lo, hi = cfg["star_flux"]
    bg = dict(cfg["background_plane"])
    scale = cfg["coordinate_scale_px"]

    def stars_of(key, t, origin):
        kt = jax.random.fold_in(key, t)
        ky, kx, kf = jax.random.split(jax.random.fold_in(kt, 0), 3)
        ys = origin[0] + jax.random.uniform(ky, (k,), jnp.float32, 0, tr)
        xs = origin[1] + jax.random.uniform(kx, (k,), jnp.float32, 0, tc)
        return ys, xs, jax.random.uniform(kf, (k,), jnp.float32, lo, hi)

    @jax.jit
    def render(key, t, origin, nb, nb_origin):
        ys, xs, flux = jax.vmap(stars_of, (None, 0, 0))(key, nb, nb_origin)
        flux = jnp.where((nb >= 0)[:, None], flux, 0.0)
        ys, xs, flux = ys.ravel(), xs.ravel(), flux.ravel()
        kt = jax.random.fold_in(key, t)
        ko, kp, kn = jax.random.split(jax.random.fold_in(kt, 1), 3)
        off = jax.random.uniform(ko, (2,), jnp.float32)
        plane = jax.random.uniform(kp, (3,), jnp.float32, -1.0, 1.0) \
            * jnp.array([bg["offset"], bg["slope_per_1024_px"],
                         bg["slope_per_1024_px"]], jnp.float32)
        yy = origin[0] + jnp.arange(tr, dtype=jnp.float32) + off[0]
        xx = origin[1] + jnp.arange(tc, dtype=jnp.float32) + off[1]
        gy = jnp.exp(-0.5 * ((yy[None] - ys[:, None]) / sig) ** 2)
        gx = jnp.exp(-0.5 * ((xx[None] - xs[:, None]) / sig) ** 2)
        img = jnp.einsum("ky,kx->yx", gy * flux[:, None], gx,
                         precision=jax.lax.Precision.HIGHEST)
        img = (img + cfg["sky_level"] + plane[0]
               + plane[1] * xx[None, :] / scale
               + plane[2] * yy[:, None] / scale
               + cfg["noise_sigma"] * jax.random.normal(kn, (tr, tc),
                                                        jnp.float32))
        return img, off
    return render


def _frozen(cfg: dict) -> tuple:
    keys = ("tile_rows", "tile_cols", "stars_per_tile", "star_flux",
            "psf_sigma_px", "sky_level", "background_plane", "noise_sigma",
            "coordinate_scale_px")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else
                  tuple(sorted(cfg[k].items())) if isinstance(cfg[k], dict)
                  else cfg[k]) for k in keys)


def raw_tiles(cfg: dict, rows: int, cols: int, key):
    """Per tile, row-major: the float32 raw image and its sub-pixel
    (dy, dx) offset, on the device."""
    render = _tile_fn(_frozen(cfg))
    org = origins(cfg, rows, cols).astype(np.float32)
    nb = neighbours(rows, cols)
    out = []
    for t in range(rows * cols):
        nb_org = org[np.maximum(nb[t], 0)]
        out.append(render(key, np.int32(t), org[t], nb[t], nb_org))
    return out
