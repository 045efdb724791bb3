"""Step tools of the montage-2mass deployment: the users' code.

``/fetch``, ``/project``, ``/difffit`` and ``/background`` are small
jitted programs on the chip; ``/overlaps``, ``/bgmodel`` and ``/add`` are
host code.  Outputs go back as they come, device arrays included.
"""
from __future__ import annotations

import json
from functools import lru_cache
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.bodies import timed
from benchmarks.chip.common import jax_key
from benchmarks.chip.configs.montage_2mass import sky


@lru_cache(maxsize=4)
def _config(path: str) -> str:
    return Path(path).read_text()


def config_of(path: str) -> dict:
    return json.loads(_config(path))


@jax.jit
def montage_project(image, offset):
    """Shift a raw tile onto the mosaic grid: the value at raw position
    (i - dy, j - dx) by bilinear interpolation, edges clamped."""
    dy, dx = offset[0], offset[1]
    up = jnp.concatenate([image[:1], image[:-1]], axis=0)
    rows = dy * up + (1.0 - dy) * image
    left = jnp.concatenate([rows[:, :1], rows[:, :-1]], axis=1)
    return dx * left + (1.0 - dx) * rows


@jax.jit
def montage_difffit(a, b, centre, scale):
    """Least-squares plane c0 + c1 u + c2 v through a - b, with u, v the
    strip's pixel offsets from its centre over ``scale``."""
    d = a - b
    v = (jnp.arange(d.shape[0], dtype=jnp.float32) - centre[0]) / scale
    u = (jnp.arange(d.shape[1], dtype=jnp.float32) - centre[1]) / scale
    uu = jnp.broadcast_to(u[None, :], d.shape)
    vv = jnp.broadcast_to(v[:, None], d.shape)
    one = jnp.ones_like(d)
    basis = (one, uu, vv)
    ata = jnp.stack([jnp.stack([jnp.sum(p * q) for q in basis])
                     for p in basis])
    atb = jnp.stack([jnp.sum(p * d) for p in basis])
    return jnp.linalg.solve(ata, atb)


@jax.jit
def montage_background(image, plane, origin, scale):
    """Subtract a mosaic-coordinate plane A + B X/scale + C Y/scale."""
    y = origin[0] + jnp.arange(image.shape[0], dtype=jnp.float32)
    x = origin[1] + jnp.arange(image.shape[1], dtype=jnp.float32)
    return image - (plane[0] + plane[1] * x[None, :] / scale
                    + plane[2] * y[:, None] / scale)


def fetch_tool(config: str, rows: int, cols: int):
    cfg = config_of(config)
    org = sky.origins(cfg, rows, cols)

    def fn(inputs, ctx):
        tiles = sky.raw_tiles(cfg, rows, cols, jax_key(int(inputs["seed"])))
        return {"raw": [{"tile": t, "origin": org[t], "offset": off,
                         "image": img} for t, (img, off) in enumerate(tiles)]}
    return timed("/fetch", fn)


def project_tool():
    def fn(inputs, ctx):
        raw = inputs["raw"]
        return {"proj": {"tile": raw["tile"], "origin": raw["origin"],
                         "image": montage_project(raw["image"],
                                                  raw["offset"])}}
    return timed("/project", fn)


def overlaps_tool(config: str, rows: int, cols: int):
    cfg = config_of(config)
    pairs = sky.pairs(rows, cols)

    def fn(inputs, ctx):
        proj = {p["tile"]: p for p in inputs["proj"]}
        out = []
        for t1, t2 in pairs:
            o1, o2 = proj[t1]["origin"], proj[t2]["origin"]
            y0, x0, y1, x1 = sky.overlap(cfg, o1, o2)

            def strip(p, o):
                return p["image"][y0 - o[0]:y1 - o[0], x0 - o[1]:x1 - o[1]]
            out.append({"tiles": (t1, t2), "corner": (y0, x0),
                        "a": strip(proj[t1], o1), "b": strip(proj[t2], o2)})
        return {"pair": out}
    return timed("/overlaps", fn)


def difffit_tool(config: str):
    scale = float(config_of(config)["coordinate_scale_px"])

    def fn(inputs, ctx):
        p = inputs["pair"]
        h, w = p["a"].shape
        centre = jnp.array([(h - 1) / 2, (w - 1) / 2], jnp.float32)
        return {"fit": {"tiles": p["tiles"], "corner": p["corner"],
                        "centre": ((h - 1) / 2, (w - 1) / 2),
                        "coeffs": montage_difffit(p["a"], p["b"], centre,
                                                  scale)}}
    return timed("/difffit", fn)


def solve_planes(fits, n_tiles: int, scale: float) -> np.ndarray:
    """mConcatFit + mBgModel: per-tile planes (A, B, C) whose pairwise
    differences best match the fitted ones, summing to zero."""
    m = np.zeros((len(fits) + 1, n_tiles))
    rhs = np.zeros((len(fits) + 1, 3))
    for i, f in enumerate(fits):
        t1, t2 = f["tiles"]
        c0, c1, c2 = np.asarray(f["coeffs"], np.float64)
        yc = f["corner"][0] + f["centre"][0]
        xc = f["corner"][1] + f["centre"][1]
        m[i, t1], m[i, t2] = 1.0, -1.0
        rhs[i] = (c0 - c1 * xc / scale - c2 * yc / scale, c1, c2)
    m[-1] = 1.0
    return np.linalg.lstsq(m, rhs, rcond=None)[0]


def bgmodel_tool(config: str, tiles: int):
    scale = float(config_of(config)["coordinate_scale_px"])

    def fn(inputs, ctx):
        planes = solve_planes(inputs["fit"], tiles, scale)
        return {"corr": [{"tile": t, "plane": planes[t]}
                         for t in range(tiles)]}
    return timed("/bgmodel", fn)


def background_tool(config: str):
    scale = float(config_of(config)["coordinate_scale_px"])

    def fn(inputs, ctx):
        proj, corr = inputs["proj"], inputs["corr"]
        if proj["tile"] != corr["tile"]:
            raise ValueError(f"tile {proj['tile']} got the plane of tile "
                             f"{corr['tile']}")
        img = montage_background(
            proj["image"], jnp.asarray(corr["plane"], jnp.float32),
            jnp.asarray(proj["origin"], jnp.float32), scale)
        return {"bg": {"tile": proj["tile"], "origin": proj["origin"],
                       "image": img}}
    return timed("/background", fn)


def add_tool(config: str, rows: int, cols: int):
    cfg = config_of(config)
    h, w = sky.mosaic_shape(cfg, rows, cols)

    def fn(inputs, ctx):
        total = np.zeros((h, w), np.float32)
        count = np.zeros((h, w), np.float32)
        for b in inputs["bg"]:
            y, x = b["origin"]
            img = np.asarray(b["image"])
            total[y:y + img.shape[0], x:x + img.shape[1]] += img
            count[y:y + img.shape[0], x:x + img.shape[1]] += 1.0
        return {"mosaic": total / count}
    return timed("/add", fn)
