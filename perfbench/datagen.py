"""Seeded tables for the benchmark's configurations.

The yardstick's own copy of the HIGGS-like generator (the program keeps
one in ``benchmarks/workload.py``; a later PR may change that one, not
this). One 28-column *tile* carries the calibrated class structure:

- three mean-shift features (the linear signal lr and nb see);
- five bimodal features whose per-class mean and variance match, so
  only axis-aligned splits separate them;
- four correlation-sign pairs, learnable only through interactions;
- the rest N(0, 1) noise.

A configuration's ``data`` block says how many columns there are, and
how strong the structure is in the tiles after the first
(``tile_scale``): a wide table is the tile repeated with fresh draws, so
no two columns are copies and no split ties exactly.

Rows are made in fixed chunks, each from its own spawned seed
(``SeedSequence(seed).spawn``), on a few threads, straight into the
column arrays the store takes: the same seed gives the same table
whatever the thread count.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

TILE = 28
DELTA = 0.24          # mean-shift half-gap
MODE = 0.95           # bimodal mode offset; the mode sd keeps variance 1
RHO = 0.55            # correlation magnitude of the sign pairs
SHIFT = (10, 11, 12)
BIMODAL = (13, 14, 15, 16, 17)
PAIRS = ((20, 21), (22, 23), (24, 25), (26, 27))
#: Values per chunk; part of the table's definition (a chunk's rows come
#: from that chunk's seed), so it is a constant, not an option.
CHUNK_VALUES = 1 << 24


def _fill_tile(rng, y, out, scale, floor=0.0):
    """One tile's columns for the rows of ``y`` into ``out`` (n, <=28)."""
    n, width = out.shape
    out[:] = rng.normal(size=(n, width)).astype(np.float32)
    mode = MODE * scale
    mode_sd = float(np.sqrt(1.0 - mode * mode))
    for f in BIMODAL:
        if f >= width:
            break
        sign = rng.integers(0, 2, n) * 2 - 1
        bim = (mode * sign + mode_sd * rng.normal(size=n)).astype(np.float32)
        out[:, f] = np.where(y == 1, bim, out[:, f])
    rho = RHO * scale
    resid = np.float32(np.sqrt(1.0 - rho * rho))
    for a, b in PAIRS:
        if b >= width:
            break
        z = rng.normal(size=n).astype(np.float32)
        e = rng.normal(size=n).astype(np.float32)
        r = np.where(y == 1, rho, -rho).astype(np.float32)
        out[:, a] = z
        out[:, b] = r * z + resid * e
    for f in range(width):
        # every other column: a weak shift of its own, ``floor`` to twice
        # that (a column with exactly no signal of its own makes split
        # gains tie to float32 rounding at millions of rows)
        delta = DELTA if f in SHIFT else floor * (1.0 + f / (TILE - 1))
        if delta:
            out[:, f] += np.where(y == 1, delta * scale,
                                  -delta * scale).astype(np.float32)


def make_table(n: int, d: int, seed, tile_scale: float = 1.0,
               threads: int = 8, floor: float = 0.0):
    """(XT float32 (d, n), y int32 (n,)) for one table, features in rows:
    each row of ``XT`` is one catalog column, contiguous, and the
    reference wants the long axis last. ``seed``: a whole number or a
    ``SeedSequence``."""
    XT = np.empty((d, n), np.float32)
    y = np.empty((n,), np.int32)
    rows = max(1, CHUNK_VALUES // d)
    bounds = [(lo, min(lo + rows, n)) for lo in range(0, n, rows)]
    if not isinstance(seed, np.random.SeedSequence):
        seed = np.random.SeedSequence(int(seed))
    seeds = seed.spawn(len(bounds))

    def chunk(i):
        lo, hi = bounds[i]
        rng = np.random.default_rng(seeds[i])
        y[lo:hi] = rng.integers(0, 2, hi - lo).astype(np.int32)
        block = np.empty((hi - lo, TILE), np.float32)
        for t, c0 in enumerate(range(0, d, TILE)):
            width = min(TILE, d - c0)
            _fill_tile(rng, y[lo:hi], block[:, :width],
                       1.0 if t == 0 else tile_scale, floor)
            XT[c0:c0 + width, lo:hi] = block[:, :width].T

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for fut in [pool.submit(chunk, i) for i in range(len(bounds))]:
            fut.result()
    return XT, y


def as_columns(XT: np.ndarray, y: np.ndarray) -> dict:
    """The table as the catalog's columns: ``f0..f{d-1}`` and ``label``
    (no copy: a column is a row of ``XT``)."""
    cols = {f"f{i}": XT[i] for i in range(XT.shape[0])}
    cols["label"] = y.astype(np.int64)
    return cols
