"""Tile decompositions.

API parity with /root/reference/heat/core/tiling.py (``SplitTiles`` :16,
``SquareDiagTiles`` :331). The reference builds these as the addressing
layer of its rank-divergent algorithms (``resplit_`` consumes SplitTiles;
the tiled CAQR consumes SquareDiagTiles). In this framework resharding
and QR are expressed declaratively (GSPMD + TSQR), so no internal
algorithm needs a tile map — but algorithms USERS built on the reference
tiles do, so both classes are fully functional tile VIEWS here:

* indexing a tile (or a slice of tiles) returns its values;
* assigning to a tile writes through to the underlying DNDarray (the
  write is a global setitem — XLA turns it into the same local-shard
  scatter the reference's rank-local write performs);
* the geometry surface (``lshape_map``, ``tile_locations``,
  ``tile_ends_g``, ``tile_map``, ``get_start_stop``,
  ``local_get``/``local_set``, ``local_to_global``) matches the
  reference names.

Single-controller note: the reference's "local" accessors address the
calling rank's band; here every device's band is addressable from the one
controller, so ``local_*`` take the device rank explicitly (default 0) —
the same signature shift ``DNDarray.lloc`` documents.
"""

from __future__ import annotations

import numpy as np

from typing import List, Optional, Tuple, Union

from .dndarray import DNDarray
from ..observability.tracing import span as _span

__all__ = ["SplitTiles", "SquareDiagTiles"]


def _starts(extents: np.ndarray) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(extents)])


class SplitTiles:
    """Tiles along every dimension with the split-axis boundaries of every
    device (reference: tiling.py:16). ``tile_dimensions[d]`` holds the tile
    extents along dim d; one tile boundary set per device along each dim.
    """

    def __init__(self, arr: DNDarray):
        if not isinstance(arr, DNDarray):
            raise TypeError(f"arr must be a DNDarray, got {type(arr)}")
        self.__arr = arr
        size = arr.comm.size
        # per-dim tile extents: the split dim follows the chunk geometry,
        # other dims are chunked the same way "theoretically" (reference
        # computes torch chunk sizes per dim)
        dims = []
        for d in range(arr.ndim):
            counts = [
                arr.comm.chunk(arr.gshape, d, rank=r)[1][d] for r in range(size)
            ]
            dims.append(np.array(counts, dtype=np.int64))
        self.__tile_dimensions = dims
        self.__tile_locations = self.set_tile_locations(
            split=arr.split, tile_dims=dims, arr=arr
        )

    @property
    def arr(self) -> DNDarray:
        return self.__arr

    @property
    def lshape_map(self) -> np.ndarray:
        """(size, ndim) local-shape map (reference: tiling.py:146)."""
        return self.__arr.lshape_map

    @property
    def tile_dimensions(self) -> List[np.ndarray]:
        return self.__tile_dimensions

    @property
    def tile_ends_g(self) -> np.ndarray:
        """Global END index of every tile along every dim, shape
        (ndim, size) (reference: tiling.py:164)."""
        return np.stack([np.cumsum(t) for t in self.__tile_dimensions])

    @property
    def tile_locations(self) -> np.ndarray:
        return self.__tile_locations

    @staticmethod
    def set_tile_locations(split: Optional[int], tile_dims: List[np.ndarray], arr: DNDarray) -> np.ndarray:
        """Device owning each tile (reference: tiling.py set_tile_locations)."""
        shape = tuple(len(t) for t in tile_dims)
        locations = np.zeros(shape, dtype=np.int64)
        if split is None:
            return locations
        size = arr.comm.size
        idx = [slice(None)] * len(shape)
        for r in range(size):
            idx[split] = r
            locations[tuple(idx)] = r
        return locations

    def __tile_slices(self, key) -> Tuple[slice, ...]:
        """Global slices covering the requested tile (or tile-slice) key."""
        starts = [_starts(t) for t in self.__tile_dimensions]
        if not isinstance(key, tuple):
            key = (key,)
        slices = []
        for d in range(self.__arr.ndim):
            if d < len(key):
                k = key[d]
                if isinstance(k, slice):
                    lo, hi, step = k.indices(len(self.__tile_dimensions[d]))
                    if step != 1:
                        raise ValueError("tile slices must be contiguous (step 1)")
                    slices.append(slice(int(starts[d][lo]), int(starts[d][hi])))
                else:
                    k = int(k)
                    slices.append(slice(int(starts[d][k]), int(starts[d][k + 1])))
            else:
                slices.append(slice(None))
        return tuple(slices)

    def __getitem__(self, key) -> Optional[np.ndarray]:
        """Tile values as numpy (the reference returns the rank-local torch
        slice; under a single controller every tile is addressable)."""
        # slice on device first: only the tile travels to host
        with _span("ht.sync.read", what="tile"):
            return np.asarray(self.__arr.larray[self.__tile_slices(key)])

    def __setitem__(self, key, value) -> None:
        """Assign to a tile — writes through to the underlying DNDarray
        (reference: tiling.py:299 writes the rank-local slice)."""
        self.__arr[self.__tile_slices(key)] = value


class SquareDiagTiles:
    """Square tiles along the diagonal of a 2-D array (reference:
    tiling.py:331): the addressing scheme of the reference's tiled QR
    (``tiles_per_proc`` partitions each device's band). Fully indexable
    and writable; see the module docstring for the single-controller
    reading of the ``local_*`` accessors.
    """

    def __init__(self, arr: DNDarray, tiles_per_proc: int = 2):
        if not isinstance(arr, DNDarray):
            raise TypeError(f"arr must be a DNDarray, got {type(arr)}")
        if arr.ndim != 2:
            raise ValueError("Arr must be 2 dimensional")
        if not isinstance(tiles_per_proc, int) or tiles_per_proc < 1:
            raise ValueError(f"tiles_per_proc must be a positive int, got {tiles_per_proc}")
        self.__arr = arr
        size = arr.comm.size
        m, n = arr.gshape
        split = arr.split if arr.split is not None else 0

        # per-device extents along the split dim
        counts = [arr.comm.chunk(arr.gshape, split, rank=r)[1][split] for r in range(size)]
        row_per_proc = []
        row_starts = [0]
        for c in counts:
            per = max(1, tiles_per_proc)
            base = c // per
            rem = c % per
            sizes = [base + (1 if i < rem else 0) for i in range(per)]
            sizes = [s for s in sizes if s > 0]
            row_per_proc.append(len(sizes))
            for s in sizes:
                row_starts.append(row_starts[-1] + s)
        # square tiles: column boundaries mirror row boundaries up to n
        col_bounds = [b for b in row_starts if b <= n]
        if col_bounds[-1] != n:
            col_bounds.append(n)

        self.__split = split
        self.__row_starts = np.array(row_starts, dtype=np.int64)
        self.__col_starts = np.array(col_bounds, dtype=np.int64)
        self.__tile_rows_per_process = row_per_proc
        self.__tile_columns = len(self.__col_starts) - 1
        self.__tile_rows = len(self.__row_starts) - 1

    @property
    def arr(self) -> DNDarray:
        return self.__arr

    @property
    def lshape_map(self) -> np.ndarray:
        """(size, 2) local-shape map (reference: tiling.py:737)."""
        return self.__arr.lshape_map

    @property
    def last_diagonal_process(self) -> int:
        """Rank of the last device holding part of the diagonal
        (reference: tiling.py:745)."""
        m, n = self.__arr.gshape
        diag_end = min(m, n)
        # device whose band contains row/col diag_end - 1
        tile = int(np.searchsorted(self.__row_starts, diag_end - 1, side="right") - 1)
        return int(self.tile_map[min(tile, self.__tile_rows - 1), 0])

    @property
    def tile_columns(self) -> int:
        """Number of tile columns (reference: tiling.py tile_columns)."""
        return self.__tile_columns

    @property
    def tile_columns_per_process(self) -> List[int]:
        """Reference tiling.py:766 — every process sees all tile columns
        (column tiles are not owner-partitioned in the split=0 layout)."""
        return [self.__tile_columns] * self.__arr.comm.size

    @property
    def tile_map(self) -> np.ndarray:
        """(tile_rows, tile_columns) device owning each tile (reference:
        tiling.py:773 stores (start_row, start_col, rank) triples; the
        rank plane is the load-bearing part)."""
        size = self.__arr.comm.size
        owners = np.zeros((self.__tile_rows, self.__tile_columns), dtype=np.int64)
        # a row tile belongs to the device whose band contains it
        bands = np.cumsum([0] + self.__tile_rows_per_process)
        for r in range(size):
            owners[bands[r]: bands[r + 1], :] = r
        return owners

    @property
    def tile_rows(self) -> int:
        """Number of tile rows."""
        return self.__tile_rows

    @property
    def tile_rows_per_process(self) -> List[int]:
        return list(self.__tile_rows_per_process)

    @property
    def row_indices(self) -> List[int]:
        return self.__row_starts[:-1].tolist()

    @property
    def col_indices(self) -> List[int]:
        return self.__col_starts[:-1].tolist()

    def get_tile_size(self, key: Tuple[int, int]) -> Tuple[int, int]:
        """(rows, cols) of tile ``key``."""
        i, j = key
        return (
            int(self.__row_starts[i + 1] - self.__row_starts[i]),
            int(self.__col_starts[j + 1] - self.__col_starts[j]),
        )

    def get_start_stop(self, key: Tuple[int, int]) -> Tuple[int, int, int, int]:
        """(row start, row stop, col start, col stop) of tile ``key``
        (reference: tiling.py:822)."""
        rs, re, cs, ce = self.__tile_bounds(key)
        return rs, re, cs, ce

    def __tile_bounds(self, key) -> Tuple[int, int, int, int]:
        if not isinstance(key, tuple):
            key = (key, slice(None))
        i, j = key
        if isinstance(i, slice):
            lo, hi, step = i.indices(self.__tile_rows)
            if step != 1:
                raise ValueError("tile slices must be contiguous (step 1)")
            rs, re = int(self.__row_starts[lo]), int(self.__row_starts[hi])
        else:
            i = int(i)
            rs, re = int(self.__row_starts[i]), int(self.__row_starts[i + 1])
        if isinstance(j, slice):
            lo, hi, step = j.indices(self.__tile_columns)
            if step != 1:
                raise ValueError("tile slices must be contiguous (step 1)")
            cs, ce = int(self.__col_starts[lo]), int(self.__col_starts[hi])
        else:
            j = int(j)
            cs, ce = int(self.__col_starts[j]), int(self.__col_starts[j + 1])
        return rs, re, cs, ce

    def __getitem__(self, key) -> np.ndarray:
        rs, re, cs, ce = self.__tile_bounds(key)
        with _span("ht.sync.read", what="tile"):
            return np.asarray(self.__arr.larray[rs:re, cs:ce])

    def __setitem__(self, key, value) -> None:
        """Assign to a tile — writes through to the underlying DNDarray
        (reference: tiling.py:1206)."""
        rs, re, cs, ce = self.__tile_bounds(key)
        self.__arr[rs:re, cs:ce] = value

    # ------------------------------------------------------------------ #
    # local (per-device band) accessors                                  #
    # ------------------------------------------------------------------ #
    def local_to_global(self, key: Tuple[int, int], rank: int = 0) -> Tuple[int, int]:
        """Map a device-local tile index to the global tile index
        (reference: tiling.py:1018; the rank is explicit here — see the
        module docstring)."""
        i, j = key
        base = int(np.sum(self.__tile_rows_per_process[:rank]))
        return base + int(i), int(j)

    def local_get(self, key: Tuple[int, int], rank: int = 0) -> np.ndarray:
        """Values of device ``rank``'s local tile ``key`` (reference:
        tiling.py:935)."""
        return self[self.local_to_global(key, rank)]

    def local_set(self, key: Tuple[int, int], value, rank: int = 0) -> None:
        """Assign device ``rank``'s local tile ``key`` (reference:
        tiling.py:955)."""
        self[self.local_to_global(key, rank)] = value

    def match_tiles(self, tiles_to_match: "SquareDiagTiles") -> None:
        """Adopt the row/column boundaries of another tile map so the two
        arrays can be addressed tile-by-tile together — the reference
        aligns Q's tiles to A's before the tiled QR sweep
        (tiling.py:1080). Boundaries are clipped to this array's extents.
        """
        if not isinstance(tiles_to_match, SquareDiagTiles):
            raise TypeError(
                f"tiles_to_match must be SquareDiagTiles, got {type(tiles_to_match)}"
            )
        m, n = self.__arr.gshape
        rows = [b for b in tiles_to_match.__row_starts.tolist() if b <= m]
        if rows[-1] != m:
            rows.append(m)
        cols = [b for b in tiles_to_match.__col_starts.tolist() if b <= n]
        if cols[-1] != n:
            cols.append(n)
        self.__row_starts = np.array(rows, dtype=np.int64)
        self.__col_starts = np.array(cols, dtype=np.int64)
        self.__tile_rows = len(rows) - 1
        self.__tile_columns = len(cols) - 1
        # rows-per-process: recount against the matched boundaries
        size = self.__arr.comm.size
        counts = [
            self.__arr.comm.chunk(self.__arr.gshape, self.__split, rank=r)[1][self.__split]
            for r in range(size)
        ]
        band_ends = np.cumsum(counts)
        self.__tile_rows_per_process = [
            int(
                np.sum(
                    (self.__row_starts[:-1] >= (band_ends[r - 1] if r else 0))
                    & (self.__row_starts[:-1] < band_ends[r])
                )
            )
            for r in range(size)
        ]
