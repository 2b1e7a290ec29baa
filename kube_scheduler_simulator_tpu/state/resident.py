"""A host array's copy on the device, brought up to date by a patch.

The volume carry (state/volumecarry.py) owns two cluster-sized bool
arrays, pv_node_ok [V, N] and on_node [N, C], of which a pass changes a
row and a bit.  The truth stays on the host: the carry writes its numpy
arrays as before, and tells a journal here WHERE it wrote.  At the pass's
one upload site (state/compile.py, span cw_upload) the journal becomes a
small payload that rides in the pass's packed buffers in the leaf's
place (state/packed.py; the scan takes the array itself as an argument
of its own beside them), and one jitted dispatch, which cuts the payload
out of those buffers itself, makes the new device array from the old:

  RowsResident    new = old[src] with the rows written afresh set from the
                  payload: src [V] says which old row each row is (-1: a
                  row of zeros, the padding a delete leaves), so a PV
                  inserted or deleted mid-table is a shift composed into
                  src, and a table laid out again (_lay_pvs) hands its own
  CellsResident   new = old.at[nodes, slots].set(values)

The VALUES are read from the host array when the payload is made, so the
order of the journal's entries cannot matter, and an entry written twice
is one entry.  Payloads have fixed capacity (ROWS_MAX rows, CELLS_MAX
cells) and are padded by repeating an entry (setting a value twice is
setting it once), so their shapes are the arrays' buckets': the patch is
compiled when an array is first made resident and never after.

What a journal cannot say drops the device copy, and the next pass sends
the array whole, in a transfer of its own beside a payload that writes
nothing (volume_resident_uploads_total{reason}): first | resync | nodes |
drivers | bucket (another shape) | overflow (more written than a payload
holds: an import).  Nothing is donated: an earlier pass's CompiledWorkload may hold the generation
before, which is freed with its last holder.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.tracing import TRACER
from .packed import PackedPass, unpack_leaves

ROWS_MAX = 8        # rows written afresh that one patch carries
CELLS_MAX = 256     # cells that one patch carries

_ZEROS, _FRESH = -1, -2     # src: a row of zeros; a row the payload brings


class RowsPatch(NamedTuple):
    src: np.ndarray         # [V] int32: the old row, or _ZEROS / _FRESH
    rows: np.ndarray        # [ROWS_MAX] int32
    fresh: np.ndarray       # [ROWS_MAX, N]


class CellsPatch(NamedTuple):
    at: np.ndarray          # [2, CELLS_MAX] int32: nodes, slots
    values: np.ndarray      # [CELLS_MAX]


def _patch_rows(old, patch: RowsPatch):
    kept = jnp.where((patch.src >= 0)[:, None],
                     old[jnp.maximum(patch.src, 0)], False)
    return kept.at[patch.rows].set(patch.fresh)


def _patch_cells(old, patch: CellsPatch):
    return old.at[patch.at[0], patch.at[1]].set(patch.values)


def _patch_program(fn, layout: tuple, patch: type, picks: tuple):
    """(run(old, bufs) -> new: fn applied to the payload that lies at
    leaves `picks` of a pass's packed buffers (state/packed.py: `layout`),
    cut out of them inside the same executable; the buffers' shapes)."""
    def run(old, bufs):
        return fn(old, patch(*unpack_leaves(layout, picks, bufs)))

    sizes: dict[str, int] = {}
    for dt, shape in layout:
        sizes[dt] = sizes.get(dt, 0) + math.prod(shape)
    return run, {dt: jax.ShapeDtypeStruct((n,), np.dtype(dt))
                 for dt, n in sizes.items()}


@lru_cache(maxsize=64)
def _compiled(fn, old: jax.ShapeDtypeStruct, layout: tuple, patch: type,
              picks: tuple):
    """_patch_program compiled for an array of this shape.  Made when an
    array becomes resident, so that no patched pass of the same layout
    meets the compile; a pass of another layout (another pod count: its
    scan compiles too) compiles its own."""
    run, bufs = _patch_program(fn, layout, patch, picks)
    return jax.jit(run).lower(old, bufs).compile()


class _Resident:
    """The device copy of one host array as of the session's last pass."""

    patch_fn = None

    def __init__(self):
        self.dev: jax.Array | None = None   # None: the next pass sends whole
        self.why = "first"                  # ... and counts this reason
        self._whole: np.ndarray | None = None   # outgoing() -> incoming()

    def drop(self, why: str) -> None:
        """The journal cannot follow what happens next."""
        if self.dev is not None:
            self.dev, self.why = None, why
            self._forget()

    def outgoing(self, host: np.ndarray) -> NamedTuple:
        """What rides in the leaf's place in the pass's packed buffers: the
        patch's payload, numpy leaves.  Always one, of the bucket's fixed
        shapes, so that the buffers have one layout (and the scan that
        takes them whole one executable) whether the array is patched or
        sent whole; where it is sent whole, by incoming() and in a transfer
        of its own, the payload writes nothing."""
        if self.dev is not None and self.dev.shape != host.shape:
            self.drop("bucket")
        if self.dev is not None:
            patch = self._payload(host)
            if patch is not None:
                return patch
            self.drop("overflow")
        TRACER.inc("volume_resident_uploads_total", reason=self.why)
        self._whole = host
        return self._payload(host)      # of a journal that holds nothing

    @property
    def whole_nbytes(self) -> int:
        """The bytes this pass sends beside the payload."""
        return 0 if self._whole is None else self._whole.nbytes

    def incoming(self, packed: PackedPass, rode: NamedTuple) -> jax.Array:
        """`rode` is outgoing()'s payload as it lies in the pass's upload,
        a Packed a leaf -> the device array of this pass."""
        picks = tuple(leaf.k for leaf in rode)
        if self._whole is not None:
            TRACER.count("workload_h2d_transfers_total")
            TRACER.count("pass_device_dispatches_total")
            # a copy: the carry goes on writing into its array, and a
            # device_put may alias the host's memory (the CPU backend does)
            self.dev, self.why = jnp.array(self._whole), None
            self._whole = None
        like = jax.ShapeDtypeStruct(self.dev.shape, self.dev.dtype)
        run = _compiled(self.patch_fn, like, packed.layout, type(rode), picks)
        if self._written():
            TRACER.count("pass_device_dispatches_total")
            self.dev = run(self.dev, packed.bufs)
            TRACER.count("volume_resident_patches_total")
        self._forget()
        return self.dev


class RowsResident(_Resident):
    """An array whose rows move, are written whole or are cleared."""

    patch_fn = staticmethod(_patch_rows)

    def __init__(self):
        super().__init__()
        self._src: np.ndarray | None = None     # None: every row where it was

    def _forget(self) -> None:
        self._src = None

    def _written(self) -> bool:
        return self._src is not None

    def _rows(self) -> np.ndarray:
        if self._src is None:
            self._src = np.arange(self.dev.shape[0], dtype=np.int32)
        return self._src

    def moved(self, i: int, v: int, by: int) -> None:
        """Rows i:v are now rows i + by:v + by (overlapping, as a memmove)."""
        if self.dev is not None:
            src = self._rows()
            src[i + by:v + by] = src[i:v].copy()

    def wrote(self, i: int, cleared: bool = False) -> None:
        """Row i was written afresh, or (cleared) set to zeros."""
        if self.dev is not None:
            self._rows()[i] = _ZEROS if cleared else _FRESH

    def relaid(self, src: np.ndarray, v: int) -> None:
        """Rows :v are the rows src of before (< 0: written afresh), the
        rows past v zeros."""
        if self.dev is not None:
            old = self._rows()
            new = np.full_like(old, _ZEROS)
            new[:v] = np.where(src >= 0, old[np.maximum(src, 0)], _FRESH)
            self._src = new

    def _payload(self, host: np.ndarray) -> RowsPatch | None:
        src = self._src if self._src is not None else np.arange(
            host.shape[0], dtype=np.int32)
        rows = np.flatnonzero(src == _FRESH).astype(np.int32)
        if rows.size > ROWS_MAX:
            return None
        # padded with a row that is written with what it holds
        rows = np.resize(rows if rows.size else np.zeros(1, np.int32), ROWS_MAX)
        return RowsPatch(src=src, rows=rows, fresh=host[rows])


class CellsResident(_Resident):
    """An array of which single cells are written."""

    patch_fn = staticmethod(_patch_cells)

    def __init__(self):
        super().__init__()
        self._cells: set[tuple[int, int]] = set()

    def _forget(self) -> None:
        self._cells = set()

    def _written(self) -> bool:
        return bool(self._cells)

    def wrote(self, j: int, s: int) -> None:
        if self.dev is not None:
            self._cells.add((j, s))
            if len(self._cells) > CELLS_MAX:
                self.drop("overflow")

    def _payload(self, host: np.ndarray) -> CellsPatch | None:
        # a slot past the extent is no column of this pass's plane; padded
        # with a cell that is written with what it holds
        cells = sorted(c for c in self._cells if c[1] < host.shape[1])
        at = np.asarray(cells or [(0, 0)], dtype=np.int32).T
        at = np.stack([np.resize(at[0], CELLS_MAX), np.resize(at[1], CELLS_MAX)])
        return CellsPatch(at=at, values=host[at[0], at[1]])
