"""The decode batch's per-slot step state: one host mirror, one resident
device copy, and the record of what differs between the two.

The step programs (``llama.decode_window``, ``mixed_step``,
``verify_window``) are told of their decode slots through ONE int32
matrix ``[B, W]`` (``llama.ROW_FIELDS``: last token, length, step count,
seed, top-k, adapter id, and as their bits temperature, top-p and the
three penalties; then the slot's block-table row, two for a model with a
window pool). The matrix lives on the device: a program applies a few
``(slot, column, value)`` cells to it, runs, and returns it with the
lengths, step counts and last tokens its own steps left, and the next
dispatch of the same batch takes that output as its input. The host
keeps the same matrix in numpy: it is the truth for everything the host
decides and counts (``_note_decode_work`` and its kind read these views,
never the device), and every change to it goes through a writer here
that says what the device now lacks:

* ``place`` / ``move``: a sequence took a slot, or a reshard moved the
  device's copy. The next dispatch sends the whole mirror, in one
  transfer (a resynchronisation); so does the one after a dispatch that
  did not bring the matrix back (``took``): a verify step, whose rows
  advance by their own accepted counts, or one that raised;
* ``set_tables``: a block-table row changed. The cells that differ are
  remembered and go up as the next dispatch's delta (a crossed page is
  one cell, a page released behind a window one cell with page 0);
* ``release``: a slot is vacated. Cells too: length 0, no adapter, and
  page 0 in the table columns a dead row's steps write through (its
  position starts at 0 in every program, so the first ``head``); the
  rest of its table row stays, on both sides, until the next tenant's
  ``set_tables`` overwrites it: nothing reads it (a row of length 0
  walks no page). A leave therefore needs no resynchronisation, and a
  program that is already enqueued runs the row as it found it;
* ``advance``: the host emitted what the device sampled. Nothing to
  send: the device's copy is already there. ``hand_over`` holds the
  lengths against what the programs must have left and resynchronises
  if they ever disagree.

The device may be AHEAD of the mirror: the loop enqueues a program
before it has fetched the one before, so a row's length there is the
mirror's plus the steps enqueued for it and not yet emitted
(``pending``, a count a row). Cells still reach the right program: they
are applied in device order. A resynchronisation does not (the mirror
knows nothing of the tokens in flight), so ``hand_over`` refuses one
while anything is pending: the loop drains first (``stale`` tells it).

``hand_over`` is the one place a dispatch thunk gets its decode rows'
arguments from; ``took`` takes the program's returned matrix back.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from ..models.llama import ROW_FIELDS, ROW_FLOATS, ROW_TABLES

# cells a delta carries; a dispatch with more resynchronises. A decode
# row crosses at most one page a pool a window and gives one back behind
# its window: three cells a row at most, 96 at a batch of 32
DELTA_CELLS = 128

_DEFAULTS = {"top_ps": 1.0, "rep_pens": 1.0, "adapter_ids": -1}
_COL = {name: i for i, name in enumerate(ROW_FIELDS)}


class StepState:
    def __init__(self, batch: int, width: int, window: bool = False,
                 sharding=None, head: int = 1):
        self.batch, self.width = batch, width
        #: the leading table columns a dead row's steps write through
        #: (the longest window over the block size, rounded up)
        self.head = head
        n_tables = 2 if window else 1
        self.host = np.zeros((batch, ROW_TABLES + n_tables * width), np.int32)
        for i, name in enumerate(ROW_FIELDS):
            col = self.host[:, i]
            if name in ROW_FLOATS:
                col = col.view(np.float32)
            col[:] = _DEFAULTS.get(name, 0)
            setattr(self, name, col)
        self.tables = self.host[:, ROW_TABLES: ROW_TABLES + width]
        self.wtables = (self.host[:, ROW_TABLES + width:] if window
                        else None)
        self.sharding = sharding
        self._dev = None  # the resident matrix (a device array)
        self._dev_lens = np.zeros(batch, np.int32)  # the lengths it holds
        self._stale = True
        self._cells: dict[tuple[int, int], int] = {}
        self._no_delta = None  # the empty delta, resident too

    # ---- views ----

    def table_views(self) -> list:
        return [self.tables] + ([] if self.wtables is None
                                else [self.wtables])

    def table_column(self, pool: int, col: int) -> int:
        """The matrix column of table ``pool``'s column ``col``."""
        return ROW_TABLES + pool * self.width + col

    # ---- writers (the event loop's thread) ----

    def place(self, slot: int, *, seq_len: int, token: int, steps: int,
              seed: int = 0, temperature: float = 1.0, top_k: int = 0,
              top_p: float = 1.0, freq_pen: float = 0.0,
              pres_pen: float = 0.0, rep_pen: float = 1.0,
              adapter_id: int = -1) -> None:
        """A sequence takes ``slot`` (its tables: ``set_tables``)."""
        self.seq_lens[slot], self.tokens[slot] = seq_len, token
        self.steps[slot], self.seeds[slot] = steps, seed
        self.temps[slot], self.top_ks[slot] = temperature, top_k
        self.top_ps[slot], self.adapter_ids[slot] = top_p, adapter_id
        self.freq_pens[slot], self.pres_pens[slot] = freq_pen, pres_pen
        self.rep_pens[slot] = rep_pen
        self._stale = True

    def release(self, slot: int) -> None:
        """``slot`` is vacated: length 0 and page 0 where a dead row
        writes, so that nothing the next program does for the row
        reaches a page given away. A few cells, not a
        resynchronisation."""
        self._set(slot, _COL["seq_lens"], 0)
        self._set(slot, _COL["adapter_ids"], -1)
        for pool in range(len(self.table_views())):
            for col in range(self.head):
                self._set(slot, self.table_column(pool, col), 0)

    def _set(self, slot: int, col: int, value: int) -> None:
        if self.host[slot, col] != value:
            self.host[slot, col] = value
            self._cells[slot, col] = value

    def set_tables(self, slot: int, table: np.ndarray,
                   wtable: Optional[np.ndarray] = None) -> None:
        """``slot``'s block-table row(s) anew; what differs from the
        mirror is what the device lacks."""
        new = table if wtable is None else np.concatenate([table, wtable])
        row = self.host[slot, ROW_TABLES:]
        for col in np.flatnonzero(row != new):
            self._cells[slot, ROW_TABLES + int(col)] = int(new[col])
        row[:] = new

    def advance(self, slot: int, seq_len: int, token: int,
                steps: int) -> None:
        """The host emitted ``slot``'s tokens of the last dispatch: the
        mirror follows where the device's copy already is."""
        self.seq_lens[slot], self.tokens[slot] = seq_len, token
        self.steps[slot] = steps

    def move(self, sharding) -> None:
        """The device's copy belongs on ``sharding`` from now on (a
        reshard): it goes up whole again."""
        self.sharding, self._no_delta, self._stale = sharding, None, True

    # ---- the dispatch thunk's side (the executor's thread) ----

    def pack_delta(self, cells) -> np.ndarray:
        """``cells`` of (slot, column, value) as a delta: ``[K, 3]``
        int32, the rest padded with a slot past the batch."""
        out = np.full((DELTA_CELLS, 3), self.batch, np.int32)
        if cells:
            out[: len(cells)] = cells
        return out

    def _lens_with_cells(self) -> np.ndarray:
        """The lengths the device's copy holds once the waiting cells
        are written (a leave's length 0)."""
        lens = self._dev_lens.copy()
        for (slot, col), v in self._cells.items():
            if col == _COL["seq_lens"]:
                lens[slot] = v
        return lens

    def stale(self, pending=0) -> bool:
        """Must the next dispatch send the whole mirror? ``pending``:
        the steps enqueued and not yet emitted, a count a row (or one
        for all), which the device is ahead."""
        ahead = np.where(self.seq_lens > 0, self.seq_lens + pending, 0)
        return (self._dev is None or self._stale
                or len(self._cells) > DELTA_CELLS
                or not np.array_equal(self._lens_with_cells(), ahead))

    def hand_over(self, pending=0):
        """(rows, delta, what was sent) for the next dispatch. The ONE
        sanctioned host-to-device transfer of a decode dispatch: the
        whole ``"mirror"`` when ``stale``, else the ``"cells"`` that
        changed, else nothing (``None``)."""
        if self._no_delta is None:
            self._no_delta = jax.device_put(self.pack_delta([]),
                                            self.sharding)
        if self.stale(pending):
            if np.any(pending):
                raise RuntimeError(
                    "the step state must go up whole under a program in "
                    "flight: the loop drains before such a dispatch")
            # (a copy: the mirror is written again before the transfer
            # has to be over)
            rows = jax.device_put(self.host.copy(), self.sharding)
            self._dev_lens[:] = self.seq_lens
            self._stale = False
            self._cells.clear()
            return rows, self._no_delta, "mirror"
        # (given away: the program donates it. Until ``took`` brings its
        # successor, a dispatch that failed leaves the state stale)
        rows, self._dev = self._dev, None
        if not self._cells:
            return rows, self._no_delta, None
        delta = jax.device_put(self.pack_delta(
            [(s, c, v) for (s, c), v in self._cells.items()]), self.sharding)
        self._dev_lens = self._lens_with_cells()
        self._cells.clear()
        return rows, delta, "cells"

    def took(self, rows, steps: int) -> None:
        """``rows``: what the program returned, ``steps`` device steps
        on from what ``hand_over`` gave it (live slots only)."""
        self._dev = rows
        self._dev_lens[self._dev_lens > 0] += steps
