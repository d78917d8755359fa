"""`BlockSource` — where the sampling loop's window data comes from.

A source serves fixed-shape windows of blocked (z, x) tuples plus the
packed presence bitmap. The contract is shaped by the device-resident
round in `repro.core.multiquery`: every `WindowData` is padded to one
static length (`pad_to`) so the jitted round never retraces, and padded
rows carry ``valid=False`` so the round masks them out of marking,
ingest and the read bookkeeping.

`fetch` is random access (used by exact completion); `stream` is the
sequential hot path a pass runs on, and is the hook `PrefetchSource`
overrides to overlap the next window's gather with the current round.

A device-resident fetch is ONE compiled program (`gather_window`) whose
device cost scales with the window, not the table. Its bitmap is stored
with the word axis zero-padded to a multiple of `LANES`: on a TPU a
gather from a table whose minor dim is not lane-aligned first copies
the whole table into the layout the gather wants (1.1 GB at TAXI-q1's
236 words, on every fetch), while the aligned table is read in place.
The tiled layout already pads the minor dim to 128 lanes, so the
padding costs no device memory that was not already spent.
"""

from __future__ import annotations

import functools
import threading
from typing import Iterable, Iterator, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.layout import BlockedDataset

__all__ = ["BlockSource", "InMemorySource", "ShardedSource", "WindowData", "as_block_source"]

LANES = 128  # minor-dim tile of the TPU's default (8, 128) layout


class WindowData(NamedTuple):
    """One padded lookahead window of block data, ready for the round.

    Leaves are device arrays from a device-resident source and host
    numpy arrays from a host-resident one (jit converts at dispatch —
    one host→device transfer, paid exactly once; the data-parallel pump
    relies on this to assemble per-worker windows on host and place the
    sharded result in a single device_put)."""

    indices: jax.Array  # (L,) i32 global block ids (padding repeats a real id)
    z: jax.Array  # (L, B) i32 candidate ids, -1 padded within blocks
    x: jax.Array  # (L, B) i32 attribute values, -1 padded
    bitmap: jax.Array  # (L, W) uint32 packed presence bitmap rows
    valid: jax.Array  # (L,) bool — False on window padding rows


@functools.partial(jax.jit, static_argnames=("words",))
def gather_window(z, x, bitmap, idx, valid, *, words: int) -> WindowData:
    """One padded window gathered from device-resident tables in one
    program. The tables are arguments, never closed over: a closure
    would bake them into the program as constants and compile once per
    source. ``bitmap`` may be lane-padded; the window keeps its first
    ``words`` words, sliced off whole gathered rows: a gather of the
    sliced rows (``bitmap[idx, :words]``) lowers to a loop of one
    dynamic slice per block on a TPU."""
    return WindowData(idx, z[idx], x[idx], bitmap[idx][:, :words], valid)


@runtime_checkable
class BlockSource(Protocol):
    """What the sampling loop needs from an I/O backend."""

    num_blocks: int
    block_size: int
    v_z: int
    v_x: int
    tuples_per_block: np.ndarray  # (num_blocks,) host-side, for accounting

    def fetch(self, win: np.ndarray, pad_to: Optional[int] = None) -> WindowData: ...

    def stream(
        self, windows: Iterable[np.ndarray], pad_to: Optional[int] = None
    ) -> Iterator[WindowData]: ...


class InMemorySource:
    """The whole blocked dataset behind the source protocol.

    ``device_resident=True`` (default) keeps the block arrays on device:
    a fetch is one device-side gather program (`gather_window`; `gathers`
    counts them) and costs no host traffic beyond its index. With
    ``device_resident=False`` blocks stay in host memory (a stand-in for
    disk or a remote FS) and each fetch gathers on host and transfers
    one window — the case `PrefetchSource` exists to overlap.
    """

    def __init__(self, dataset: BlockedDataset, *, device_resident: bool = True):
        self.num_blocks = dataset.num_blocks
        self.block_size = dataset.block_size
        self.v_z = dataset.v_z
        self.v_x = dataset.v_x
        self.tuples_per_block = (dataset.z_blocks >= 0).sum(axis=1)
        self.device_resident = device_resident
        self.gathers = 0  # one-program window gathers dispatched
        self._gathers_lock = threading.Lock()  # prefetch workers fetch beside the caller
        self._words = dataset.bitmap.shape[1]
        if device_resident:
            self._z = jnp.asarray(dataset.z_blocks)
            self._x = jnp.asarray(dataset.x_blocks)
            self._bitmap = jnp.asarray(dataset.bitmap)
            if lane_pad := -self._words % LANES:
                self._bitmap = jnp.pad(self._bitmap, ((0, 0), (0, lane_pad)))
        else:
            self._z = np.asarray(dataset.z_blocks, np.int32)
            self._x = np.asarray(dataset.x_blocks, np.int32)
            self._bitmap = np.asarray(dataset.bitmap, np.uint32)

    def _pad(self, win: np.ndarray, pad_to: Optional[int]):
        win = np.asarray(win, np.int32).ravel()
        length = len(win) if pad_to is None else pad_to
        if len(win) > length:
            raise ValueError(f"window of {len(win)} blocks exceeds pad_to={length}")
        idx = np.zeros(length, np.int32)
        idx[: len(win)] = win
        valid = np.zeros(length, bool)
        valid[: len(win)] = True
        return idx, valid

    def fetch(self, win: np.ndarray, pad_to: Optional[int] = None) -> WindowData:
        idx, valid = self._pad(win, pad_to)
        if self.device_resident:
            with self._gathers_lock:
                self.gathers += 1
            return gather_window(self._z, self._x, self._bitmap, idx, valid, words=self._words)
        # Host-resident: stay numpy — the consumer decides when the one
        # host→device transfer happens (jit dispatch, or the pump's
        # sharded device_put of the assembled multi-worker window).
        return WindowData(idx, self._z[idx], self._x[idx], self._bitmap[idx], valid)

    def stream(
        self, windows: Iterable[np.ndarray], pad_to: Optional[int] = None
    ) -> Iterator[WindowData]:
        for win in windows:
            yield self.fetch(win, pad_to)


class ShardedSource(InMemorySource):
    """One data-parallel worker's contiguous block range.

    Built on `BlockedDataset.shard`; callers keep speaking GLOBAL block
    ids (so one read_mask/visit order spans the mesh) and the source
    translates to its local range. `owned(win)` filters a global window
    down to this worker's share.

    This is the per-worker feed for the manually driven
    `repro.core.distributed.make_distributed_round` ingest — it is NOT a
    drop-in dataset for `SharedCountsScheduler`/`run_engine`, whose
    visit order is 0-based over the whole dataset (the scheduler rejects
    it explicitly).
    """

    def __init__(
        self,
        dataset: BlockedDataset,
        num_shards: int,
        shard_id: int,
        *,
        device_resident: bool = True,
    ):
        if not (0 <= shard_id < num_shards):
            raise ValueError(f"need 0 <= shard_id < num_shards, got {shard_id}/{num_shards}")
        shard = dataset.shard(num_shards, shard_id)
        super().__init__(shard, device_resident=device_resident)
        per = -(-dataset.num_blocks // num_shards)
        self.lo = shard_id * per
        self.hi = self.lo + shard.num_blocks
        self.global_num_blocks = dataset.num_blocks

    def owned(self, win: np.ndarray) -> np.ndarray:
        win = np.asarray(win, np.int32).ravel()
        return win[(win >= self.lo) & (win < self.hi)]

    def fetch(self, win: np.ndarray, pad_to: Optional[int] = None) -> WindowData:
        win = np.asarray(win, np.int32).ravel()
        if win.size and ((win < self.lo) | (win >= self.hi)).any():
            raise ValueError(
                f"block ids outside shard range [{self.lo}, {self.hi}); filter with owned()"
            )
        wd = super().fetch(win - self.lo, pad_to)
        # match the leaf residency: a jnp scalar would silently drag a
        # host-resident window onto the default device
        lo = (np.int32 if isinstance(wd.indices, np.ndarray) else jnp.int32)(self.lo)
        return wd._replace(indices=wd.indices + lo)


def as_block_source(data) -> BlockSource:
    """BlockedDataset -> InMemorySource; an existing source passes through."""
    if isinstance(data, BlockedDataset):
        return InMemorySource(data)
    if isinstance(data, BlockSource):
        return data
    raise TypeError(f"expected BlockedDataset or BlockSource, got {type(data)!r}")
