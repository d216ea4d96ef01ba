"""Hyperslab sample store — the parallel-HDF5/MPI-IO analogue (paper §III-B).

Samples are stored one file per sample (``.npy``, NDHWC layout without the
N dim: (D, H, W, C)), and ``read_hyperslab(sample, slices)`` touches ONLY
the bytes of the requested 3-D fragment — each (logical) rank reads
exactly its hyperslab, which is what lets I/O strong-scale with the
spatial partitioning. ``read_hyperslab_into`` writes the fragment into a
caller's array (a loader's batch buffer): a slab that is contiguous in
the file (only depth sliced: a whole sample, or a depth-sharded shard)
is one ``seek`` and one ``readinto``; any other slab is copied out of a
memory map.

Byte counters are kept so the I/O benchmark can report per-rank PFS traffic
(the quantity that must shrink as spatial parallelism grows — paper Fig. 5).

Transient-failure handling (DESIGN.md §11): at the paper's scale a PFS
read fails routinely and transiently; every store read retries with
exponential backoff through a capped attempt count (the ``loader.read``
fault site fires inside the retry loop, so injected transients exercise
exactly this path). A read that exhausts its attempts raises
``StoreReadError`` naming the shard file — not a bare ``OSError`` three
layers down. ``retries`` counts absorbed failures for the §11 telemetry.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple, TypeVar

import numpy as np

from repro.core import faults

MAX_READ_ATTEMPTS = 4
BACKOFF_BASE_S = 0.005  # 5ms, 10ms, 20ms, ... between attempts

T = TypeVar("T")


class StoreReadError(IOError):
    """A store read failed every attempt; names the file and the count."""

    def __init__(self, path: str, attempts: int, last: BaseException):
        self.path = path
        self.attempts = attempts
        super().__init__(
            f"store read of {path!r} failed after {attempts} attempts "
            f"(last error: {last})")


class HyperslabStore:
    """``throttle_mbps`` emulates a bandwidth-limited PFS (the paper's
    regime — local page cache makes reads unrealistically free): each
    hyperslab read sleeps ``nbytes / bandwidth``. The sleep releases the
    GIL, so a prefetching loader can hide it under device compute exactly
    the way a real PFS wait is hidden. ``None`` (default) reads at disk
    speed; benches opt in, production paths never set it."""

    def __init__(self, root: str, throttle_mbps: Optional[float] = None):
        self.root = root
        self.throttle_mbps = throttle_mbps
        self.bytes_read = 0
        self.reads = 0
        self.retries = 0
        self._dtypes: Dict[str, np.dtype] = {}
        with open(os.path.join(root, "index.json")) as f:
            self.index = json.load(f)
        self.num_samples = self.index["num_samples"]
        self.sample_shape = tuple(self.index["sample_shape"])  # (D,H,W,C)
        self.target_dim = self.index.get("target_dim", 0)
        self.label_kind = self.index.get("label_kind", "vector")
        self._targets = (
            self._retrying(os.path.join(root, "targets.npy"),
                           lambda: np.load(os.path.join(root, "targets.npy")))
            if os.path.exists(os.path.join(root, "targets.npy")) else None
        )

    def _path(self, i: int, what: str = "x") -> str:
        return os.path.join(self.root, f"{what}_{i:06d}.npy")

    def _retrying(self, path: str, read: Callable[[], T]) -> T:
        """Run ``read`` with capped exponential-backoff retries on I/O
        errors (missing files don't retry — they are config errors, and
        waiting on them would only mask the message)."""
        last: BaseException
        for attempt in range(MAX_READ_ATTEMPTS):
            try:
                faults.fire("loader.read", path=path)
                return read()
            except FileNotFoundError:
                raise
            except OSError as e:
                last = e
                if attempt + 1 < MAX_READ_ATTEMPTS:
                    self.retries += 1
                    time.sleep(BACKOFF_BASE_S * 2 ** attempt)
        raise StoreReadError(path, MAX_READ_ATTEMPTS, last)

    def read_hyperslab(self, i: int, slices: Tuple[slice, ...],
                       what: str = "x") -> np.ndarray:
        """Read one (D,H,W,C) fragment into a fresh array."""
        return self._read(i, slices, what, None)

    def read_hyperslab_into(self, i: int, slices: Tuple[slice, ...],
                            out: np.ndarray, what: str = "x") -> np.ndarray:
        """Read one (D,H,W,C) fragment into ``out``, whose shape and dtype
        must be the fragment's (from the file's header); returns ``out``.
        A failed attempt may leave ``out`` partly written; the retry
        rewrites all of it."""
        return self._read(i, slices, what, out)

    def _read(self, i: int, slices: Tuple[slice, ...], what: str,
              out: Optional[np.ndarray]) -> np.ndarray:
        path = self._path(i, what)
        arr = self._retrying(path, lambda: _read_slab(path, slices, out))
        self.bytes_read += arr.nbytes
        self.reads += 1
        if self.throttle_mbps:
            time.sleep(arr.nbytes / (self.throttle_mbps * 1e6))
        return arr

    def dtype(self, what: str = "x") -> np.dtype:
        """The element type of the ``what`` files (sample 0's header)."""
        if what not in self._dtypes:
            path = self._path(0, what)
            with open(path, "rb") as f:
                self._dtypes[what] = _npy_header(f, path)[2]
        return self._dtypes[what]

    def read_full(self, i: int, what: str = "x") -> np.ndarray:
        return self.read_hyperslab(
            i, tuple(slice(None) for _ in self.sample_shape), what)

    def target(self, i: int) -> np.ndarray:
        return self._targets[i]

    def reset_counters(self):
        self.bytes_read = 0
        self.reads = 0
        self.retries = 0


def _npy_header(f, path: str) -> Tuple[Tuple[int, ...], bool, np.dtype, int]:
    """(shape, fortran_order, dtype, data offset) of the ``.npy`` file open
    as ``f``, left positioned at its data."""
    version = np.lib.format.read_magic(f)
    if version == (1, 0):
        shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
    elif version == (2, 0):
        shape, fortran, dtype = np.lib.format.read_array_header_2_0(f)
    else:
        raise ValueError(f"{path!r}: unsupported .npy version {version}")
    return shape, fortran, dtype, f.tell()


def _read_slab(path: str, slices: Tuple[slice, ...],
               out: Optional[np.ndarray]) -> np.ndarray:
    """One attempt at the fragment ``slices`` of ``path``, into ``out``
    (a fresh array when None). A file shorter than its header says, or a
    short read, raises ``OSError`` so the caller's retry loop sees it."""
    with open(path, "rb", buffering=0) as f:
        shape, fortran, dtype, offset = _npy_header(f, path)
        idx = tuple(slices) + (slice(None),) * (len(shape) - len(slices))
        bounds = [s.indices(n) for s, n in zip(idx, shape)]
        slab = tuple(len(range(*b)) for b in bounds)
        if out is None:
            out = np.empty(slab, dtype)
        elif out.shape != slab or out.dtype != dtype:
            raise ValueError(
                f"{path!r}: fragment is {slab} {dtype}, the destination "
                f"{out.shape} {out.dtype}")
        row = int(np.prod(shape[1:])) * dtype.itemsize
        size = offset + shape[0] * row
        if os.fstat(f.fileno()).st_size < size:
            raise OSError(f"{path!r} is truncated: under {size} bytes")
        # C-order with only depth sliced: the fragment is one byte range
        contiguous = (not fortran and bounds[0][2] == 1
                      and all(b == (0, n, 1)
                              for b, n in zip(bounds[1:], shape[1:])))
        if contiguous and out.flags.c_contiguous:
            f.seek(offset + bounds[0][0] * row)
            view = memoryview(out).cast("B")
            got = 0
            while got < len(view):
                n = f.readinto(view[got:])
                if not n:
                    raise OSError(f"{path!r}: short read, {got} of "
                                  f"{len(view)} bytes")
                got += n
        else:
            np.copyto(out, np.memmap(f, dtype, "r", offset, shape,
                                     "F" if fortran else "C")[idx])
    return out


def write_dataset(
    root: str,
    cubes: Sequence[np.ndarray],        # each (D, H, W, C)
    targets: Optional[np.ndarray] = None,  # (N, target_dim) regression
    labels: Optional[Sequence[np.ndarray]] = None,  # per-voxel seg labels
) -> None:
    os.makedirs(root, exist_ok=True)
    for i, c in enumerate(cubes):
        np.save(os.path.join(root, f"x_{i:06d}.npy"), c)
        if labels is not None:
            np.save(os.path.join(root, f"y_{i:06d}.npy"), labels[i])
    index = {
        "num_samples": len(cubes),
        "sample_shape": list(cubes[0].shape),
        "target_dim": 0 if targets is None else int(targets.shape[1]),
        "label_kind": "voxel" if labels is not None else "vector",
    }
    if targets is not None:
        np.save(os.path.join(root, "targets.npy"),
                targets.astype(np.float32))
    with open(os.path.join(root, "index.json"), "w") as f:
        json.dump(index, f)
