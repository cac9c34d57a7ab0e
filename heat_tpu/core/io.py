"""Parallel I/O: HDF5, netCDF, CSV.

API parity with /root/reference/heat/core/io.py (``load`` :671 dispatching
by extension :1082-1133, ``load_hdf5`` :57, ``save_hdf5`` :166,
``load_csv`` :722, ``save_csv`` :948, ``supports_hdf5``/``supports_netcdf``).
The reference reads per-rank hyperslabs (each rank its ``comm.chunk``); a
single controller reads one slab per device and stitches the global array
with ``jax.make_array_from_single_device_arrays`` — in multi-process mode
each host reads only its addressable devices' slabs. netCDF support is
gated on the library being present (same as the reference).
"""

from __future__ import annotations

import os
import csv as _csv

import numpy as np

import jax
import jax.numpy as jnp

from typing import Optional, Tuple, Union

from . import types
from .communication import Communication, sanitize_comm
from .devices import Device, sanitize_device
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis
from ..observability.tracing import span as _span

__all__ = ["load", "load_csv", "save_csv", "save", "supports_hdf5", "supports_netcdf"]

try:
    import h5py

    __HDF5 = True
except ImportError:
    __HDF5 = False

try:
    import netCDF4

    __NETCDF = True
except ImportError:
    __NETCDF = False


def supports_hdf5() -> bool:
    """True if HDF5 I/O is available (reference: io.py supports_hdf5)."""
    return __HDF5


def supports_netcdf() -> bool:
    """True if netCDF I/O is available (reference: io.py supports_netcdf)."""
    return __NETCDF


def _from_numpy(data: np.ndarray, dtype, split, device, comm) -> DNDarray:
    from . import factories

    return factories.array(data, dtype=dtype, split=split, device=device, comm=comm)


def _np_storage_dtype(dtype) -> np.dtype:
    """On-disk numpy dtype for a framework dtype: bfloat16 has no
    HDF5/netCDF/CSV representation and is stored as float32 (exact)."""
    return np.dtype(np.float32) if dtype is types.bfloat16 else np.dtype(dtype.jax_type())


def _assemble_sharded(read_slab, gshape, dtype, split, device, comm) -> DNDarray:
    """Assemble a split DNDarray from per-device slab reads without ever
    materializing the global array on the host — the single-controller
    analog of the reference's per-rank hyperslab reads (io.py:57-150).

    ``read_slab(slices) -> np.ndarray`` reads one hyperslab from storage.
    Each device's (padded) block is read, zero-padded to the physical block
    extent, put on ITS device only, and the global jax.Array is stitched
    with ``make_array_from_single_device_arrays``.
    """
    from . import _padding
    from .devices import sanitize_device

    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    gshape = tuple(int(s) for s in gshape)
    split = sanitize_axis(gshape, split)
    jdt = _np_storage_dtype(dtype)

    if split is None:
        # replicated: every host reads the full array once
        data = np.asarray(read_slab(tuple(slice(0, s) for s in gshape)), dtype=jdt)
        return _from_numpy(data, dtype, None, device, comm)

    phys = _padding.phys_shape(gshape, split, comm.size)
    block = phys[split] // comm.size
    n = gshape[split]
    shards = []
    blk_shape = list(gshape)
    blk_shape[split] = block
    proc = jax.process_index()
    for r, dev in enumerate(comm.devices):
        if dev.process_index != proc:
            # multi-host: another host reads this slab — the reference's
            # per-rank hyperslab pattern (io.py:57); each process passes
            # only its addressable shards to make_array_from_single_device_arrays
            continue
        start = r * block
        stop = min(start + block, n)
        if stop > start:
            sl = tuple(
                slice(start, stop) if i == split else slice(0, s) for i, s in enumerate(gshape)
            )
            slab = np.asarray(read_slab(sl), dtype=jdt)
            if slab.shape[split] < block:
                widths = [(0, 0)] * len(gshape)
                widths[split] = (0, block - slab.shape[split])
                slab = np.pad(slab, widths)
        else:
            slab = np.zeros(tuple(blk_shape), dtype=jdt)
        if dtype is types.bfloat16:
            slab = slab.astype(jnp.bfloat16)
        shards.append(jax.device_put(slab, dev))
    arr = jax.make_array_from_single_device_arrays(tuple(phys), comm.sharding(len(gshape), split), shards)
    return DNDarray(arr, gshape, dtype, split, device, comm)


def _multiprocess_gather_for_save(data: DNDarray):
    """Multi-writer safety for saves (plain h5py/netCDF4 handles must not
    write one file from several processes concurrently — the reference
    relies on parallel drivers we don't have: h5py ``driver='mpio'``
    (reference io.py:214) and netCDF4 ``parallel=True`` (io.py:585); a
    plain multi-writer 'w' open truncates per process and corrupts).

    FULL-array gather — every host materializes the whole array. Kept
    only for the netCDF append-region path, whose target geometry cannot
    be decomposed into split-blocks; the main save paths stream bounded
    slabs via ``_multiprocess_save_slabs`` instead (ADVICE r3: the full
    allgather OOMs hosts at the 200 GB north-star scale).

    Returns ``(is_multiprocess, host_array_or_None)``.
    """
    if jax.process_count() == 1:
        return False, None
    arr = data.numpy()  # collective cross-process allgather
    if data.dtype is types.bfloat16:
        arr = np.asarray(arr, dtype=np.float32)
    return True, np.asarray(arr)


def _multiprocess_save_slabs(data: DNDarray):
    """Yield ``(global_slices, host_block)`` for a single-writer
    multi-process save with BOUNDED host memory: ONE split-block is
    allgathered per round (a collective — every process must drain the
    iterator, in step), never the whole array. Only process 0 should
    write the yielded slabs; other processes receive them too (the
    allgather is symmetric) and drop them immediately."""
    from jax.experimental import multihost_utils

    arr = data._phys
    # bf16 upcasts PER SLAB (below) — an up-front astype of the global
    # array would materialize a full-size f32 copy across HBM, defeating
    # the bounded-memory point of the streaming
    cast = data.dtype is types.bfloat16
    split = data.split
    if split is None or arr.is_fully_addressable:
        with _span("ht.sync.read", what="io.save"):
            host = np.asarray(jax.device_get(arr))
        if cast:
            host = host.astype(np.float32)
        if host.shape != tuple(data.shape):
            host = host[tuple(slice(0, s) for s in data.shape)]
        yield tuple(slice(0, s) for s in data.shape), host
        return
    n = data.shape[split]
    block = arr.shape[split] // data.comm.size
    for r in range(data.comm.size):
        start = r * block
        stop = min(start + block, n)
        if stop <= start:
            continue
        idx = [slice(None)] * data.ndim
        idx[split] = slice(start, stop)
        slab = arr[tuple(idx)]  # global slice of the sharded array
        if cast:
            slab = slab.astype(jnp.float32)  # one block, bounded
        with _span("ht.sync.read", what="io.save"):
            host = np.asarray(multihost_utils.process_allgather(slab, tiled=True))
        sl = tuple(
            slice(start, stop) if i == split else slice(0, s)
            for i, s in enumerate(data.shape)
        )
        yield sl, host[tuple(slice(0, s.stop - s.start) for s in sl)]


def _drain(slab_iter) -> None:
    """Finish a collective slab stream unconditionally — every process
    must participate in every per-slab allgather even when the WRITER
    fails mid-stream (an undrained iterator would leave the other
    processes blocked inside process_allgather while the writer's
    exception never propagates)."""
    for _ in slab_iter:
        pass


def _sync_processes(tag: str) -> None:
    """Cross-process barrier so no host proceeds past a save before the
    writer (process 0) has finished — the analog of the reference's
    trailing ``comm.Barrier()`` in its rank-ordered write loops."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def _write_shards(data: DNDarray, write_slab) -> None:
    """Write a DNDarray shard-by-shard: ``write_slab(global_slices,
    host_block)`` receives each device's LOGICAL block — the global array is
    never gathered (the reference's rank-ordered writes, io.py:166-260)."""
    if data.split is None:
        arr = data._phys
        if data.dtype is types.bfloat16:
            arr = arr.astype(jnp.float32)
        with _span("ht.sync.read", what="io.save"):
            host = np.asarray(jax.device_get(arr))
        write_slab(tuple(slice(0, s) for s in data.shape), host)
        return
    split = data.split
    n = data.shape[split]
    block = data._phys.shape[split] // data.comm.size
    for r in range(data.comm.size):
        start = r * block
        stop = min(start + block, n)
        if stop <= start:
            continue
        shard = None
        for s in data._phys.addressable_shards:
            # single-device/replicated shards carry slice(None) indices
            s_start = s.index[split].start if s.index[split].start is not None else 0
            if s_start == start:
                shard = s.data
                break
        if shard is None:
            if jax.process_count() == 1:
                raise RuntimeError(
                    f"no addressable shard found for block {r} (start {start}) — "
                    f"shard indices: {[s.index for s in data._phys.addressable_shards]}"
                )
            continue  # non-addressable in multi-process; another host writes it
        valid = [slice(None)] * data.ndim
        valid[split] = slice(0, stop - start)
        with _span("ht.sync.read", what="io.save"):
            host = np.asarray(jax.device_get(shard[tuple(valid)]))
        if data.dtype is types.bfloat16:
            host = host.astype(np.float32)
        sl = tuple(
            slice(start, stop) if i == split else slice(0, s) for i, s in enumerate(data.shape)
        )
        write_slab(sl, host)


if __HDF5:
    __all__.extend(["load_hdf5", "save_hdf5"])

    def load_hdf5(
        path: str,
        dataset: str,
        dtype=types.float32,
        load_fraction: float = 1.0,
        split: Optional[int] = None,
        device=None,
        comm=None,
    ) -> DNDarray:
        """Load a dataset from an HDF5 file (reference: io.py:57). The
        reference reads one hyperslab per rank; in multi-process mode we
        read one slab per host and assemble, single-controller reads once.
        """
        if not isinstance(path, str):
            raise TypeError(f"path must be str, got {type(path)}")
        if not isinstance(dataset, str):
            raise TypeError(f"dataset must be str, got {type(dataset)}")
        comm = sanitize_comm(comm)
        dtype = types.canonical_heat_type(dtype)
        with h5py.File(path, "r") as handle:
            ds = handle[dataset]
            gshape = list(ds.shape)
            if load_fraction < 1.0 and split is not None:
                gshape[split] = int(gshape[split] * load_fraction)
            return _assemble_sharded(
                lambda sl: ds[sl], tuple(gshape), dtype, split, device, comm
            )

    def save_hdf5(data: DNDarray, path: str, dataset: str, mode: str = "w", **kwargs) -> None:
        """Save a DNDarray to HDF5 (reference: io.py:166). Single-process:
        one hyperslab write per device shard, global array never gathered.
        Multi-process: collective allgather + single-writer (process 0) —
        see ``_multiprocess_gather_for_save``."""
        if not isinstance(data, DNDarray):
            raise TypeError(f"data must be a DNDarray, got {type(data)}")
        if not isinstance(path, str):
            raise TypeError(f"path must be str, got {type(path)}")
        np_dtype = kwargs.pop("dtype", _np_storage_dtype(data.dtype))  # h5py casts on write
        if jax.process_count() > 1:
            # bounded-memory single-writer: stream one split-block per
            # collective round (see _multiprocess_save_slabs)
            slabs = _multiprocess_save_slabs(data)
            if jax.process_index() == 0:
                try:
                    with h5py.File(path, mode) as handle:
                        ds = handle.create_dataset(
                            dataset, shape=data.shape, dtype=np_dtype, **kwargs
                        )
                        for sl, host in slabs:
                            ds[sl] = host
                finally:
                    _drain(slabs)  # keep collectives in step on writer error
            else:
                _drain(slabs)  # collective participation, nothing kept
            _sync_processes("heat_tpu.io.save_hdf5")
            return
        with h5py.File(path, mode) as handle:
            ds = handle.create_dataset(dataset, shape=data.shape, dtype=np_dtype, **kwargs)
            _write_shards(data, lambda sl, host: ds.__setitem__(sl, host))


if __NETCDF:
    __all__.extend(["load_netcdf", "save_netcdf"])

    def load_netcdf(path, variable, dtype=types.float32, split=None, device=None, comm=None, **kwargs):
        """Load a variable from a netCDF file (reference: io.py:283 — one
        hyperslab per rank). Split loads read one slab per device; the
        global array is never materialized on the host."""
        with netCDF4.Dataset(path, "r") as handle:
            var = handle.variables[variable]
            gshape = tuple(var.shape)
            return _assemble_sharded(
                lambda sl: np.asarray(var[sl]),
                gshape,
                types.canonical_heat_type(dtype),
                split,
                device,
                comm,
            )

    def save_netcdf(
        data,
        path,
        variable,
        mode="w",
        dimension_names=None,
        is_unlimited=False,
        file_slices=slice(None),
        **kwargs,
    ):
        """Save a DNDarray to netCDF (reference: io.py:366).

        ``mode``: 'w' truncates, 'a'/'r+' opens for update. Appending
        along a dimension (the reference's time-series pattern) works by
        creating the variable once with ``is_unlimited=True`` and then
        writing subsequent steps with ``mode='r+'`` and ``file_slices``
        addressing the new region, e.g. ``file_slices=slice(t, t+1)``.
        """
        if mode not in ("w", "a", "r+"):
            raise ValueError(f"mode must be one of 'w', 'a', 'r+', got {mode!r}")
        if not isinstance(data, DNDarray):
            raise TypeError(f"data must be a DNDarray, got {type(data)}")
        np_dtype = _np_storage_dtype(data.dtype)
        if dimension_names is None:
            dims = [f"{variable}_dim{i}" for i in range(data.ndim)]
        elif isinstance(dimension_names, str):
            dims = [dimension_names]
        else:
            dims = list(dimension_names)
        if len(dims) != data.ndim:
            raise ValueError(
                f"{len(dims)} dimension names given for {data.ndim} dimensions"
            )
        multi = jax.process_count() > 1
        trivial = (
            file_slices == slice(None)
            or file_slices is Ellipsis
            or (
                isinstance(file_slices, tuple)
                and all(s == slice(None) or s is Ellipsis for s in file_slices)
            )
        )
        host_arr = None
        if multi and trivial:
            slabs = _multiprocess_save_slabs(data)  # bounded-memory stream
        elif multi:
            # append-region addressing: the caller's target geometry does
            # not decompose into split-blocks — full gather (whole-array
            # host memory; appends along an unlimited dim are small)
            _, host_arr = _multiprocess_gather_for_save(data)
        if multi and jax.process_index() != 0:
            # drain the collective slab stream; only process 0 opens the
            # file (plain netCDF4 handles are not multi-writer safe —
            # reference uses parallel=True, io.py:585)
            if trivial:
                _drain(slabs)
            _sync_processes("heat_tpu.io.save_netcdf")
            return
        if multi and trivial:
            try:
                with netCDF4.Dataset(path, mode) as handle:
                    for i, name in enumerate(dims):
                        if name not in handle.dimensions:
                            handle.createDimension(name, None if is_unlimited else data.shape[i])
                    if variable in handle.variables:
                        var = handle.variables[variable]
                    else:
                        var = handle.createVariable(variable, np_dtype, tuple(dims), **kwargs)
                    for sl, host in slabs:
                        var[sl] = host
            finally:
                _drain(slabs)  # keep collectives in step on writer error
            _sync_processes("heat_tpu.io.save_netcdf")
            return
        with netCDF4.Dataset(path, mode) as handle:
            for i, name in enumerate(dims):
                if name not in handle.dimensions:
                    handle.createDimension(name, None if is_unlimited else data.shape[i])
            if variable in handle.variables:
                var = handle.variables[variable]
            else:
                var = handle.createVariable(variable, np_dtype, tuple(dims), **kwargs)
            if multi:
                var[file_slices] = host_arr
            elif trivial:
                # one hyperslab write per device shard, never gathering
                # (the reference's rank-ordered writes, io.py:366)
                _write_shards(data, lambda sl, host: var.__setitem__(sl, host))
            else:
                # append-region addressing: the target region's geometry is
                # the caller's (e.g. a new step along an unlimited dim) —
                # write it in one piece
                arr = data.numpy()
                if data.dtype is types.bfloat16:
                    arr = np.asarray(arr, dtype=np.float32)
                var[file_slices] = arr
        if multi:
            _sync_processes("heat_tpu.io.save_netcdf")


_CSV_ANCHOR_STRIDE = 256  # one recorded line-start offset per 256 lines


def _csv_data_start(path: str, header_lines: int) -> int:
    """Byte offset of the first data row (after ``header_lines`` lines)."""
    if header_lines <= 0:
        return 0
    off = 0
    with open(path, "rb") as fh:
        for _ in range(header_lines):
            line = fh.readline()
            if not line:
                break
            off += len(line)
    return off


def _csv_scan_range(path: str, start: int, stop: int, data_start: int, file_size: int):
    """Scan bytes [start, stop) of the file for line starts — each host
    touches ONLY its range (the reference's per-rank byte-range scan,
    io.py:807-830). Returns (line_count, anchors) where ``anchors``
    records the byte offset of every ``_CSV_ANCHOR_STRIDE``-th line this
    range owns (a line is owned by the range containing the newline that
    precedes it), bounding index memory at ~8 bytes per 256 lines."""
    count = 0
    anchors = []
    # the very first data row has no preceding newline; a header-only /
    # empty file (data_start == file_size) has no first row to seed
    if start == data_start and data_start < file_size:
        anchors.append(data_start)
        count = 1
    chunk_size = 1 << 22
    with open(path, "rb") as fh:
        fh.seek(start)
        pos = start
        remaining = stop - start
        while remaining > 0:
            buf = fh.read(min(chunk_size, remaining))
            if not buf:
                break
            idx = buf.find(b"\n")
            while idx >= 0:
                line_start = pos + idx + 1
                if line_start < file_size:  # trailing newline starts no row
                    if count % _CSV_ANCHOR_STRIDE == 0:
                        anchors.append(line_start)
                    count += 1
                idx = buf.find(b"\n", idx + 1)
            pos += len(buf)
            remaining -= len(buf)
    return count, anchors


def _load_csv_parallel(
    path: str, header_lines: int, sep: str, dtype, encoding: str, device, comm
) -> DNDarray:
    """Multi-process split=0 CSV ingest by byte ranges (the TPU-native
    analog of reference io.py:818-900): every host scans only its byte
    range for line starts, the tiny stride-compressed index is
    allgathered, and each host then reads exactly the byte spans that
    cover its addressable devices' row blocks. No host ever holds the
    whole file. Interior rows must be non-empty and uniform-width (the
    reference's empty-line tolerance is a torch-side repack this path
    trades for bounded memory)."""
    import io as _io

    from jax.experimental import multihost_utils

    file_size = os.path.getsize(path)
    data_start = _csv_data_start(path, header_lines)
    nproc = jax.process_count()
    p = jax.process_index()
    span = file_size - data_start
    start = data_start + p * span // nproc
    stop = data_start + (p + 1) * span // nproc
    count, anchors = _csv_scan_range(path, start, stop, data_start, file_size)

    # exchange (count, n_anchors), then the padded anchor arrays
    meta = multihost_utils.process_allgather(
        np.array([count, len(anchors)], dtype=np.int64)
    ).reshape(nproc, 2)
    counts = meta[:, 0]
    max_anchors = int(meta[:, 1].max())
    padded = np.full(max(max_anchors, 1), -1, dtype=np.int64)
    padded[: len(anchors)] = np.asarray(anchors, dtype=np.int64)
    all_anchors = multihost_utils.process_allgather(padded).reshape(nproc, -1)
    cum = np.concatenate([[0], np.cumsum(counts)])
    n_rows = int(cum[-1])

    # column count from the first data row (every host reads one line)
    with open(path, "rb") as fh:
        fh.seek(data_start)
        first = fh.readline().decode(encoding)
    n_cols = first.rstrip("\r\n").count(sep) + 1 if first.strip() else 1

    def locate(row: int) -> int:
        """Byte offset of global data row ``row``'s line start."""
        if row >= n_rows:
            return file_size
        q = int(np.searchsorted(cum, row, side="right") - 1)
        j = row - int(cum[q])
        a = j // _CSV_ANCHOR_STRIDE
        off = int(all_anchors[q, a])
        skip = j - a * _CSV_ANCHOR_STRIDE
        if skip == 0:
            return off
        with open(path, "rb") as fh:
            fh.seek(off)
            for _ in range(skip):
                fh.readline()
            return fh.tell()

    np_dtype = _np_storage_dtype(dtype)

    def read_slab(sl):
        rstart, rstop = sl[0].start or 0, sl[0].stop
        b0, b1 = locate(rstart), locate(rstop)
        with open(path, "rb") as fh:
            fh.seek(b0)
            raw = fh.read(b1 - b0)
        block = np.genfromtxt(
            _io.BytesIO(raw), delimiter=sep, dtype=np_dtype, encoding=encoding
        ).reshape(rstop - rstart, n_cols)
        return block[(slice(None),) + tuple(sl[1:])]

    return _assemble_sharded(read_slab, (n_rows, n_cols), dtype, 0, device, comm)


def load_csv(
    path: str,
    header_lines: int = 0,
    sep: str = ",",
    dtype=types.float32,
    encoding: str = "utf-8",
    split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Load a CSV file (reference: io.py:722). split=0 in a multi-process
    world reads per-host byte ranges (see ``_load_csv_parallel``); other
    configurations parse on the controller like the reference's
    split=None/1 full-file passes (io.py:805, 925-946)."""
    if not isinstance(path, str):
        raise TypeError(f"path must be str, got {type(path)}")
    if split not in (None, 0, 1):
        raise ValueError(f"split must be in [None, 0, 1], but is {split}")
    dtype = types.canonical_heat_type(dtype)
    if split == 0 and jax.process_count() > 1:
        return _load_csv_parallel(path, header_lines, sep, dtype, encoding, device, comm)
    np_dtype = _np_storage_dtype(dtype)
    data = np.genfromtxt(
        path, delimiter=sep, skip_header=header_lines, dtype=np_dtype, encoding=encoding
    )
    if data.ndim == 1:
        # genfromtxt flattens both single-column and single-row files;
        # disambiguate by counting separators in the first data line
        with open(path, encoding=encoding) as fh:
            for _ in range(header_lines):
                fh.readline()
            first = fh.readline().strip()
        ncols = first.count(sep) + 1 if first else 1
        data = data.reshape(1, -1) if ncols > 1 else data.reshape(-1, 1)
    return _from_numpy(data, dtype, split, device, comm)


def save_csv(
    data: DNDarray,
    path: str,
    header_lines=None,
    sep: str = ",",
    decimals: int = -1,
    **kwargs,
) -> None:
    """Save a DNDarray to CSV (reference: io.py:948). Multi-process:
    single-writer (process 0) over a bounded slab stream — one
    split-block allgathered per collective round, never the whole array
    (same policy as save_hdf5)."""
    if not isinstance(data, DNDarray):
        raise TypeError(f"data must be a DNDarray, got {type(data)}")
    fmt = f"%.{decimals}f" if decimals >= 0 else "%s"
    header = "\n".join(header_lines) if header_lines else ""
    if jax.process_count() > 1:
        if data.split not in (None, 0):
            data = data.resplit(0)  # CSV appends rows; stream row blocks
        slabs = _multiprocess_save_slabs(data)
        if jax.process_index() == 0:
            try:
                with open(path, "w") as fh:
                    if header:
                        fh.write(header + "\n")
                    for _, host in slabs:
                        if host.ndim == 1:
                            host = host.reshape(-1, 1)
                        np.savetxt(fh, host, delimiter=sep, fmt=fmt, comments="")
            finally:
                _drain(slabs)  # keep collectives in step on writer error
        else:
            _drain(slabs)
        _sync_processes("heat_tpu.io.save_csv")
        return
    arr = data.numpy()
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    np.savetxt(path, arr, delimiter=sep, fmt=fmt, header=header, comments="")


def load(path: str, *args, **kwargs) -> DNDarray:
    """Load by file extension (reference: io.py:1082-1133)."""
    if not isinstance(path, str):
        raise TypeError(f"path must be str, got {type(path)}")
    ext = os.path.splitext(path)[-1].lower().strip()
    if ext in (".h5", ".hdf5"):
        if not __HDF5:
            raise RuntimeError(f"hdf5 is required for file extension {ext}")
        return load_hdf5(path, *args, **kwargs)
    if ext in (".nc", ".nc4", ".netcdf"):
        if not __NETCDF:
            raise RuntimeError(f"netcdf is required for file extension {ext}")
        return load_netcdf(path, *args, **kwargs)
    if ext == ".csv":
        return load_csv(path, *args, **kwargs)
    raise ValueError(f"unsupported file extension {ext}")


def save(data: DNDarray, path: str, *args, **kwargs) -> None:
    """Save by file extension (reference: io.py:~1050)."""
    if not isinstance(path, str):
        raise TypeError(f"path must be str, got {type(path)}")
    ext = os.path.splitext(path)[-1].lower().strip()
    if ext in (".h5", ".hdf5"):
        if not __HDF5:
            raise RuntimeError(f"hdf5 is required for file extension {ext}")
        return save_hdf5(data, path, *args, **kwargs)
    if ext in (".nc", ".nc4", ".netcdf"):
        if not __NETCDF:
            raise RuntimeError(f"netcdf is required for file extension {ext}")
        return save_netcdf(data, path, *args, **kwargs)
    if ext == ".csv":
        return save_csv(data, path, *args, **kwargs)
    raise ValueError(f"unsupported file extension {ext}")
