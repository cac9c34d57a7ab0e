"""Deterministic slab-streamed checkpointing (ISSUE 13).

A preemptible fleet loses slices as a matter of course; the only state
that survives is what reached a persistent store before the preemption.
This module is the durable half of ``heat_tpu.resilience``: a versioned
on-disk envelope capturing estimator/optimizer state mid-``fit`` —
cluster centers and streaming counts, ``DataParallelOptimizer`` params,
optimizer state and the error-feedback carry, and the EXPLICIT RNG
stream state — with three hard properties:

- **O(slab) host memory** — arrays are written as bounded split-block
  slabs through the same per-device-block machinery ``core/io.py``
  streams saves with: a sharded operand contributes one device block at
  a time, an unsharded one is chunked at :data:`SLAB_BYTES`. Nothing
  ever materializes a second full copy on the host; the observed
  high-water mark is RECORDED in the envelope (``max_slab_bytes``) so
  tests assert the bound instead of eyeballing it.
- **Integrity + provenance** — every entry carries a sha256 computed
  while its slabs stream out (the AOT-cache keying discipline applied
  to training state), and the envelope meta stamps the PR 12 gate
  roster (``gates.program_gate_roster``), the resolved topology, the
  world size and the jax/heat_tpu versions. A truncated or bit-flipped
  entry fails verification as :class:`CheckpointCorrupt` — restore then
  falls back to the previous committed step, never resumes from garbage.
- **Atomic commit** — a checkpoint is written under
  ``step_<N>.tmp-<pid>`` (data files fsynced, then the meta, which is
  written LAST) and becomes visible via one ``os.rename``. A crash at
  any byte leaves either the previous committed step or an ignorable
  ``.tmp-*`` orphan; there is no torn-but-visible state.

``restore_latest`` re-shards every saved array onto the CURRENT world
(a restored split-0 operand lands on however many devices survive), so
the ``fit(ckpt=)`` / ``partial_fit`` resume contract holds across a
world resize — the resumed stream replays the remaining windows on the
new mesh and, because the streaming updates are replicated-window
programs, reproduces the uninterrupted run's bits exactly (pinned by
the chaos CI leg at 8 AND 5 virtual devices).

Trust boundary: like the AOT store, ``HEAT_TPU_CKPT_DIR`` must carry
the same write permissions as the deployment's code. Restore parses
JSON and raw little-endian buffers only — no pickle — but training
state is still an input an attacker who owns the directory controls.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil

import numpy as np

from typing import Any, Dict, Optional, Tuple

from ..core import gates as _gates
from ..observability import telemetry as _telemetry
from ..observability import tracing as _tracing

__all__ = [
    "CKPT_DIR_ENV",
    "CheckpointConfig",
    "CheckpointCorrupt",
    "FORMAT",
    "RESILIENCE_ENV",
    "SLAB_BYTES",
    "ckpt_dir",
    "latest_step",
    "list_steps",
    "load",
    "resilience_enabled",
    "resilience_mode",
    "restore_latest",
    "save",
    "step_path",
]

RESILIENCE_ENV = "HEAT_TPU_RESILIENCE"
CKPT_DIR_ENV = "HEAT_TPU_CKPT_DIR"

#: envelope format version — bumped on layout changes; a mismatch is
#: :class:`CheckpointCorrupt` (never a best-effort parse).
FORMAT = 1

#: slab granularity for UNSHARDED entries (numpy / replicated jax
#: arrays): 64 MiB keeps host staging far below any operand of
#: interest while amortizing syscall overhead; sharded entries stream
#: at their natural split-block size instead (the io.py unit).
SLAB_BYTES = 64 << 20

_STEP_RE = re.compile(r"^step_(\d{8})$")


# --------------------------------------------------------------------- #
# the gate
# --------------------------------------------------------------------- #
def resilience_mode() -> str:
    """Resolved ``HEAT_TPU_RESILIENCE`` mode (``"0"``/``"1"``/``"auto"``).
    ``0`` disables the elastic runtime everywhere — no checkpoint hooks,
    no world-epoch guards, no drain fences: the exact pre-resilience
    code paths (the escape hatch every gated subsystem ships). ``1``
    forces it (the chaos CI leg); ``auto`` (default) engages where the
    caller explicitly hands the runtime a checkpoint config or a world
    watcher."""
    v = _gates.get(RESILIENCE_ENV, "auto").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "0"
    if v in ("1", "on", "true", "force", "yes"):
        return "1"
    return "auto"


def resilience_enabled(explicit: bool = False) -> bool:
    """Does the elastic runtime engage? ``explicit`` = the caller handed
    it a checkpoint config / watcher (the ``auto`` trigger)."""
    mode = resilience_mode()
    if mode == "0":
        return False
    if mode == "1":
        return True
    return bool(explicit)


def ckpt_dir(override: Optional[str] = None) -> str:
    """The checkpoint store root: ``override``, else
    ``HEAT_TPU_CKPT_DIR``, else the user default."""
    if override:
        return os.path.expanduser(override)
    return os.path.expanduser(
        _gates.get(
            CKPT_DIR_ENV,
            os.path.join("~", ".cache", "heat_tpu", "ckpt"),
        )
    )


class CheckpointCorrupt(RuntimeError):
    """A checkpoint failed verification: truncated/bit-flipped entry
    (sha256 mismatch), malformed meta, or a format-version mismatch.
    ``restore_latest`` treats it as "this step never committed" and
    falls back to the previous one."""


class CheckpointConfig:
    """How a resumable ``fit`` checkpoints.

    Parameters
    ----------
    directory : store root (default: :func:`ckpt_dir`).
    tag : the envelope family one training run writes under.
    every : checkpoint every N stream windows (``fit(ckpt=)``).
    keep : committed steps retained per tag (older ones are pruned
        after each successful commit; >= 2 so a truncated newest step
        always has a committed predecessor to fall back to).
    """

    def __init__(self, directory: Optional[str] = None, tag: str = "fit",
                 every: int = 1, keep: int = 2):
        if every < 1:
            raise ValueError(f"ckpt.every must be >= 1, got {every}")
        if keep < 2:
            raise ValueError(f"ckpt.keep must be >= 2 (fallback needs a predecessor), got {keep}")
        self.directory = ckpt_dir(directory)
        self.tag = str(tag)
        self.every = int(every)
        self.keep = int(keep)

    def __repr__(self) -> str:
        return (
            f"CheckpointConfig(directory={self.directory!r}, tag={self.tag!r}, "
            f"every={self.every}, keep={self.keep})"
        )


# --------------------------------------------------------------------- #
# envelope layout helpers
# --------------------------------------------------------------------- #
def step_path(directory: str, tag: str, step: int) -> str:
    return os.path.join(directory, tag, f"step_{int(step):08d}")


def list_steps(directory: str, tag: str) -> list:
    """Committed step numbers for ``tag``, ascending (``.tmp-*`` write
    orphans are invisible by construction)."""
    root = os.path.join(directory, tag)
    try:
        names = os.listdir(root)
    except OSError:
        return []
    out = []
    for n in names:
        m = _STEP_RE.match(n)
        if m and os.path.isfile(os.path.join(root, n, "meta.json")):
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(directory: str, tag: str) -> Optional[int]:
    steps = list_steps(directory, tag)
    return steps[-1] if steps else None


def _stamps() -> Dict[str, Any]:
    """Provenance stamps: versions, world geometry, the resolved
    topology, and the PR 12 program-affecting gate ROSTER — so an
    operator can always answer "what produced this checkpoint"."""
    import jax

    from ..core import communication as _comm
    from ..version import __version__

    world = _comm.get_comm()
    try:
        size = int(world.size)
        topo = str(world.topology)
    except Exception:
        size, topo = -1, "flat"
    return {
        "heat_tpu": __version__,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "world_size": size,
        "topology": topo,
        "gate_roster": _gates.program_gate_roster(),
    }


class _SlabWriter:
    """Streams one entry's bytes to disk while hashing them — the
    single funnel every entry kind writes through, so the sha256 and
    the O(slab) high-water mark are computed in the same pass.

    The durable commit is pipelined so it runs at the DISK edge, not
    the hash edge: sha256 rides a background hasher thread (a bounded
    queue of the slab views — still O(slab) host memory), and after
    each slab the kernel is nudged to start writeback early
    (``sync_file_range``-style via a background fsync), so the final
    close-time fsync flushes a mostly-clean file instead of paying the
    whole flush serially after the whole write. Measured on the dev
    box: inline hashing + one trailing fsync commits a 2.1 GB entry at
    ~0.36 GB/s; pipelined it tracks the raw durable-write figure
    (~0.47 GB/s)."""

    def __init__(self, path: str):
        import queue
        import threading

        self._f = open(path, "wb")
        # hasher-thread-owned; close() JOINS the thread before reading
        # the digest — the join is the fence
        self._sha = hashlib.sha256()  # racecheck: guarded-by(hasher join in close())
        self.nbytes = 0
        self.max_slab = 0
        self._q: "queue.Queue" = queue.Queue(maxsize=4)
        self._done = threading.Event()
        # worker-thread-only; close() joins the flusher before reading
        self._flush_error = None  # racecheck: guarded-by(flusher join in close())
        self._hasher = threading.Thread(target=self._hash_loop, daemon=True)
        self._flusher = threading.Thread(target=self._flush_loop, daemon=True)
        self._hasher.start()
        self._flusher.start()

    def _hash_loop(self) -> None:
        while True:
            block = self._q.get()
            if block is None:
                return
            self._sha.update(block)

    def _flush_loop(self) -> None:
        # early writeback: flush the dirty pages accumulated so far
        # while the main thread keeps writing/hashing — fsync from a
        # second thread on the same fd is the portable
        # sync_file_range. A writeback error here is RECORDED and
        # fails the commit at close(): on Linux >= 4.13 the first
        # fsync to observe an EIO marks it seen for this struct file,
        # so close()'s own fsync could otherwise falsely succeed and
        # commit an envelope that never durably reached the disk.
        fd = self._f.fileno()
        while not self._done.wait(0.05):
            try:
                os.fsync(fd)
            except OSError as e:
                self._flush_error = e
                return

    def write(self, host_block: np.ndarray) -> None:
        arr = np.ascontiguousarray(host_block)
        view = memoryview(arr).cast("B")
        self.max_slab = max(self.max_slab, view.nbytes)
        self._q.put(view)  # the ndarray ref keeps the bytes alive
        self._f.write(view)
        self.nbytes += view.nbytes

    def record_staging(self, nbytes: int) -> None:
        """Fold an out-of-band host staging cost (e.g. the one-shot
        ``device_get`` of a replicated device entry) into the recorded
        high-water mark — ``max_slab_bytes`` must reflect the TRUE
        host footprint or the O(slab) assertion certifies a lie."""
        self.max_slab = max(self.max_slab, int(nbytes))

    def close(self) -> Tuple[str, int, int]:
        self._q.put(None)
        self._hasher.join()
        self._f.flush()
        self._done.set()
        self._flusher.join()
        if self._flush_error is not None:
            self._f.close()
            raise self._flush_error
        os.fsync(self._f.fileno())
        self._f.close()
        return self._sha.hexdigest(), self.nbytes, self.max_slab

    def abort(self) -> None:
        """Tear down without committing (the save() error path): both
        threads joined, fd closed — a failed save must not leak a
        20 Hz flusher, a parked hasher, or an open fd per retry."""
        self._done.set()
        try:
            self._q.put_nowait(None)
        except Exception:
            # queue full: the hasher is alive and draining — a blocking
            # put is bounded by one block's hash time
            self._q.put(None)
        self._hasher.join()
        self._flusher.join()
        try:
            self._f.close()
        except OSError:
            pass


def _iter_np_slabs(arr: np.ndarray, slab: int):
    """Fixed-size slabs of an unsharded host array (flat byte view)."""
    flat = arr.reshape(-1)
    per = max(1, slab // max(arr.dtype.itemsize, 1))
    for off in range(0, flat.size, per):
        yield flat[off:off + per]


def _write_dnd(writer: _SlabWriter, data) -> Dict[str, Any]:
    """One DNDarray entry, streamed block-by-block through the io.py
    per-device-slab machinery (``_write_shards``): the host never holds
    more than one device's logical block. Split None/0 only — row-major
    file layout keeps those blocks contiguous; other splits resplit at
    the caller."""
    from ..core import io as _io

    if data.split not in (None, 0):
        raise NotImplementedError(
            f"checkpoint: DNDarray entries support split None/0, got "
            f"split={data.split} — resplit(0) before checkpointing"
        )
    _io._write_shards(data, lambda _sl, host: writer.write(host))
    return {
        "kind": "dnd",
        "shape": list(data.shape),
        "dtype": data.dtype.__name__,
        "split": data.split,
    }


def _write_jax(writer: _SlabWriter, arr) -> Dict[str, Any]:
    """One jax.Array entry. A split-0-sharded array streams its
    addressable shards in mesh order (one device block on the host at a
    time — the EF-carry case); a replicated/single-device array is
    fetched once and chunked at :data:`SLAB_BYTES`."""
    import jax

    shards = getattr(arr, "addressable_shards", None)
    sharded = bool(shards) and len(shards) > 1 and not _replicated(arr)
    if sharded:
        blocks = sorted(shards, key=lambda s: (s.index[0].start or 0))
        starts = [(s.index[0].start or 0) for s in blocks]
        if len(set(starts)) != len(starts):
            sharded = False  # partial replication: fall back to one fetch
    if sharded:
        for s in blocks:
            writer.write(np.asarray(jax.device_get(s.data)))
    else:
        # a replicated/single-device entry stages WHOLE on the host for
        # the duration of its write — that one-shot fetch IS the true
        # high-water mark for this entry, and it is recorded as such
        # (the O(slab) contract holds for the split-block and numpy
        # paths; big state should ride those — this records, not hides)
        host = np.asarray(jax.device_get(arr))
        writer.record_staging(host.nbytes)
        for slab in _iter_np_slabs(host, SLAB_BYTES):
            writer.write(slab)
    return {
        "kind": "jax",
        "shape": list(arr.shape),
        "dtype": str(np.dtype(arr.dtype)),
        "split": 0 if sharded else None,
    }


def _replicated(arr) -> bool:
    try:
        return bool(arr.sharding.is_fully_replicated)
    except Exception:
        return True


def _write_np(writer: _SlabWriter, arr: np.ndarray) -> Dict[str, Any]:
    for slab in _iter_np_slabs(arr, SLAB_BYTES):
        writer.write(slab)
    return {
        "kind": "np",
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
        "split": None,
    }


_SCALAR_TYPES = (bool, int, float, str, type(None))


def _is_scalarish(v) -> bool:
    if isinstance(v, _SCALAR_TYPES):
        return True
    if isinstance(v, (tuple, list)):
        return all(_is_scalarish(x) for x in v)
    return False


# --------------------------------------------------------------------- #
# save
# --------------------------------------------------------------------- #
def save(state: Dict[str, Any], *, tag: str, step: int,
         directory: Optional[str] = None) -> str:
    """Commit one checkpoint envelope atomically. ``state`` maps entry
    names to DNDarrays, jax arrays, numpy arrays, or plain scalars/
    tuples (the RNG stream tuple rides here). Returns the committed
    step directory. Host memory stays O(slab) throughout; the observed
    high-water mark lands in ``meta["max_slab_bytes"]``."""
    from ..core.dndarray import DNDarray
    from ..observability import events as _obs_events

    directory = ckpt_dir(directory)
    final = step_path(directory, tag, step)
    tmp = f"{final}.tmp-{os.getpid()}"
    if os.path.isdir(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    entries: Dict[str, Dict[str, Any]] = {}
    scalars: Dict[str, Any] = {}
    max_slab = 0
    total = 0
    writer = None
    save_sp = _tracing.start_span(
        "ckpt.save", tag=tag, step=int(step)
    ) if _tracing._ENABLED else None
    try:
        for name in sorted(state):
            value = state[name]
            if _is_scalarish(value):
                scalars[name] = (
                    list(value) if isinstance(value, tuple) else value
                )
                continue
            # one span per entry around the slab write stream, one
            # around close() — the hasher join + trailing fsync, the
            # durable edge.
            # detached: a mid-write failure (ENOSPC) must not strand an
            # open span on the thread's parent stack
            entry_sp = _tracing.start_span(
                "ckpt.write", entry=name, detached=True,
                parent_id=None if save_sp is None else save_sp.id,
            ) if _tracing._ENABLED else None
            writer = _SlabWriter(os.path.join(tmp, f"{name}.bin"))
            if isinstance(value, DNDarray):
                desc = _write_dnd(writer, value)
            elif isinstance(value, np.ndarray):
                desc = _write_np(writer, value)
            else:
                desc = _write_jax(writer, value)
            with _tracing.span(
                "ckpt.hash_commit", entry=name,
                parent_id=None if entry_sp is None else entry_sp.id,
            ):
                sha, nbytes, slab_hi = writer.close()
            writer = None
            _tracing.end_span(entry_sp, bytes=nbytes)
            desc.update({"sha256": sha, "nbytes": nbytes})
            entries[name] = desc
            max_slab = max(max_slab, slab_hi)
            total += nbytes
        meta = {
            "format": FORMAT,
            "tag": tag,
            "step": int(step),
            "stamps": _stamps(),
            "entries": entries,
            "scalars": scalars,
            "total_bytes": total,
            "max_slab_bytes": max_slab,
        }
        # the meta carries the RESUME-CRITICAL cursor (window_index,
        # slab, RNG tuple) — it gets the same integrity treatment the
        # entry files do: a digest over its canonical serialization,
        # verified at every load
        meta["meta_sha256"] = _meta_digest(meta)
        with _tracing.span(
            "ckpt.commit", tag=tag, step=int(step), bytes=total,
            parent_id=None if save_sp is None else save_sp.id,
        ):
            meta_path = os.path.join(tmp, "meta.json")
            with open(meta_path, "w") as f:
                json.dump(meta, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            if os.path.isdir(final):
                # re-saving an already-committed step is an explicit
                # overwrite (not a crash-path concern): drop the old one
                shutil.rmtree(final)
            os.rename(tmp, final)  # THE commit point
            _fsync_dir(os.path.dirname(final))
    except BaseException:
        if writer is not None:
            # a mid-entry failure (ENOSPC is the routine one) must not
            # leak the writer's threads/fd on every retry
            writer.abort()
        shutil.rmtree(tmp, ignore_errors=True)
        _tracing.end_span(save_sp, status="error")
        raise
    _tracing.end_span(save_sp, bytes=total)
    _tracing.flight_record("ckpt.commit", tag, int(step))
    if _telemetry._ENABLED:
        _telemetry.inc("resilience.ckpt.save")
        _telemetry.inc("resilience.ckpt.bytes", total)
        _obs_events.emit(
            "resilience.ckpt.save", tag=tag, step=int(step),
            bytes=total, max_slab_bytes=max_slab,
        )
    return final


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:
        pass  # platforms without directory fsync


def prune(directory: str, tag: str, keep: int) -> list:
    """Drop all but the newest ``keep`` committed steps; returns the
    pruned step numbers."""
    steps = list_steps(directory, tag)
    drop = steps[:-keep] if keep > 0 else []
    for s in drop:
        shutil.rmtree(step_path(directory, tag, s), ignore_errors=True)
    return drop


# --------------------------------------------------------------------- #
# load / restore
# --------------------------------------------------------------------- #
def _meta_digest(meta: Dict[str, Any]) -> str:
    """sha256 over the meta's canonical serialization (sort_keys JSON,
    the digest field excluded)."""
    body = {k: v for k, v in meta.items() if k != "meta_sha256"}
    return hashlib.sha256(json.dumps(body, sort_keys=True).encode()).hexdigest()


def _read_meta(path: str) -> Dict[str, Any]:
    try:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorrupt(f"{path}: unreadable meta.json ({e})") from None
    if not isinstance(meta, dict) or meta.get("format") != FORMAT:
        raise CheckpointCorrupt(
            f"{path}: format {meta.get('format') if isinstance(meta, dict) else '?'} "
            f"!= {FORMAT}"
        )
    if meta.get("meta_sha256") != _meta_digest(meta):
        raise CheckpointCorrupt(
            f"{path}: meta.json digest mismatch — the envelope's cursor/"
            "scalar state does not match what was committed"
        )
    return meta


def _verify_entry(path: str, name: str, desc: Dict[str, Any]) -> None:
    """Streaming sha256 re-hash of one entry file (O(slab) memory)."""
    fp = os.path.join(path, f"{name}.bin")
    sha = hashlib.sha256()
    nbytes = 0
    try:
        with open(fp, "rb") as f:
            while True:
                chunk = f.read(SLAB_BYTES)
                if not chunk:
                    break
                sha.update(chunk)
                nbytes += len(chunk)
    except OSError as e:
        raise CheckpointCorrupt(f"{path}: entry {name!r} unreadable ({e})") from None
    if nbytes != int(desc["nbytes"]):
        raise CheckpointCorrupt(
            f"{path}: entry {name!r} truncated — {nbytes} B on disk, "
            f"{desc['nbytes']} B committed"
        )
    if sha.hexdigest() != desc["sha256"]:
        raise CheckpointCorrupt(
            f"{path}: entry {name!r} sha256 mismatch — bytes on disk do "
            "not match what was committed"
        )


def _restore_flat_entry(path: str, name: str, desc: Dict[str, Any], verify: bool):
    """One-pass restore of an ``np``/``jax`` entry: the bytes are read
    ONCE into the destination buffer and hashed from there — recovery
    reads each byte a single time (a second full read of a multi-GB
    envelope at the disk edge would double the recovery wall-clock)."""
    import jax.numpy as jnp

    fp = os.path.join(path, f"{name}.bin")
    shape = tuple(int(s) for s in desc["shape"])
    host = np.empty(shape, dtype=np.dtype(desc["dtype"]))
    view = memoryview(host).cast("B")
    try:
        with open(fp, "rb") as f:
            n = f.readinto(view)
            extra = f.read(1)
    except OSError as e:
        raise CheckpointCorrupt(f"{path}: entry {name!r} unreadable ({e})") from None
    if n != int(desc["nbytes"]) or extra:
        raise CheckpointCorrupt(
            f"{path}: entry {name!r} is {n}{'+' if extra else ''} B on disk, "
            f"{desc['nbytes']} B committed"
        )
    if verify:
        sha = hashlib.sha256()
        for off in range(0, n, SLAB_BYTES):
            sha.update(view[off:off + SLAB_BYTES])
        if sha.hexdigest() != desc["sha256"]:
            raise CheckpointCorrupt(
                f"{path}: entry {name!r} sha256 mismatch — bytes on disk do "
                "not match what was committed"
            )
    if desc["kind"] == "jax":
        if desc.get("split") == 0:
            from ..core import communication as _comm

            return _comm.get_comm().shard(jnp.asarray(host), 0)
        return jnp.asarray(host)
    return host


def _restore_entry(path: str, name: str, desc: Dict[str, Any]):
    """Rebuild one ``dnd`` entry ONTO THE CURRENT WORLD: a DNDarray
    re-sharded over however many devices the resolved world has now
    (the io.py per-device assembly — no global host array). Flat
    ``np``/``jax`` entries restore through :func:`_restore_flat_entry`
    instead."""
    from ..core import io as _io, types as _types

    fp = os.path.join(path, f"{name}.bin")
    shape = tuple(int(s) for s in desc["shape"])
    dtype = getattr(_types, desc["dtype"])
    np_dtype = _io._np_storage_dtype(dtype)

    def read_slab(sl):
        return _read_block(fp, shape, np_dtype, sl)

    return _io._assemble_sharded(
        read_slab, shape, dtype, desc["split"], None, None
    )


def _read_block(fp: str, shape, np_dtype, sl) -> np.ndarray:
    """One contiguous row-block of a row-major entry file (split 0 /
    replicated reads only — the write-side restriction's mirror)."""
    row_elems = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
    start = sl[0].start or 0
    stop = sl[0].stop if sl[0].stop is not None else shape[0]
    count = (stop - start) * row_elems
    block = np.fromfile(
        fp, dtype=np_dtype, count=count, offset=start * row_elems * np_dtype.itemsize
    )
    block = block.reshape((stop - start,) + tuple(shape[1:]))
    rest = tuple(sl[1:])
    return block[(slice(None),) + rest] if rest else block


def load(path: str, verify: bool = True) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Load one committed envelope: ``(state, meta)``. ``state`` holds
    the restored arrays (re-sharded onto the current world) plus the
    scalar entries; tuples round-trip as tuples. ``verify`` re-hashes
    every entry first (:class:`CheckpointCorrupt` on any mismatch)."""
    meta = _read_meta(path)
    state: Dict[str, Any] = {}
    for name, desc in meta["entries"].items():
        if desc["kind"] in ("np", "jax"):
            # flat entries verify AND restore in one read
            state[name] = _restore_flat_entry(path, name, desc, verify)
        else:
            if verify:
                _verify_entry(path, name, desc)
            state[name] = _restore_entry(path, name, desc)
    for name, value in meta["scalars"].items():
        state[name] = tuple(value) if isinstance(value, list) else value
    if _telemetry._ENABLED:
        _telemetry.inc("resilience.ckpt.load")
    return state, meta


def restore_latest(directory: Optional[str] = None, *, tag: str
                   ) -> Optional[Tuple[int, Dict[str, Any], Dict[str, Any]]]:
    """The newest VALID committed checkpoint for ``tag``:
    ``(step, state, meta)``, or ``None`` when no step verifies. A
    truncated/corrupt newest step (the chaos harness's injection) falls
    back to its committed predecessor — corruption costs recency, never
    correctness."""
    directory = ckpt_dir(directory)
    for step in reversed(list_steps(directory, tag)):
        path = step_path(directory, tag, step)
        try:
            state, meta = load(path, verify=True)
        except CheckpointCorrupt:
            if _telemetry._ENABLED:
                _telemetry.inc("resilience.ckpt.corrupt")
            continue
        return step, state, meta
    return None
