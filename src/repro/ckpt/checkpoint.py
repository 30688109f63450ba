"""Sharded, compressed, atomic, SELF-HEALING checkpointing.

Design (orbax is not available offline; this implements the subset needed for
pod-scale fault tolerance):

  * **Layout**: one directory per step: ``manifest.json`` (pytree structure,
    shapes, dtypes, per-leaf content checksums, user metadata) + ``data.bin``
    (concatenated zstd frames, one per leaf, offsets in the manifest).
  * **Atomic commit**: everything is written to ``<dir>.tmp``; an fsync'd
    rename + ``COMMITTED`` marker makes partially-written checkpoints
    impossible to restore from (node failure mid-save is safe).
  * **Async save**: arrays are snapshotted to host memory synchronously (so
    training can mutate donated buffers), compression + IO happen on a
    background thread — the training loop loses only the device->host copy.
  * **Elastic restore**: the manifest stores *logical* arrays; restore takes
    any target mesh/shardings and ``jax.device_put``s each leaf, so a job can
    restart on a different topology (tested: save on 1x1, restore on 2x4).
  * **Multi-host**: each process writes only the shards it owns
    (``addressable_shards``) under a per-process data file; restore reads all
    data files present.  On this single-process container that degenerates to
    one file, but the layout is multi-host correct.

**Self-healing (the fault model).**  The atomic-commit protocol only covers
crashes DURING a save; a committed checkpoint can still rot afterwards
(storage bit-flips, torn metadata writes, partial syncs).  Three layers turn
that from "restore loads garbage into the device carry" into "restore skips a
generation":

  * **Per-leaf checksums** — ``manifest.json`` stores a crc32 + byte count of
    every leaf's RAW (uncompressed) bytes.  ``restore`` verifies each leaf as
    it reads and raises ``CheckpointCorruptError`` naming the leaf and the
    failed field; ``verify_checkpoint`` runs the same battery without
    materializing arrays (marker, manifest parse, required fields, data-file
    bounds, decompress, checksum).  Checkpoints written before checksums
    existed (no ``crc32`` field) restore unchecked — forward compatible.
  * **Generation fallback** — ``latest_valid(root)`` walks committed
    generations newest -> oldest and returns the newest one that PASSES
    verification, so a corrupt ``latest_committed`` costs one window of
    progress, never the run (``serve.stream.restore`` logs each skipped
    generation).
  * **Bounded retention** — ``AsyncSaver(keep=N)`` garbage-collects old
    generations after each commit: keep the newest N, but NEVER the newest
    checksum-valid generation (if everything newer is corrupt, the only
    restorable state is by definition worth more than the retention budget).
    Without GC an always-on serving loop grows its checkpoint directory
    without bound (ROADMAP item 5's memory/disk-ceiling concern).

**What is injectable** (``ft.chaos`` post-commit corruption sites:
``ckpt.bitflip`` / ``ckpt.truncate`` / ``ckpt.torn_manifest``, plus
``ckpt.save_latency`` in the writer): ``AsyncSaver`` accepts a duck-typed
``chaos`` engine and calls ``on_save_start(step)`` before writing and
``on_save_committed(path, step)`` after the atomic rename — injection
happens at exactly the boundaries real rot happens, never inside the commit
protocol itself (that window is already covered by the crash-atomicity
tests).  All of it is RECOVERABLE: the corruption battery in
``tests/test_ckpt.py`` asserts each fault fails verification with the
leaf/field named and falls back a generation.

**Accounting.**  ``AsyncSaver.save`` opens host spans (``common.obs``):
``ckpt.wait`` (joining the previous writer), ``ckpt.fetch`` (the leaves to
the host, each counted as a ``checkpoint`` D2H fetch) and, on the writer
thread, ``ckpt.write``.  ``write_counts()`` gives the generations this
process committed and the bytes their files hold.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import zstandard as zstd

from repro.common import obs

COMMIT_MARKER = "COMMITTED"

# manifest format: 2 adds per-leaf raw-byte crc32/nbytes (format-1
# checkpoints restore without checksum verification)
MANIFEST_FORMAT = 2


class CheckpointCorruptError(RuntimeError):
    """A committed checkpoint failed content verification (checksum
    mismatch, truncated data, torn manifest).  Callers holding generation
    history should fall back (``latest_valid``)."""


def _compress(data: bytes) -> Tuple[bytes, str]:
    return zstd.ZstdCompressor(level=3).compress(data), "zstd"


def _decompress(blob: bytes, codec: str) -> bytes:
    if codec == "zstd":
        return zstd.ZstdDecompressor().decompress(blob)
    if codec == "zlib":     # the committed detector checkpoints are zlib
        return zlib.decompress(blob)
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def _flatten(tree: Any):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves], treedef


def _leaf_to_host(x) -> np.ndarray:
    if isinstance(x, jax.Array):
        if len(x.addressable_shards) < len(x.sharding.device_set):
            raise ValueError("multi-host leaf not fully addressable; shard-save path required")
        obs.count_fetch("checkpoint")
        return np.asarray(x)
    return np.asarray(x)


_writes = {"generations": 0, "bytes": 0}
_writes_lock = threading.Lock()


def write_counts() -> Dict[str, int]:
    """Checkpoints committed by this process so far, and the bytes their
    files hold (data, manifest and commit marker)."""
    with _writes_lock:
        return dict(_writes)


class AsyncSaver:
    """Background-thread checkpoint writer with atomic commit, bounded
    retention GC (``keep``) and chaos injection hooks (``chaos``)."""

    def __init__(self, keep: Optional[int] = None, chaos: Any = None):
        if keep is not None and keep < 1:
            raise ValueError(f"keep must be >= 1 (got {keep})")
        self.keep = keep
        self.chaos = chaos
        self.gc_removed: List[str] = []   # generation dirs GC deleted
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, tree: Any, path: str | Path, *, step: int = 0,
             metadata: Optional[Dict] = None, blocking: bool = False) -> None:
        with obs.span("ckpt.wait"):
            self.wait()  # only one outstanding save
        with obs.span("ckpt.fetch"):
            host_leaves, treedef = _flatten(tree)
            host_leaves = [(k, _leaf_to_host(v)) for k, v in host_leaves]
            treedef_str = str(treedef)

        def _write():
            try:
                with obs.span("ckpt.write"):
                    if self.chaos is not None:
                        self.chaos.on_save_start(step)
                    _write_checkpoint(host_leaves, treedef_str, Path(path),
                                      step=step, metadata=metadata or {})
                    if self.chaos is not None:
                        self.chaos.on_save_committed(Path(path), step)
                    if self.keep is not None:
                        self.gc_removed.extend(
                            str(p) for p in gc_generations(
                                Path(path).parent, self.keep))
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        if blocking:
            _write()
            if self._error:
                err, self._error = self._error, None
                raise err
        else:
            self._thread = threading.Thread(target=_write, daemon=True)
            self._thread.start()


def _write_checkpoint(host_leaves, treedef_str: str, path: Path, *,
                      step: int, metadata: Dict) -> None:
    tmp = path.with_name(path.name + ".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    manifest = {"format": MANIFEST_FORMAT, "step": step, "metadata": metadata,
                "treedef": treedef_str, "leaves": {}}
    pid = jax.process_index() if jax.process_count() > 1 else 0
    data_path = tmp / f"data.{pid}.bin"
    written = 0
    with open(data_path, "wb") as f:
        for key, arr in host_leaves:
            raw = np.ascontiguousarray(arr).tobytes()
            blob, codec = _compress(raw)
            off = f.tell()
            f.write(blob)
            written += len(blob)
            manifest["leaves"][key] = {
                "shape": list(arr.shape), "dtype": str(arr.dtype),
                "offset": off, "nbytes": len(blob), "file": data_path.name,
                "codec": codec,
                # content integrity: crc32 + byte count of the RAW leaf
                # bytes — what restore verifies before anything reaches a
                # device carry
                "crc32": zlib.crc32(raw), "raw_nbytes": len(raw),
            }
        f.flush()
        os.fsync(f.fileno())
    for name, text in (("manifest.json", json.dumps(manifest)),
                       (COMMIT_MARKER, "ok")):
        with open(tmp / name, "w") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        written += len(text)        # ASCII: json.dumps escapes the rest
    if path.exists():
        shutil.rmtree(path)
    os.rename(tmp, path)
    # fsync the parent directory so the rename is durable
    dfd = os.open(path.parent, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    with _writes_lock:
        _writes["generations"] += 1
        _writes["bytes"] += written


def save(tree: Any, path: str | Path, *, step: int = 0,
         metadata: Optional[Dict] = None) -> None:
    AsyncSaver().save(tree, path, step=step, metadata=metadata, blocking=True)


def is_committed(path: str | Path) -> bool:
    """Committed = the atomic rename happened.  A ``*.tmp`` staging
    directory is NEVER committed, even though it contains a marker file
    just before the rename — a crash in that window must fall back to the
    previous checkpoint, not restore from a directory whose contents were
    never made durable as a unit."""
    path = Path(path)
    return (not path.name.endswith(".tmp")
            and (path / COMMIT_MARKER).exists())


def generations(root: str | Path) -> List[Path]:
    """All COMMITTED checkpoint directories under ``root``, oldest first
    (directory names sort by generation — the serving loop's zero-padded
    ``window_%08d`` naming guarantees it)."""
    root = Path(root)
    if not root.exists():
        return []
    return sorted((p for p in root.iterdir() if is_committed(p)),
                  key=lambda p: p.name)


def latest_committed(root: str | Path) -> Optional[Path]:
    cands = generations(root)
    return cands[-1] if cands else None


def _load_manifest(path: Path) -> Dict:
    """Parse + structurally validate a checkpoint manifest, raising
    ``CheckpointCorruptError`` naming the failed file/field."""
    mf = path / "manifest.json"
    if not mf.exists():
        raise CheckpointCorruptError(f"{path.name}: manifest.json missing")
    try:
        manifest = json.loads(mf.read_text())
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointCorruptError(
            f"{path.name}: manifest.json unreadable (torn write?): {e}")
    for field in ("step", "treedef", "leaves"):
        if field not in manifest:
            raise CheckpointCorruptError(
                f"{path.name}: manifest.json missing field {field!r}")
    for key, ent in manifest["leaves"].items():
        for field in ("shape", "dtype", "offset", "nbytes", "file"):
            if field not in ent:
                raise CheckpointCorruptError(
                    f"{path.name}: leaf {key}: manifest missing field "
                    f"{field!r}")
    return manifest


def _read_leaf_raw(path: Path, files: Dict[str, Path], key: str,
                   ent: Dict) -> bytes:
    """Read + decompress + checksum-verify one leaf's raw bytes, raising
    ``CheckpointCorruptError`` naming the leaf and the failed field."""
    fp = files.get(ent["file"])
    if fp is None:
        raise CheckpointCorruptError(
            f"{path.name}: leaf {key}: data file {ent['file']!r} missing")
    size = fp.stat().st_size
    if ent["offset"] + ent["nbytes"] > size:
        raise CheckpointCorruptError(
            f"{path.name}: leaf {key}: data file truncated "
            f"(need {ent['offset'] + ent['nbytes']} bytes, have {size})")
    with open(fp, "rb") as f:
        f.seek(ent["offset"])
        blob = f.read(ent["nbytes"])
    try:
        raw = _decompress(blob, ent.get("codec", "zstd"))
    except Exception as e:
        raise CheckpointCorruptError(
            f"{path.name}: leaf {key}: decompress failed "
            f"(corrupt data.bin?): {e}")
    if "raw_nbytes" in ent and len(raw) != ent["raw_nbytes"]:
        raise CheckpointCorruptError(
            f"{path.name}: leaf {key}: field raw_nbytes mismatch "
            f"({len(raw)} != {ent['raw_nbytes']})")
    if "crc32" in ent and zlib.crc32(raw) != ent["crc32"]:
        raise CheckpointCorruptError(
            f"{path.name}: leaf {key}: field crc32 checksum mismatch")
    return raw


def verify_checkpoint(path: str | Path) -> List[str]:
    """Full content verification of one checkpoint: commit marker, manifest
    parse + required fields, per-leaf data-file bounds, decompression and
    raw-byte checksums.  Returns the list of error strings (empty = valid);
    each error names the leaf/field that failed."""
    path = Path(path)
    if not is_committed(path):
        return [f"{path.name}: not committed (no marker / staging dir)"]
    try:
        manifest = _load_manifest(path)
    except CheckpointCorruptError as e:
        return [str(e)]
    files = {p.name: p for p in path.glob("data.*.bin")}
    errors = []
    for key, ent in manifest["leaves"].items():
        try:
            raw = _read_leaf_raw(path, files, key, ent)
            expect = (int(np.prod(ent["shape"]))
                      * np.dtype(ent["dtype"]).itemsize)
            if len(raw) != expect:
                errors.append(f"{path.name}: leaf {key}: field shape/dtype "
                              f"inconsistent with payload ({len(raw)} bytes "
                              f"!= {expect})")
        except CheckpointCorruptError as e:
            errors.append(str(e))
    return errors


def latest_valid(root: str | Path) -> Optional[Path]:
    """The newest committed generation that PASSES ``verify_checkpoint`` —
    the self-healing restore target: a corrupt ``latest_committed`` falls
    back through generation history instead of killing the run."""
    for p in reversed(generations(root)):
        if not verify_checkpoint(p):
            return p
    return None


def gc_generations(root: str | Path, keep: int) -> List[Path]:
    """Bounded retention: delete committed generations beyond the newest
    ``keep``, but NEVER the newest checksum-valid generation (when every
    newer generation is corrupt, that old valid one is the only restorable
    state — retention must not destroy it).  Returns the deleted paths.
    Uncommitted/staging directories are never touched (a concurrent save's
    ``*.tmp`` is live state)."""
    gens = generations(root)
    if keep < 1 or len(gens) <= keep:
        return []
    protect = latest_valid(root)
    removed = []
    for p in gens[:-keep]:
        if protect is not None and p == protect:
            continue
        shutil.rmtree(p)
        removed.append(p)
    return removed


def restore(path: str | Path, target: Any, *, shardings: Any = None) -> Tuple[Any, Dict]:
    """Restore into the structure of ``target`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional matching pytree of
    NamedSharding for elastic placement onto any mesh.  Every leaf is
    checksum-verified as it is read (format >= 2 checkpoints);
    ``CheckpointCorruptError`` names the leaf/field so callers can fall
    back a generation (``latest_valid``)."""
    path = Path(path)
    if not is_committed(path):
        raise FileNotFoundError(f"no committed checkpoint at {path}")
    manifest = _load_manifest(path)
    files = {p.name: p for p in path.glob("data.*.bin")}

    leaves, treedef = jax.tree_util.tree_flatten_with_path(target)
    sh_leaves = (jax.tree.leaves(shardings) if shardings is not None
                 else [None] * len(leaves))
    out = []
    for (kpath, tgt), sh in zip(leaves, sh_leaves):
        key = jax.tree_util.keystr(kpath)
        if key not in manifest["leaves"]:
            raise KeyError(f"leaf {key} missing from checkpoint")
        ent = manifest["leaves"][key]
        raw = _read_leaf_raw(path, files, key, ent)
        arr = np.frombuffer(raw, dtype=ent["dtype"]).reshape(ent["shape"])
        if tuple(arr.shape) != tuple(tgt.shape):
            raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} vs target {tgt.shape}")
        if str(tgt.dtype) != ent["dtype"]:
            arr = arr.astype(tgt.dtype)
        out.append(jax.device_put(arr, sh) if sh is not None else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), manifest["metadata"] | {"step": manifest["step"]}
