"""Checkpointing on the RawArray format — the paper's archival story as the
framework's fault-tolerance plane.

A checkpoint is a directory::

    step_000420/
      manifest.json        tree structure, leaf -> file, dtypes/shapes,
                           loader state, adamw step, user metadata
      param__embed.ra      one RawArray file per pytree leaf
      param__dense_layers__attn__wq.ra
      opt__m__....ra
      ...

Design properties (DESIGN.md §2):

* every leaf file is independently memory-mappable → restore streams
  straight into device buffers; a *sharded* restore reads only each host's
  row slice via ``ra.memmap_slice`` (elastic resharding: the mesh that
  restores may differ from the mesh that saved);
* **atomic publish**: writes land in ``<dir>.tmp`` and are renamed only
  after fsync — a killed job never leaves a half-written "latest";
* **async save**: leaves are snapshotted to host RAM (np.asarray) and
  written by a background thread while training continues;
* keep-last-k garbage collection;
* **remote restore** (DESIGN.md §9): ``load_checkpoint`` /
  ``restore_resharded`` accept an ``http(s)://`` checkpoint-directory URL —
  a fresh host cold-starts a model straight from a byte-range server, the
  manifest over HTTP and every leaf streamed by the same one-wave engine
  plan as local restore;
* **remote save** (DESIGN.md §11): ``save_checkpoint`` (and the manager)
  also accept a checkpoint-directory URL — each leaf is one authenticated
  atomic PUT and the manifest uploads last, so a remote checkpoint becomes
  visible only once complete (checkpoint-to-object-store without touching
  local disk).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import core as ra
from ..core.dtypes import is_float

MANIFEST = "manifest.json"
_SEP = "__"

_join = ra.join_path


def _load_manifest(path: str) -> Dict[str, Any]:
    if ra.is_url(path):
        from .. import remote

        return json.loads(remote.fetch_bytes(_join(path, MANIFEST)))
    with open(os.path.join(path, MANIFEST)) as f:
        return json.load(f)


def _leaf_name(path: Any, prefix: str) -> str:
    keys = []
    for k in path:
        if hasattr(k, "key"):
            keys.append(str(k.key))
        elif hasattr(k, "idx"):
            keys.append(str(k.idx))
        else:
            keys.append(str(k))
    return prefix + _SEP + _SEP.join(keys) if keys else prefix


def _flatten(tree: Any, prefix: str) -> Dict[str, np.ndarray]:
    return {
        _leaf_name(path, prefix): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _leaf_to_numpy(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def save_checkpoint(
    directory: str,
    step: int,
    params: Any,
    opt_state: Any = None,
    *,
    extra: Optional[Dict[str, Any]] = None,
    crc32: bool = False,
    chunked: bool = False,
    codec: Optional[str] = None,
    chunk_bytes: Optional[int] = None,
    quantize: Optional[str] = None,
) -> str:
    """Synchronous atomic save. Returns the final checkpoint path.

    ``chunked=True`` writes every leaf chunk-compressed (DESIGN.md §10):
    leaves compress concurrently on the shared engine pool (within one leaf
    the chunks compress serially — the leaf writes already occupy the pool;
    a single-leaf save chunk-parallelizes instead), and restore folds every
    leaf's chunk decodes into the one restore wave.

    ``quantize="u8"`` (DESIGN.md §12/§13) stores every float leaf as uint8
    codes with data-driven per-channel calibration; the schema rides BOTH in
    each leaf's trailing metadata (any RawArray reader can decode the file
    standalone) and in the manifest (so restore resolves dequant parameters
    without a per-leaf metadata round trip). Non-float and 0-d leaves are
    stored verbatim. Composes with ``chunked``/``codec``.

    ``directory`` may be an ``http(s)://`` URL of a write-enabled byte-range
    server (DESIGN.md §11): every leaf ships as one authenticated PUT with
    server-side atomic publish (engine-pool-parallel across leaves, token
    knob ``RA_REMOTE_TOKEN``), and the manifest is uploaded LAST — readers
    resolve a checkpoint through its manifest, so the checkpoint does not
    exist remotely until the final PUT lands (the remote twin of the local
    temp-dir + rename publish)."""
    remote_save = ra.is_url(directory)
    final = _join(directory, f"step_{step:08d}")
    if remote_save:
        tmp = final  # leaf PUTs are individually atomic; manifest-last publishes
    else:
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)

    leaves: Dict[str, np.ndarray] = {}
    leaves.update(_flatten(params, "param"))
    if opt_state is not None:
        leaves.update(_flatten(opt_state, "opt"))

    manifest: Dict[str, Any] = {
        "format": "rawarray-checkpoint-v1",
        "step": step,
        "leaves": {},
        "extra": extra or {},
        "time": time.time(),
    }
    # leaf writes go wide over the shared engine pool (DESIGN.md §8); each
    # write falls back to sequential I/O internally while on a pool thread
    write_tasks = []
    for name, leaf in leaves.items():
        arr = _leaf_to_numpy(leaf)
        fname = name + ".ra"
        fpath = _join(tmp, fname)
        entry: Dict[str, Any] = {
            "file": fname,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype) if arr.dtype.names is None else "void",
        }
        meta: Optional[bytes] = None
        if (
            quantize is not None
            and arr.dtype.names is None
            and is_float(arr.dtype)
            and arr.ndim >= 1
        ):
            # calibrate on the save thread (cheap vs compression) so the
            # schema can land in the manifest; the engine tasks then write
            # plain uint8 payloads
            info = ra.quant.quant_params(arr, quantize)
            arr = info.quantize(arr)
            meta = info.encode()
            entry["quant"] = info.to_dict()
            entry["stored_dtype"] = str(arr.dtype)
        write_tasks.append(
            lambda p=fpath, a=arr, m=meta: ra.write(
                p, a, metadata=m, crc32=crc32,
                chunked=chunked, codec=codec, chunk_bytes=chunk_bytes,
            )
        )
        manifest["leaves"][name] = entry
    ra.engine.run_tasks(write_tasks)
    body = json.dumps(manifest, indent=1).encode()
    if remote_save:
        from .. import remote

        remote.upload_bytes(_join(final, MANIFEST), body)  # publish: manifest LAST
        return final
    with open(os.path.join(tmp, MANIFEST), "wb") as f:
        f.write(body)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def _entry_quant(entry: Dict[str, Any], fpath: str, hdr) -> Optional["ra.quant.QuantInfo"]:
    """The leaf's dequantization schema, or None for a verbatim leaf.

    Fast path is the manifest (recorded at save time, zero extra I/O); the
    fallback reads the file's trailing metadata so checkpoints whose leaves
    were quantized by other writers (plain ``ra.write(quantize=)``) still
    restore to logical floats."""
    q = entry.get("quant")
    if q is not None:
        return ra.quant.QuantInfo.from_dict(q)
    want = entry.get("dtype")
    if hdr.dtype() == np.uint8 and want not in (None, "uint8", "void"):
        return ra.read_quant_metadata(fpath)
    return None


def _read_leaves_parallel(
    path: str,
    manifest: Dict[str, Any],
    names: List[str],
    quants_out: Optional[Dict[str, Any]] = None,
) -> Dict[str, np.ndarray]:
    """Stream many leaf files into preallocated arrays in ONE engine wave:
    cross-file and intra-file slab parallelism share the pool (DESIGN.md §8).
    Chunked-compressed leaves (DESIGN.md §10) join the wave too — one
    fetch+decompress task per chunk across all leaves. Quantized-u8 leaves
    (DESIGN.md §12) are dequantized host-side in a follow-up parallel wave —
    unless the caller passes ``quants_out``, which receives each quantized
    leaf's ``QuantInfo`` and leaves the stored u8 codes untouched (the
    cold-start paths decode on device instead; DESIGN.md §13)."""
    arrays: Dict[str, np.ndarray] = {}
    jobs = []
    chunk_tasks = []
    fds: List[int] = []
    fallback: List[Tuple[str, str]] = []
    # resolve every leaf's (header, source, chunk table, quant schema)
    # concurrently first: remotely each resolution costs 1-2 HTTP round
    # trips, and a serial loop over hundreds of leaves would dominate
    # cold-start latency
    metas: Dict[str, Tuple[str, Any, Any, Any]] = {}
    quants: Dict[str, Any] = {}

    def _resolve(name: str) -> None:
        entry = manifest["leaves"][name]
        fpath = _join(path, entry["file"])
        hdr = ra.header_of(fpath)
        src = None
        table = None
        chunked = bool(hdr.flags & ra.FLAG_CHUNKED) and not hdr.big_endian
        if hdr.data_length and (hdr.plain or chunked):
            if ra.is_url(fpath):
                from .. import remote

                src = remote.get_reader(fpath)
            elif chunked:
                src = os.open(fpath, os.O_RDONLY)
                fds.append(src)
        if chunked and src is not None:
            table = ra.codec.read_table(src, hdr)
        q = _entry_quant(entry, fpath, hdr)
        if q is not None:
            quants[name] = q
        metas[name] = (fpath, hdr, src, table)

    try:
        ra.engine.run_tasks([(lambda n=n: _resolve(n)) for n in names])
        for name in names:
            fpath, hdr, src, table = metas[name]
            if table is not None:
                arr = np.empty(hdr.shape, hdr.dtype())
                arrays[name] = arr
                if hdr.logical_nbytes:
                    mv = memoryview(arr.reshape(-1).view(np.uint8)).cast("B")
                    chunk_tasks += ra.codec.chunk_read_tasks(
                        src, hdr, table, 0, hdr.logical_nbytes, mv
                    )
                continue
            if not hdr.plain:
                fallback.append((name, fpath))
                continue
            arr = np.empty(hdr.shape, hdr.dtype())
            arrays[name] = arr
            if hdr.data_length:
                if src is None:
                    src = os.open(fpath, os.O_RDONLY)
                    fds.append(src)
                mv = memoryview(arr.reshape(-1).view(np.uint8)).cast("B")
                jobs.append((src, hdr.nbytes, mv))
        if chunk_tasks:  # one wave: slab preads + chunk decodes share the pool
            ra.engine.run_tasks(ra.engine.span_read_tasks(jobs) + chunk_tasks)
        else:
            ra.engine.parallel_read_spans(jobs)
    finally:
        for fd in fds:
            os.close(fd)
    for name, fpath in fallback:
        arrays[name] = np.asarray(ra.read(fpath))
    if quants_out is not None:
        quants_out.update(quants)
    elif quants:  # host dequant, parallel across leaves (numpy drops the GIL)
        def _dq(name: str) -> None:
            arrays[name] = quants[name].dequantize(arrays[name])

        ra.engine.run_tasks([(lambda n=n: _dq(n)) for n in quants])
    return arrays


def load_checkpoint(
    path: str,
    params_like: Any,
    opt_like: Any = None,
    *,
    mmap: bool = True,
) -> Tuple[Any, Any, Dict[str, Any]]:
    """Restore into the structure of ``params_like`` (shape tree or pytree).

    With ``mmap=True`` (default) every leaf is streamed into a preallocated
    array by one parallel engine wave over all leaf files; ``mmap=False``
    keeps the simple per-leaf ``ra.read`` path. ``path`` may be an
    ``http(s)://`` checkpoint URL — same wave plan, ranged reads."""
    manifest = _load_manifest(path)

    def restore(tree: Any, prefix: str) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        names = [_leaf_name(pth, prefix) for pth, _ in flat]
        if mmap:
            arrays = _read_leaves_parallel(path, manifest, names)
        else:
            arrays = {
                n: np.asarray(
                    ra.read(_join(path, manifest["leaves"][n]["file"]), dequantize=True)
                )
                for n in names
            }
        out = []
        for name, (pth, like) in zip(names, flat):
            arr = arrays[name]
            want = tuple(like.shape)
            if tuple(arr.shape) != want:
                raise ValueError(f"{name}: checkpoint {arr.shape} vs model {want}")
            out.append(arr)
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(tree), out)

    params = restore(params_like, "param")
    opt = restore(opt_like, "opt") if opt_like is not None else None
    return params, opt, manifest.get("extra", {})


def restore_resharded(
    path: str,
    name: str,
    *,
    row_start: int,
    row_stop: int,
    dequantize: bool = False,
) -> np.ndarray:
    """Elastic restore: read only rows [start, stop) of one leaf — offset
    arithmetic on the .ra file, no full-array read (a different mesh's host
    reads exactly its slice). Works on a checkpoint URL too (the row slab
    becomes ranged requests) and on chunked-compressed leaves (DESIGN.md
    §10): only the chunks overlapping the row slab are fetched + decoded.

    ``dequantize=True`` reconstructs logical floats from a quantized-u8
    leaf; row slicing composes with the quant schema because calibration is
    per-channel over the LAST axis (every row carries all channels)."""
    manifest = _load_manifest(path)
    entry = manifest["leaves"][name]
    fpath = _join(path, entry["file"])
    hdr = ra.header_of(fpath)
    quant = _entry_quant(entry, fpath, hdr) if dequantize else None

    def _dq(a: np.ndarray) -> np.ndarray:
        return quant.dequantize(a) if quant is not None else a

    chunked = bool(hdr.flags & ra.FLAG_CHUNKED)
    if not ra.is_url(fpath) and not chunked:
        return _dq(np.asarray(ra.memmap_slice(fpath, row_start, row_stop)))
    if hdr.compressed and not chunked:
        raise ra.RawArrayError(
            "cannot row-slice a whole-file-compressed payload; "
            "save the checkpoint with chunked=True"
        )
    if not hdr.shape:
        raise ra.RawArrayError("cannot row-slice a 0-d array")
    n = hdr.shape[0]
    row_start, row_stop = max(0, row_start), min(row_stop, n)
    if row_stop < row_start:
        raise ra.RawArrayError(f"bad slice [{row_start}, {row_stop})")
    row = hdr.elbyte
    for d in hdr.shape[1:]:
        row *= d
    out = np.empty((row_stop - row_start,) + hdr.shape[1:], hdr.dtype())
    if out.nbytes:
        fd = None
        if ra.is_url(fpath):
            from .. import remote

            src: object = remote.get_reader(fpath)
        else:
            src = fd = os.open(fpath, os.O_RDONLY)
        try:
            mv = memoryview(out.reshape(-1).view(np.uint8)).cast("B")
            if chunked:
                table = ra.codec.read_table(src, hdr)
                ra.engine.run_tasks(ra.codec.chunk_read_tasks(
                    src, hdr, table, row_start * row, row_stop * row, mv
                ))
            else:
                ra.engine.parallel_read_into(src, hdr.nbytes + row_start * row, mv)
        finally:
            if fd is not None:
                os.close(fd)
    return _dq(out)


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for d in os.listdir(directory):
        if d.startswith("step_") and not d.endswith(".tmp"):
            try:
                steps.append(int(d[5:]))
            except ValueError:
                pass
    return max(steps) if steps else None


class CheckpointManager:
    """Async, keep-last-k checkpoint driver for the training loop."""

    def __init__(
        self,
        directory: str,
        *,
        keep: int = 3,
        async_save: bool = True,
        chunked: bool = False,
        codec: Optional[str] = None,
        chunk_bytes: Optional[int] = None,
        quantize: Optional[str] = None,
    ):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self.chunked = chunked
        self.codec = codec
        self.chunk_bytes = chunk_bytes
        self.quantize = quantize
        self._thread: Optional[threading.Thread] = None
        self.save_s = 0.0
        if not ra.is_url(directory):
            os.makedirs(directory, exist_ok=True)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def save(self, step: int, params: Any, opt_state: Any = None, extra: Optional[Dict] = None) -> None:
        self.wait()  # one in flight at a time
        # snapshot to host BEFORE returning control (params may mutate next step)
        host_params = jax.tree_util.tree_map(_leaf_to_numpy, params)
        host_opt = (
            jax.tree_util.tree_map(_leaf_to_numpy, opt_state) if opt_state is not None else None
        )

        def run():
            t0 = time.perf_counter()
            save_checkpoint(
                self.directory, step, host_params, host_opt, extra=extra,
                chunked=self.chunked, codec=self.codec, chunk_bytes=self.chunk_bytes,
                quantize=self.quantize,
            )
            self._gc()
            self.save_s += time.perf_counter() - t0

        if self.async_save:
            self._thread = threading.Thread(target=run, daemon=False, name="ra-ckpt")
            self._thread.start()
        else:
            run()

    def _gc(self) -> None:
        if ra.is_url(self.directory):
            return  # remote stores garbage-collect server-side, not from here
        steps = sorted(
            int(d[5:])
            for d in os.listdir(self.directory)
            if d.startswith("step_") and not d.endswith(".tmp")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"), ignore_errors=True)

    def latest(self) -> Optional[int]:
        return latest_step(self.directory)

    def path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")
