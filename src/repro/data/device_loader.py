"""Device feed plane: prefetch-to-device loader with on-device dequant
(DESIGN.md §12).

The host ``DataLoader`` ends at numpy batches in host RAM; a train step
then pays host→device transfer *inside* its critical path, and the
paper's read-bandwidth win evaporates at the device boundary.
``DeviceLoader`` closes that gap:

* a feeder thread pulls host batches (the loader's prefetch ring is the
  staging buffer) and ``jax.device_put``s every field, keeping up to
  ``RA_DEVICE_BUFS`` (default 2) batches RESIDENT ON DEVICE — so host
  gather, staging-buffer fill, and the H2D copy all overlap the running
  train step;
* quantized fields (DESIGN.md §12) cross the PCIe/ICI link as uint8 —
  4× fewer bytes than float32 — and are decoded ON DEVICE by the fused
  Pallas kernel ``repro.kernels.ops.dequant_u8`` (one HBM read of the u8
  codes, fused ``q*scale + bias``) through
  ``repro.kernels.device_dequant.DeviceDequant``; the wrapped loader's
  host-side dequantization is turned off automatically. A field whose minor
  axis is not a multiple of 128 lanes (RGB pixels) crosses in the kernel's
  packed 2-D view, so neither the host nor the device relays it out; only
  the decoded float result is reshaped back;
* ``stats()`` folds ``h2d_s`` (time inside device transfers), ``h2d_bytes``
  (bytes actually moved) and ``device_wait_s`` (consumer starved on the
  device queue) into the wrapped loader's counters, so the train loop's
  straggler monitor sees the whole feed path;
* wrapping a mesh loader (``DataLoader(mesh=...)``, DESIGN.md §15) turns on
  **global assembly**: each host's local batch is split over its addressable
  devices and declared as the local shards of one global ``jax.Array`` via
  ``jax.make_array_from_single_device_arrays`` (data axis = mesh hosts ×
  local devices), so the sharded train-step factories in
  ``repro.distributed.steps`` consume mesh batches unchanged.

Safety: the wrapped loader streams host batches into its reused ring
(``reuse_buffers``, turned on here: no consumer ever sees a host batch),
and the feeder blocks until each transfer completes before pulling the
next one, so a ring slot is never overwritten mid-copy. Where a target
device's ``device_put`` may alias host memory — the CPU backend's can,
zero-copy — each block is copied before its put, or the "device" batch
would change under the consumer when the ring recycles
(``may_alias_host``); a TPU's or GPU's put copies into device memory and
reads the ring slot itself. Device batches are immutable ``jax.Array``s. The
dequant kernel is dispatched on the feeder thread too — decode belongs to
the feed pipeline, leaving the consumer's critical path as nothing but a
queue pop and its train step (jax compiled-function execution is
thread-safe). Producer errors are sticky exactly like the host loader's:
every ``next()`` after a failure re-raises instead of hanging.

Usage (flag-gated in ``repro.launch.train`` via ``--device-feed``)::

    loader = DeviceLoader(DataLoader(RaDataset(root), batch, ...))
    batch = next(loader)        # fields are jax.Arrays, already on device
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..core import obs
from ..core.spec import RawArrayError, env_int
from .loader import DataLoader, LoaderState

# device blocks put, one per target device a batch
_SHARDS = obs.counter("device_loader.shards")
# device blocks copied off the host batch before their put
_DETACHED = obs.counter("device_loader.detached")


def may_alias_host(devices: Sequence[Any]) -> bool:
    """Whether a ``jax.device_put`` onto any of ``devices`` may alias the
    host array it is given, so that a block of a reused host buffer must
    be copied before its put: the CPU backend's put can hand the buffer
    over zero-copy, while a TPU's or GPU's copies it into device memory."""
    return any(d.platform == "cpu" for d in devices)


def default_device_bufs() -> int:
    """Device-resident batch depth (knob ``RA_DEVICE_BUFS``, default 2)."""
    return max(1, env_int("RA_DEVICE_BUFS", 2))


class DeviceLoader:
    """Wrap a ``DataLoader`` so consumers receive device-resident batches.

    ``bufs`` device batches (knob ``RA_DEVICE_BUFS``) are kept in flight;
    quantized fields are moved as uint8 and dequantized on device with the
    fused Pallas kernel (DESIGN.md §12). The wrapped loader must not have
    started iterating yet (its prefetch pipeline is reconfigured here).
    """

    def __init__(
        self,
        loader: DataLoader,
        *,
        bufs: Optional[int] = None,
        device: Any = None,
        interpret: Optional[bool] = None,
    ):
        import jax  # deferred: keep `repro.data` importable without jax

        if loader._q is not None or loader._thread is not None:
            raise RawArrayError(
                "DeviceLoader must wrap a DataLoader that has not started "
                "iterating (stop() it first)"
            )
        self._jax = jax
        self.loader = loader
        mesh = getattr(loader, "mesh", None)
        # a mesh loader's batches are assembled into global jax.Arrays
        self.global_arrays = mesh is not None
        if self.global_arrays and mesh.host_count > 1 and jax.process_count() != mesh.host_count:
            raise RawArrayError(
                f"global assembly needs one jax process per mesh host: "
                f"mesh has {mesh.host_count} hosts but "
                f"jax.process_count()={jax.process_count()}"
            )
        self._gsharding: Any = None  # lazy data_mesh.data_sharding()
        self._devices: Optional[List[Any]] = None  # lazy: the blocks' targets
        # device decode replaces host decode: raw uint8 over the wire
        loader.dequant = False
        # host batches are staged in the loader's reused ring (the naive
        # seed path has none): only the feeder sees them, and it finishes
        # each transfer before it pulls the next batch
        loader.reuse_buffers = not loader.naive
        self.bufs = max(1, bufs if bufs is not None else default_device_bufs())
        self.device = device
        self._interpret = interpret
        self._dequant: Optional[Dict[str, Any]] = None  # field -> DeviceDequant
        # Regression note (ralint guarded-by): the feeder thread writes the
        # h2d_* counters while the consumer writes _wait_s/_n_batches and
        # stats() reads both — previously with no lock anywhere.
        self._stats_lock = threading.Lock()
        self._h2d_s = 0.0      # guarded-by: _stats_lock
        self._h2d_bytes = 0    # guarded-by: _stats_lock
        self._h2d_n = 0        # guarded-by: _stats_lock
        self._wait_s = 0.0     # guarded-by: _stats_lock
        self._n_batches = 0    # guarded-by: _stats_lock
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None

    # ---- feeder thread ------------------------------------------------------
    def _start(self) -> None:
        q = self._q = queue.Queue(maxsize=self.bufs)
        stop = self._stop = threading.Event()
        self._exc = None
        # captured by value: a zombie feeder that outlives its join timeout
        # keeps THIS loader object even after stop() swaps in a fresh one,
        # so it can never steal batches from (or poison the sticky-error
        # state of) a restarted pipeline
        loader = self.loader
        jax = self._jax
        if self._devices is None:
            self._devices = jax.local_devices() if self.global_arrays else [self.device]
        # device=None puts on jax's default device
        default = jax.config.jax_default_device or jax.devices()[0]
        if isinstance(default, str):  # a platform name
            default = jax.devices(default)[0]
        detach = loader.reuse_buffers and may_alias_host(
            [default if d is None else d for d in self._devices])

        def run():
            while not stop.is_set():
                try:
                    batch = next(loader)
                    state = batch.pop("_state", None)
                    with obs.span("device_loader.h2d") as sp:
                        blocks = self._put_blocks(batch, detach)
                        sp.n = nbytes = sum(int(v.nbytes) for v in batch.values())
                    with self._stats_lock:
                        self._h2d_s += sp.seconds
                        self._h2d_bytes += nbytes
                        self._h2d_n += 1
                    # on-device decode is part of the FEED pipeline: dispatch
                    # the fused dequant here so the consumer's critical path
                    # is nothing but q.get() + train step
                    with obs.span("device_loader.dequant"):
                        moved = self._decode_blocks(blocks)
                    item: Any = (moved, state)
                except Exception as e:  # surface in consumer (sticky there)
                    item = e
                with obs.span("device_loader.put_wait"):
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                if isinstance(item, Exception):
                    return

        self._thread = threading.Thread(target=run, daemon=True, name="ra-h2d")
        self._thread.start()

    def _decoders(self) -> Dict[str, Any]:
        """A ``DeviceDequant`` per quantized field, built once."""
        if self._dequant is None:
            from ..kernels.device_dequant import DeviceDequant  # deferred: pallas is heavy

            self._dequant = {}
            for f, info in getattr(self.loader.ds, "quant", {}).items():
                shape, _ = self.loader.ds.logical_spec(f)
                if not shape:
                    raise RawArrayError(
                        f"quantized field {f!r} has a scalar row shape"
                    )
                self._dequant[f] = DeviceDequant(info, (-1,) + tuple(shape),
                                                 interpret=self._interpret)
        return self._dequant

    def _put_blocks(self, batch: Dict[str, np.ndarray], detach: bool) -> List[Dict[str, Any]]:
        """Split the host batch's rows over the target devices (``device``
        alone, or this host's addressable devices for a mesh loader) and
        ``device_put`` each device's block of every field, quantized fields
        as u8 in the kernel's view (a block of rows is a contiguous slice,
        so a packed view is free): one ``device_loader.shard_put`` span per
        device around its puts, then one wait for every transfer, which
        must COMPLETE before the staging ring may recycle. With ``detach``
        each block is copied before its put. Returns one
        ``{field: single-device array}`` per device, in device order."""
        jax = self._jax
        nd = len(self._devices)
        dq = self._decoders()
        for k, v in batch.items():
            n = int(v.shape[0])
            if n % nd:
                raise RawArrayError(
                    f"{k}: local batch of {n} rows does not split over "
                    f"{nd} local devices"
                )
        blocks = []
        for i, d in enumerate(self._devices):
            with obs.span("device_loader.shard_put") as sp:
                rows = {}
                for k, v in batch.items():
                    per = int(v.shape[0]) // nd
                    rows[k] = v[i * per : (i + 1) * per]
                    if detach:
                        rows[k] = np.array(rows[k], copy=True)
                sp.n = sum(int(r.nbytes) for r in rows.values())
                blocks.append({k: dq[k].put(r, d) if k in dq else jax.device_put(r, d)
                               for k, r in rows.items()})
        jax.block_until_ready(blocks)
        _SHARDS.add(nd)
        if detach:
            _DETACHED.add(nd)
        return blocks

    def _decode_blocks(self, blocks: List[Dict[str, Any]]) -> Dict[str, Any]:
        """Decode every quantized block on its OWN device with the fused
        kernel (uint8 in HBM → float out; DESIGN.md §12); jax
        compiled-function execution is thread-safe. One device's blocks are
        the batch. A mesh loader's are declared as the addressable shards of
        ``(host_count * local_B, ...)`` global ``jax.Array``s (DESIGN.md
        §15), which is metadata-only — no gather, no cross-host traffic."""
        jax = self._jax
        dq = self._decoders()
        for blk, d in zip(blocks, self._devices):
            for k, q in dq.items():
                if k in blk:
                    blk[k] = q.decode(blk[k], d)
        if not self.global_arrays:
            return blocks[0]
        if self._gsharding is None:
            from ..distributed import data_mesh

            self._gsharding = data_mesh.data_sharding()
        host_count = self.loader.mesh.host_count
        out: Dict[str, Any] = {}
        for k in blocks[0]:
            shards = [b[k] for b in blocks]
            gshape = (len(shards) * int(shards[0].shape[0]) * host_count,) + tuple(
                shards[0].shape[1:])
            out[k] = jax.make_array_from_single_device_arrays(
                gshape, self._gsharding, shards
            )
        return out

    # ---- iteration ----------------------------------------------------------
    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def __next__(self) -> Dict[str, Any]:
        if self._exc is not None:
            raise self._exc  # sticky, same contract as DataLoader.__next__
        if self._q is None:
            self._start()
        with obs.span("device_loader.wait") as sp:
            item = self._q.get()
        with self._stats_lock:
            self._wait_s += sp.seconds
        if isinstance(item, Exception):
            self._exc = item
            raise item
        moved, state = item
        moved["_state"] = state
        with self._stats_lock:
            self._n_batches += 1
        return moved

    # ---- lifecycle ----------------------------------------------------------
    def stop(self) -> None:
        """Stop the feeder and VERIFY it exited, then stop the wrapped
        loader. A feeder wedged past the join timeout (blocked inside the
        wrapped loader) keeps only its captured references: the wrapped
        loader is REPLACED with an equivalent fresh one, so the zombie can
        never steal a batch from — or stick a stale error onto — a
        restarted pipeline."""
        self._stop.set()
        if self._q is not None:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            if self._thread.is_alive():
                self.loader = self._detached_clone(self.loader)
        self._q = None
        self._thread = None
        self._exc = None
        self.loader.stop()

    @staticmethod
    def _detached_clone(old: DataLoader) -> DataLoader:
        """A fresh DataLoader equivalent to ``old`` (same dataset, order,
        position) sharing none of its queues, events, or buffers; ``old``
        stays with the zombie feeder that still references it."""
        old_q = old._q
        old.stop()  # best-effort: signals old's own producer too
        if old_q is not None:
            # wake a feeder blocked in old.__next__'s q.get(): the sentinel
            # error makes next() raise, and the feeder's (set) stop event
            # then ends the thread instead of leaking it on an orphaned get
            try:
                old_q.put_nowait(
                    RawArrayError("loader detached from a wedged device feeder")
                )
            except queue.Full:
                pass
        new = DataLoader(
            old.ds, old.batch_size, seed=old.seed, shuffle=old.shuffle,
            host_id=old.host_id, host_count=old.host_count,
            prefetch=old.prefetch, reuse_buffers=old.reuse_buffers,
            naive=old.naive, dequant=old.dequant, mesh=old.mesh,
        )
        new.state = LoaderState(old.state.epoch, old.state.step)
        return new

    def restore(self, state: LoaderState) -> None:
        """Resume exactly after the batch ``state`` describes (drains the
        device pipeline, then delegates to the wrapped loader)."""
        self.stop()
        self.loader.restore(state)

    def steps_per_epoch(self) -> int:
        return self.loader.steps_per_epoch()

    @property
    def ds(self):
        return self.loader.ds

    @property
    def state(self) -> LoaderState:
        return self.loader.state

    def stats(self) -> Dict[str, float]:
        """Wrapped loader counters plus the device feed's: ``h2d_s`` (time
        inside host→device transfers), ``h2d_bytes`` (bytes moved — 4×
        smaller for quantized fields), ``device_wait_s`` (consumer starved
        on the device queue: the straggler signal), ``device_batches``."""
        out = dict(self.loader.stats())
        with self._stats_lock:
            out.update(
                h2d_s=self._h2d_s,
                h2d_bytes=float(self._h2d_bytes),
                h2d_batches=float(self._h2d_n),  # feeder runs ahead of consumer
                device_wait_s=self._wait_s,
                device_batches=float(self._n_batches),
            )
        return out
