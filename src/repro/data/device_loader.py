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
  codes, fused ``q*scale + bias``); the wrapped loader's host-side
  dequantization is turned off automatically;
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

Safety: the feeder blocks until each transfer completes before pulling the
next host batch, so the wrapped loader's ``reuse_buffers`` ring is never
overwritten mid-copy; device batches are immutable ``jax.Array``s. The
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
import time
from typing import Any, Dict, Iterator, Optional, Tuple

import numpy as np

from ..core.spec import RawArrayError, env_int
from .loader import DataLoader, LoaderState


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
        global_arrays: Optional[bool] = None,
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
        # a mesh loader assembles global jax.Arrays by default; override only
        # to keep plain per-host arrays (e.g. non-collective eval loops)
        self.global_arrays = (mesh is not None) if global_arrays is None else bool(global_arrays)
        if self.global_arrays:
            if mesh is None:
                raise RawArrayError(
                    "global_arrays=True requires DataLoader(mesh=...)"
                )
            if mesh.host_count > 1 and jax.process_count() != mesh.host_count:
                raise RawArrayError(
                    f"global assembly needs one jax process per mesh host: "
                    f"mesh has {mesh.host_count} hosts but "
                    f"jax.process_count()={jax.process_count()} (use "
                    f"data_mesh.make_global_batch directly to simulate)"
                )
        self._gsharding: Any = None  # lazy data_mesh.data_sharding()
        self._gdevices: Any = None
        # device decode replaces host decode: raw uint8 over the wire
        loader.dequant = False
        self.bufs = max(1, bufs if bufs is not None else default_device_bufs())
        self.device = device
        self._interpret = interpret
        self._quant_dev: Dict[str, Tuple[Any, Any, np.dtype]] = {}
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

    # ---- quantized-field kernel parameters ---------------------------------
    def _quant_params(self) -> Dict[str, Tuple[Any, Any, np.dtype]]:
        """Per-field ``(scale, bias, out_dtype)`` with scale/bias already on
        device, built once: the dequant kernel wants ``(C,)`` float32 for
        the last axis of each quantized field."""
        if not self._quant_dev:
            for f, info in getattr(self.loader.ds, "quant", {}).items():
                shape, _ = self.loader.ds.logical_spec(f)
                if not shape:
                    raise RawArrayError(
                        f"quantized field {f!r} has a scalar row shape"
                    )
                scale, bias = info.channel_params(int(shape[-1]))
                self._quant_dev[f] = (
                    self._jax.device_put(scale, self.device),
                    self._jax.device_put(bias, self.device),
                    np.dtype(info.orig_dtype),
                )
        return self._quant_dev

    # ---- feeder thread ------------------------------------------------------
    def _start(self) -> None:
        jax = self._jax
        q = self._q = queue.Queue(maxsize=self.bufs)
        stop = self._stop = threading.Event()
        self._exc = None
        dev = self.device
        # captured by value: a zombie feeder that outlives its join timeout
        # keeps THIS loader object even after stop() swaps in a fresh one,
        # so it can never steal batches from (or poison the sticky-error
        # state of) a restarted pipeline
        loader = self.loader

        # device_put MAY alias host memory zero-copy (the CPU backend does
        # for aligned arrays): with a reused staging ring the bytes must be
        # detached first or the "device" batch changes under the consumer
        # when the ring recycles
        detach = bool(getattr(self.loader, "reuse_buffers", False))

        def run():
            while not stop.is_set():
                try:
                    batch = next(loader)
                    state = batch.pop("_state", None)
                    t0 = time.perf_counter()
                    if self.global_arrays:
                        moved = self._globalize(batch, detach)
                    else:
                        moved = {
                            k: jax.device_put(
                                np.array(v, copy=True) if detach else v, dev
                            )
                            for k, v in batch.items()
                        }
                        # the transfer must COMPLETE before the next host
                        # batch may recycle the staging ring buffer under it
                        jax.block_until_ready(list(moved.values()))
                    nbytes = sum(int(v.nbytes) for v in batch.values())
                    with self._stats_lock:
                        self._h2d_s += time.perf_counter() - t0
                        self._h2d_bytes += nbytes
                        self._h2d_n += 1
                    if not self.global_arrays:
                        # on-device decode is part of the FEED pipeline:
                        # dispatch the fused dequant here so the consumer's
                        # critical path is nothing but q.get() + train step
                        self._dequant_on_device(moved)
                    item: Any = (moved, state)
                except Exception as e:  # surface in consumer (sticky there)
                    item = e
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

    # ---- global assembly (DESIGN.md §15) ------------------------------------
    def _quant_params_on(self, device) -> Dict[str, Tuple[Any, Any, np.dtype]]:
        """Per-field dequant parameters COMMITTED to ``device`` — committed
        operands keep the fused kernel's dispatch on each shard's own device
        in the global-assembly path."""
        cache = getattr(self, "_quant_dev_on", None)
        if cache is None:
            cache = self._quant_dev_on = {}
        per = cache.get(device)
        if per is None:
            per = cache[device] = {}
            for f, info in getattr(self.loader.ds, "quant", {}).items():
                shape, _ = self.loader.ds.logical_spec(f)
                scale, bias = info.channel_params(int(shape[-1]))
                per[f] = (
                    self._jax.device_put(scale, device),
                    self._jax.device_put(bias, device),
                    np.dtype(info.orig_dtype),
                )
        return per

    def _globalize(self, batch: Dict[str, np.ndarray], detach: bool) -> Dict[str, Any]:
        """Local host batch → global ``jax.Array``s: split rows over this
        host's addressable devices, device_put each block (uint8 for
        quantized fields), dequant each block on ITS device, then declare
        the blocks as the addressable shards of the
        ``(host_count * local_B, ...)``-shaped global array. The assembly
        itself is metadata-only — no gather, no cross-host traffic."""
        jax = self._jax
        if self._gsharding is None:
            from ..distributed import data_mesh

            self._gsharding = data_mesh.data_sharding()
            self._gdevices = jax.local_devices()
        devs = self._gdevices
        nd = len(devs)
        host_count = self.loader.mesh.host_count
        out: Dict[str, Any] = {}
        for k, v in batch.items():
            n = int(v.shape[0])
            if n % nd:
                raise RawArrayError(
                    f"{k}: local batch of {n} rows does not split over "
                    f"{nd} local devices"
                )
            per = n // nd
            shards = [
                jax.device_put(
                    np.array(v[i * per : (i + 1) * per], copy=True)
                    if detach
                    else v[i * per : (i + 1) * per],
                    d,
                )
                for i, d in enumerate(devs)
            ]
            # transfers must COMPLETE before the staging ring may recycle
            jax.block_until_ready(shards)
            if k in getattr(self.loader.ds, "quant", {}):
                from ..kernels import ops  # deferred: pallas import is heavy

                shards = [
                    ops.dequant_u8(
                        s, *self._quant_params_on(d)[k][:2],
                        out_dtype=self._quant_params_on(d)[k][2],
                        interpret=self._interpret,
                    )
                    for s, d in zip(shards, devs)
                ]
            gshape = (n * host_count,) + tuple(shards[0].shape[1:])
            out[k] = jax.make_array_from_single_device_arrays(
                gshape, self._gsharding, shards
            )
        return out

    # ---- iteration ----------------------------------------------------------
    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def _dequant_on_device(self, moved: Dict[str, Any]) -> None:
        """Decode quantized fields in place with the fused Pallas kernel
        (uint8 in HBM → float out; DESIGN.md §12). Runs on the feeder
        thread — dispatch and execution overlap the consumer's train step;
        jax compiled-function execution is thread-safe."""
        quant = self._quant_params()
        if not quant:
            return
        from ..kernels import ops  # deferred: pallas import is heavy

        for f, (scale, bias, out_dtype) in quant.items():
            if f in moved:
                moved[f] = ops.dequant_u8(
                    moved[f], scale, bias, out_dtype=out_dtype,
                    interpret=self._interpret,
                )

    def __next__(self) -> Dict[str, Any]:
        if self._exc is not None:
            raise self._exc  # sticky, same contract as DataLoader.__next__
        if self._q is None:
            self._start()
        t0 = time.perf_counter()
        item = self._q.get()
        with self._stats_lock:
            self._wait_s += time.perf_counter() - t0
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
