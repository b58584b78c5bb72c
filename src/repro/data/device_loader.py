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
  dequantization is turned off automatically. A field whose minor axis is
  not a multiple of 128 lanes (RGB pixels) crosses in the kernel's packed
  2-D view (``packed_width``), so neither the host nor the device relays
  it out; only the decoded float result is reshaped back;
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

import math
import queue
import threading
from typing import Any, Dict, Iterator, NamedTuple, Optional, Tuple

import numpy as np

from ..core import obs
from ..core.spec import RawArrayError, env_int
from .loader import DataLoader, LoaderState

# device blocks the global-assembly path put, one per local device a batch
_SHARDS = obs.counter("device_loader.shards")
# quantized field blocks put in the packed view, one per field per device a batch
_PACKED = obs.counter("device_loader.packed")


def packed_width(shape: Tuple[int, ...]) -> int:
    """Lanes of the packed ``(n / width, width)`` view in which a u8 block of
    ``shape`` crosses the link: ``lcm(C, 128)`` for a minor axis ``C`` that
    is not a multiple of 128 when the element count divides it, else 0 (the
    block crosses as it is). The test ``ops.dequant_u8`` applies on the
    device, so the kernel sees the same ``(rows, width)`` call either way."""
    c = int(shape[-1])
    width = math.lcm(c, 128)
    n = math.prod(shape)
    return width if c % 128 and n and n % width == 0 else 0


def _wire(quant, field: str, v: np.ndarray) -> np.ndarray:
    """The view of host block ``v`` of ``field`` that crosses the link: a
    quantized field with a narrow minor axis as the kernel's packed
    ``(n / width, width)`` (a free reshape of the C-contiguous block),
    counted in ``device_loader.packed``; any other field as it is."""
    width = packed_width(v.shape) if field in quant else 0
    if not width:
        return v
    _PACKED.add(1)
    return v.reshape(-1, width)


class _Dequant(NamedTuple):
    """A quantized field's kernel parameters on one device: ``(C,)`` float32
    scale and bias for a block that crosses as it is, and the same tiled to
    ``lcm(C, 128)`` lanes for one in the packed view (None where ``C`` is a
    multiple of 128)."""

    scale: Any
    bias: Any
    packed_scale: Any
    packed_bias: Any
    out_dtype: np.dtype
    row_shape: Tuple[int, ...]


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
                    f"jax.process_count()={jax.process_count()} (several "
                    f"mesh hosts in one process take global_arrays=False)"
                )
        self._gsharding: Any = None  # lazy data_mesh.data_sharding()
        self._gdevices: Any = None
        # device decode replaces host decode: raw uint8 over the wire
        loader.dequant = False
        self.bufs = max(1, bufs if bufs is not None else default_device_bufs())
        self.device = device
        self._interpret = interpret
        self._quant_dev_on: Dict[Any, Dict[str, _Dequant]] = {}
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

    # ---- quantized fields: wire view and kernel parameters ------------------
    def _quant_params_on(self, device) -> Dict[str, _Dequant]:
        """Per-field dequant parameters on ``device`` (None: the default
        device), built once. The global-assembly path commits them to each
        shard's device, which keeps the fused kernel's dispatch there."""
        per = self._quant_dev_on.get(device)
        if per is None:
            per = self._quant_dev_on[device] = {}
            for f, info in getattr(self.loader.ds, "quant", {}).items():
                shape, _ = self.loader.ds.logical_spec(f)
                if not shape:
                    raise RawArrayError(
                        f"quantized field {f!r} has a scalar row shape"
                    )
                c = int(shape[-1])
                scale, bias = info.channel_params(c)
                reps = math.lcm(c, 128) // c if c % 128 else 0
                tiled = (np.tile(scale, reps), np.tile(bias, reps)) if reps else (None, None)
                per[f] = _Dequant(
                    *self._jax.device_put((scale, bias) + tiled, device),
                    np.dtype(info.orig_dtype), tuple(int(d) for d in shape),
                )
        return per

    def _decode(self, x, p: _Dequant):
        """The fused kernel on device block ``x`` of a quantized field; a
        block that crossed packed is decoded as the 2-D array it is, and only
        the float result is reshaped to its ``(rows,) + row_shape``."""
        from ..kernels import ops  # deferred: pallas import is heavy

        if x.shape[-1] == p.row_shape[-1]:
            return ops.dequant_u8(x, p.scale, p.bias, out_dtype=p.out_dtype,
                                  interpret=self._interpret)
        out = ops.dequant_u8(x, p.packed_scale, p.packed_bias,
                             out_dtype=p.out_dtype, interpret=self._interpret)
        return out.reshape((-1,) + p.row_shape)

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
        quant = getattr(loader.ds, "quant", {})

        def run():
            while not stop.is_set():
                try:
                    batch = next(loader)
                    state = batch.pop("_state", None)
                    with obs.span("device_loader.h2d") as sp:
                        if self.global_arrays:
                            moved = self._put_shards(batch, detach)
                        else:
                            moved = {
                                k: jax.device_put(_wire(
                                    quant, k, np.array(v, copy=True) if detach else v
                                ), dev)
                                for k, v in batch.items()
                            }
                            # the transfer must COMPLETE before the next host
                            # batch may recycle the staging ring buffer under it
                            jax.block_until_ready(list(moved.values()))
                        sp.n = nbytes = sum(int(v.nbytes) for v in batch.values())
                    with self._stats_lock:
                        self._h2d_s += sp.seconds
                        self._h2d_bytes += nbytes
                        self._h2d_n += 1
                    # on-device decode is part of the FEED pipeline: dispatch
                    # the fused dequant here so the consumer's critical path
                    # is nothing but q.get() + train step
                    with obs.span("device_loader.dequant"):
                        if self.global_arrays:
                            moved = self._assemble(moved)
                        else:
                            self._dequant_on_device(moved)
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

    # ---- global assembly (DESIGN.md §15) ------------------------------------
    def _put_shards(self, batch: Dict[str, np.ndarray], detach: bool) -> list:
        """Split the local host batch's rows over this host's addressable
        devices and ``device_put`` each device's block of every field
        (uint8 for quantized fields, in ``_wire``'s view: a block of rows is
        a contiguous slice, so its packed view is free too): one
        ``device_loader.shard_put`` span per device around its puts, then one
        wait for every transfer. Returns one ``{field: single-device array}``
        per device, in device order."""
        jax = self._jax
        if self._gsharding is None:
            from ..distributed import data_mesh

            self._gsharding = data_mesh.data_sharding()
            self._gdevices = jax.local_devices()
        devs = self._gdevices
        nd = len(devs)
        quant = getattr(self.loader.ds, "quant", {})
        for k, v in batch.items():
            n = int(v.shape[0])
            if n % nd:
                raise RawArrayError(
                    f"{k}: local batch of {n} rows does not split over "
                    f"{nd} local devices"
                )
        blocks = []
        for i, d in enumerate(devs):
            with obs.span("device_loader.shard_put") as sp:
                rows = {}
                for k, v in batch.items():
                    per = int(v.shape[0]) // nd
                    rows[k] = v[i * per : (i + 1) * per]
                    if detach:
                        rows[k] = np.array(rows[k], copy=True)
                    rows[k] = _wire(quant, k, rows[k])
                sp.n = sum(int(r.nbytes) for r in rows.values())
                blocks.append({k: jax.device_put(r, d) for k, r in rows.items()})
        # transfers must COMPLETE before the staging ring may recycle
        jax.block_until_ready(blocks)
        _SHARDS.add(nd)
        return blocks

    def _assemble(self, blocks: list) -> Dict[str, Any]:
        """Per-device blocks → global ``jax.Array``s: quantized fields are
        dequantized on each block's OWN device (a packed block reshaped back
        to its rows there), then the blocks are declared as the addressable
        shards of the ``(host_count * local_B, ...)`` global array. The
        assembly itself is metadata-only — no gather, no cross-host
        traffic."""
        jax = self._jax
        quant = getattr(self.loader.ds, "quant", {})
        host_count = self.loader.mesh.host_count
        out: Dict[str, Any] = {}
        for k in blocks[0]:
            shards = [b[k] for b in blocks]
            if k in quant:
                shards = [self._decode(s, self._quant_params_on(d)[k])
                          for s, d in zip(shards, self._gdevices)]
            gshape = (len(shards) * int(shards[0].shape[0]) * host_count,) + tuple(
                shards[0].shape[1:])
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
        for f, p in self._quant_params_on(self.device).items():
            if f in moved:
                moved[f] = self._decode(moved[f], p)

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
