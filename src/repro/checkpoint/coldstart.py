"""Cold-start restore engine: checkpoint (path or URL) → device buffers in
one overlapped pipeline (DESIGN.md §13).

``load_checkpoint`` reads phase by phase: resolve every leaf, fetch every
byte, decode every chunk, dequantize, and only then does the caller
``device_put`` — time-to-weights-resident is the SUM of the phases. This
module overlaps them so the total approaches the MAX:

1. **pin wave** — the whole remote version set pins in ONE ``/stat``
   listing round trip per checkpoint directory (sizes + ETags, the HTTP
   analogue of S3 ListObjectsV2; servers without the route fall back to
   per-leaf HEADs), local leaves by inode stat + held fd — a checkpoint
   overwritten mid-restore fails fast instead of silently mixing
   generations — and a bounded number of keep-alive sockets pre-warm for
   the fetch wave to come;
2. **bounded streaming** — leaves are admitted largest-first under an
   in-flight byte budget (knob ``RA_COLDSTART_INFLIGHT``); each admitted
   leaf's driver task resolves its header / chunk table / quant schema and
   fans its slab reads or chunk fetch+decode tasks onto the shared engine
   pool, so resolution round-trips, fetch, and decompress of MANY leaves
   interleave instead of serializing into phases;
3. **overlapped device upload** — whichever pool thread completes a leaf
   dispatches its ``jax.device_put`` (and, for quantized-u8 leaves
   restoring onto a single device, the fused Pallas ``dequant_u8`` —
   uint8 crosses the link, floats materialize device-side through the same
   ``DeviceDequant`` the device feed plane uses for batches) WITHOUT
   blocking, while later leaves are still being fetched/decoded; one quiet
   barrier at the end waits for every transfer at once.

The phase-by-phase path survives as :func:`restore_naive` — the benchmark
baseline (`benchmarks/bench_coldstart.py`) and the tests' reference. Both
put every leaf through :func:`_put_leaf`.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import core as ra
from ..core import obs
from ..core.spec import env_int
from .store import _entry_quant, _join, _leaf_name, _load_manifest


def default_inflight_bytes() -> int:
    """In-flight decode-buffer budget (knob ``RA_COLDSTART_INFLIGHT``,
    default 1 GiB): peak host bytes held by leaves that are fetched or
    decoding but not yet resident on device. Quantized leaves count their
    logical (post-dequant) size when dequantization must happen host-side."""
    return max(1, env_int("RA_COLDSTART_INFLIGHT", 1 << 30))


@dataclass
class ColdStartStats:
    """Filled in by :func:`restore_pipelined` (pass one in to collect)."""

    leaves: int = 0
    logical_bytes: int = 0         # sum of restored (post-dequant) leaf bytes
    stored_bytes: int = 0          # sum of on-disk/wire payload bytes
    resolve_s: float = 0.0         # wave 1: version pins + socket pre-warm (span restore.pin)
    restore_s: float = 0.0         # total time to all-weights-resident (span restore)
    h2d_s: float = 0.0             # spans restore.h2d (device_put + dequant) + restore.drain
    h2d_bytes: int = 0             # bytes crossing the host->device boundary
    dequant_leaves: int = 0        # leaves decoded from u8 (device or host)
    prewarmed_conns: int = 0       # sockets opened by pool pre-warm
    peak_inflight_bytes: int = 0   # observed max of the scheduler's budget
    inflight_cap: int = 0          # the budget it ran under


@dataclass
class _LeafPlan:
    name: str
    fpath: str
    entry: Dict[str, Any]
    want: Tuple[int, ...] = ()     # model-side shape (from the like tree)
    hdr: Any = None
    src: Any = None                # int fd, RemoteReader, or None
    fd: Optional[int] = None       # owned fd (closed by the scheduler)
    table: Any = None
    quant: Any = None              # QuantInfo or None
    pin: Any = None                # (mtime_ns, size) local | ETag str remote
    pinned: Any = None             # Event: version pin landed (or failed)
    pin_err: Any = None            # pin-task failure, re-raised by the driver
    fallback: bool = False         # non-plain non-chunked: one ra.read task
    cost: int = 0                  # budget charge while in flight
    sharding: Any = None           # per-leaf device_put target (or None)
    out: Any = None                # the restored jax.Array
    span_id: int = 0               # the leaf's restore.leaf span (obs)
    t_start: int = 0               # its start, obs.now()


def shardings_from_specs(mesh, tree: Any) -> Any:
    """Map a pytree of ``PartitionSpec``s (or None) to ``NamedSharding``s on
    ``mesh`` — the bridge from ``distributed.sharding.spec_for`` rule specs
    to the per-leaf placement :func:`restore_pipelined` consumes."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def one(spec):
        if spec is None:
            spec = PartitionSpec()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(one, tree, is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))


def _local_pin(fpath: str) -> Tuple[int, int]:
    st = os.stat(fpath)
    return (st.st_mtime_ns, st.st_size)


class _Budget:
    """In-flight byte accounting: admit (blocking), release, peak tracking."""

    def __init__(self, cap: int):
        self.cap = cap
        self.used = 0   # guarded-by: _cond
        self.peak = 0   # guarded-by: _cond
        self._cond = threading.Condition()
        self._aborted = False  # guarded-by: _cond

    def admit(self, cost: int) -> bool:
        """Block until ``cost`` fits (a single over-budget leaf is admitted
        alone — the cap bounds concurrency, it must never deadlock a leaf
        larger than itself). Returns False if the restore aborted."""
        with self._cond:
            while not self._aborted and self.used > 0 and self.used + cost > self.cap:
                self._cond.wait(timeout=0.5)
            if self._aborted:
                return False
            self.used += cost
            self.peak = max(self.peak, self.used)
            return True

    def release(self, cost: int) -> None:
        with self._cond:
            self.used -= cost
            self._cond.notify_all()

    def abort(self) -> None:
        with self._cond:
            self._aborted = True
            self._cond.notify_all()


def _pin_leaf(plan: _LeafPlan, stat_pins: Optional[Dict[str, Tuple[int, Optional[str]]]] = None) -> None:
    """Pin one leaf's version — local leaves by inode identity (mtime+size,
    plus a held fd), remote leaves by ETag: from the checkpoint directory's
    one-shot ``/stat`` listing when available (zero per-leaf round trips),
    else a revalidating HEAD. Touches no payload bytes: header/table
    resolution rides inside the streaming drivers where its round trips
    overlap fetch and decode."""
    if ra.is_url(plan.fpath):
        from .. import remote

        ent = stat_pins.get(plan.fpath) if stat_pins else None
        if ent is not None:
            # a stale listing cannot slip through: every ranged response's
            # ETag is checked against this pin, so a leaf replaced between
            # listing and read fails loudly on its first byte
            reader = remote.get_reader(plan.fpath, pinned=ent)
        else:
            # revalidate: the pin must be the server's CURRENT generation,
            # not whatever an earlier traversal cached
            reader = remote.get_reader(plan.fpath, revalidate=True)
        plan.src = reader
        plan.pin = reader.etag
    else:
        plan.pin = _local_pin(plan.fpath)
        plan.fd = plan.src = os.open(plan.fpath, os.O_RDONLY)


def _stat_pins(plans: List[_LeafPlan]) -> Dict[str, Tuple[int, Optional[str]]]:
    """Version-set pinning in one round trip per checkpoint directory: a
    ``/stat`` listing returns (size, ETag) for every file, the HTTP
    analogue of S3's ListObjectsV2. Per-leaf HEADs dominate the pin wave
    on many-leaf checkpoints (one request each against a request-bound
    server), so the listing collapses that whole wave; servers without the
    route just leave the map empty and leaves HEAD-pin individually."""
    from .. import remote

    pins: Dict[str, Tuple[int, Optional[str]]] = {}
    for d in sorted({p.fpath.rsplit("/", 1)[0] for p in plans if ra.is_url(p.fpath)}):
        try:
            listing = remote.stat_dir(d)
        except remote.RemoteAuthError:
            raise  # denial is authoritative — don't retry it once per leaf
        except ra.RawArrayError:
            continue  # no /stat route (older server) — fall back per leaf
        for name, ent in listing.items():
            pins[f"{d}/{name}"] = ent
    return pins


def _prewarm_alloc(plans: List[_LeafPlan]) -> Dict[str, int]:
    """Socket pre-warm budget, per leaf. The fetch wave runs at most
    ``engine.workers()`` tasks at once, so that is the total number of
    sockets worth holding open ACROSS all leaves — each leaf URL has its
    own pooled ``RemoteReader``, so a naive per-leaf prewarm multiplies
    into hundreds of sockets that mostly sit idle (and, worse, burst past
    server accept backlogs). Spend the budget largest-first: those leaves
    are admitted first and are the only ones whose chunk fetches fan out
    over several connections. Each reader's construction HEAD already
    parks one socket, which ``prewarm`` counts, so most small leaves cost
    nothing. Computable from manifest-derived costs alone, so each leaf's
    pin task opens its own share without a whole-checkpoint barrier."""
    alloc: Dict[str, int] = {}
    left = ra.engine.workers()
    for p in sorted(plans, key=lambda p: p.cost, reverse=True):
        if left <= 0:
            break
        if not ra.is_url(p.fpath):
            continue
        # chunk fetches are the only per-leaf fan-out; estimate their count
        # from the in-flight cost at the engine's chunking granularity
        est = max(1, min(-(-p.cost // max(1, ra.engine.chunk_bytes())), left))
        n = min(est, left)  # RemoteReader.prewarm re-caps at RA_REMOTE_CONNS
        alloc[p.name] = n
        left -= n
    return alloc


def _check_local_pin(plan: _LeafPlan) -> None:
    """Fail fast when a local leaf file was replaced mid-restore (the
    remote twin is the per-response ETag check inside ``RemoteReader``)."""
    if isinstance(plan.pin, tuple):
        try:
            now = _local_pin(plan.fpath)
        except OSError as e:
            raise ra.RawArrayError(
                f"{plan.name}: checkpoint leaf {plan.fpath} vanished "
                f"during restore ({e})"
            ) from None
        if now != plan.pin:
            raise ra.RawArrayError(
                f"{plan.name}: checkpoint leaf {plan.fpath} changed during "
                "restore (checkpoint overwritten?); restart the restore"
            )


def _resolve_leaf(plan: _LeafPlan) -> None:
    """Per-leaf resolution, run INSIDE the leaf's streaming driver so its
    round trips (header, chunk table, quant metadata) overlap other leaves'
    fetch/decode instead of forming a whole-checkpoint barrier."""
    if plan.src is not None and ra.is_url(plan.fpath):
        # pooled ranged read instead of header_of's per-call connection; the
        # block cache keeps the fetched prefix for the payload reads to come
        from ..core.header import decode_header

        head = plan.src.read_range(0, min(plan.src.size, 4096))
        hdr = plan.hdr = decode_header(head)
    else:
        hdr = plan.hdr = ra.header_of(plan.fpath)
    if tuple(hdr.shape) != plan.want:
        raise ValueError(f"{plan.name}: checkpoint {tuple(hdr.shape)} vs model {plan.want}")
    chunked = bool(hdr.flags & ra.FLAG_CHUNKED) and not hdr.big_endian
    plan.fallback = not (hdr.plain or chunked)
    if chunked and plan.src is not None and hdr.data_length:
        plan.table = ra.codec.read_table(plan.src, hdr)
    plan.quant = _entry_quant(plan.entry, plan.fpath, hdr)


def _leaf_tasks(plan: _LeafPlan, arr: np.ndarray) -> List[Callable[[], None]]:
    """The engine tasks that fill ``arr`` with the leaf's stored payload."""
    hdr = plan.hdr
    if plan.fallback:
        def _whole() -> None:
            a = np.asarray(ra.read(plan.fpath))
            np.copyto(arr, a, casting="equiv")  # equiv: byte-order fixups ok

        return [_whole]
    if not hdr.data_length:
        return []
    mv = memoryview(arr.reshape(-1).view(np.uint8)).cast("B")
    if plan.table is not None:
        return ra.codec.chunk_read_tasks(plan.src, hdr, plan.table, 0, hdr.logical_nbytes, mv)
    return ra.engine.span_read_tasks([(plan.src, hdr.nbytes, mv)])


def _entry_quant_hint(entry: Dict[str, Any]) -> Any:
    """QuantInfo from the manifest alone (no leaf I/O) — enough for budget
    costs and kernel warm-up; drivers re-derive authoritatively (with the
    metadata fallback for foreign u8 files) once the header is in hand."""
    q = entry.get("quant")
    if q is None:
        return None
    try:
        return ra.quant.QuantInfo.from_dict(q)
    except Exception:
        return None


def _decoded_on_device(quant: Any, sharding: Any, shape: Tuple[int, ...]) -> bool:
    """Whether a quantized leaf crosses as u8 and is decoded on its one
    target device by the fused kernel. A sharded leaf is decoded on the
    host (the kernel path puts one block on one device), and so is a 0-d
    leaf (it has no minor axis for the kernel's channels)."""
    return quant is not None and sharding is None and bool(shape)


def _put_leaf(arr: np.ndarray, quant: Any, sharding: Any, device: Any,
              interpret: Optional[bool]) -> Tuple[Any, int]:
    """Put one leaf's stored payload on its device(s) without waiting for
    the transfer, decoding a quantized leaf where :func:`_decoded_on_device`
    says. Returns the array and the host bytes put."""
    import jax

    if _decoded_on_device(quant, sharding, arr.shape):
        from ..kernels.device_dequant import DeviceDequant  # deferred: pallas is heavy

        dq = DeviceDequant(quant, arr.shape, interpret=interpret)
        return dq.to_device(arr, device), int(arr.nbytes)
    if quant is not None:
        arr = quant.dequantize(arr)
    return jax.device_put(arr, sharding if sharding is not None else device), int(arr.nbytes)


def _start_warmup(
    plans: List[_LeafPlan], interpret: Optional[bool]
) -> Optional[Tuple[threading.Thread, List[BaseException]]]:
    """Compile the dequant kernel for every unique quantized (shape, dtype)
    OVERLAPPED with the first fetches: Pallas compiles cost real time, and
    paying them inside the upload thread would serialize them behind the
    pipeline instead of hiding them under I/O. Returns the thread and the
    list its compile error lands in; the caller joins the thread before
    returning (a compile torn down mid-flight at interpreter exit aborts
    the process) and re-raises the error, so a kernel the device refuses
    fails the restore."""
    shapes = {}
    for p in plans:
        if _decoded_on_device(p.quant, p.sharding, p.want):
            shapes[(p.want, str(p.quant.orig_dtype))] = None
    if not shapes:
        return None
    errors: List[BaseException] = []
    parent = obs.current()

    def run() -> None:
        try:
            from ..kernels.device_dequant import compile_decode

            with obs.under(parent), obs.span("restore.warmup", len(shapes)):
                for shape, dt in shapes:
                    # AOT lower+compile only, of the very call the upload
                    # path makes: executing a full-size dummy would burn a
                    # leaf's worth of device time and park this thread in
                    # block_until_ready, GIL-convoying against the fetch wave
                    compile_decode(shape, dt, interpret=interpret)
        except BaseException as e:  # noqa: BLE001 — re-raised by restore_pipelined
            errors.append(e)

    # ralint: allow=thread-lifecycle -- returned to restore_pipelined, which
    # joins it in its finally block; bounded body (one compile per shape)
    t = threading.Thread(target=run, daemon=True, name="ra-coldstart-warm")
    t.start()
    return t, errors


def restore_pipelined(
    path: str,
    params_like: Any,
    opt_like: Any = None,
    *,
    device: Any = None,
    shardings: Any = None,
    opt_shardings: Any = None,
    inflight_bytes: Optional[int] = None,
    interpret: Optional[bool] = None,
    prewarm: bool = True,
    stats: Optional[ColdStartStats] = None,
    _after_resolve: Optional[Callable[[], None]] = None,
) -> Tuple[Any, Any, Dict[str, Any]]:
    """Restore a checkpoint with fetch, decode, dequant, and H2D overlapped.

    Same contract as ``load_checkpoint(path, params_like, opt_like)`` except
    the returned leaves are device-resident ``jax.Array``s:

    * ``device`` — explicit target device (default: jax's default);
    * ``shardings``/``opt_shardings`` — optional pytrees (matching
      ``params_like``/``opt_like``) of ``jax.sharding.Sharding`` per leaf
      for resharded restore onto a live mesh (see
      :func:`shardings_from_specs`); sharded quantized leaves dequantize
      host-side (the fused kernel path needs a single addressable target);
    * ``inflight_bytes`` — override the ``RA_COLDSTART_INFLIGHT`` budget;
    * ``stats`` — a :class:`ColdStartStats` to fill in;
    * ``_after_resolve`` — test hook, called between the pin wave and
      streaming (mutating the checkpoint here must trip the pins).

    Raises ``RawArrayError`` when any leaf's pinned version (local
    mtime+size, remote ETag) changes mid-restore, and propagates auth/
    transport errors unchanged (fail fast — never a silently mixed
    checkpoint)."""
    st = stats if stats is not None else ColdStartStats()
    st.inflight_cap = max(1, inflight_bytes if inflight_bytes is not None else default_inflight_bytes())
    with obs.span("restore") as sp:
        out = _restore_pipelined(path, params_like, opt_like, st, device=device,
                                 shardings=shardings, opt_shardings=opt_shardings,
                                 interpret=interpret, prewarm=prewarm,
                                 _after_resolve=_after_resolve)
    st.restore_s = sp.seconds
    return out


def _restore_pipelined(
    path: str,
    params_like: Any,
    opt_like: Any,
    st: ColdStartStats,
    *,
    device: Any,
    shardings: Any,
    opt_shardings: Any,
    interpret: Optional[bool],
    prewarm: bool,
    _after_resolve: Optional[Callable[[], None]],
) -> Tuple[Any, Any, Dict[str, Any]]:
    """The body of :func:`restore_pipelined`, run inside its ``restore``
    span, which every span of the restore names as an ancestor."""
    import jax

    restore_id = obs.current()
    cap = st.inflight_cap
    manifest = _load_manifest(path)

    # ---- plan construction (tree order preserved for reassembly) ----------
    trees: List[Tuple[str, Any, Any]] = [("param", params_like, shardings)]
    if opt_like is not None:
        trees.append(("opt", opt_like, opt_shardings))
    plans: List[_LeafPlan] = []
    tree_meta = []  # (prefix, treedef, leaf names in tree order)
    for prefix, tree, shtree in trees:
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        names = [_leaf_name(pth, prefix) for pth, _ in flat]
        shards: List[Any] = [None] * len(flat)
        if shtree is not None:
            sflat = jax.tree_util.tree_flatten(shtree, is_leaf=lambda x: x is None)[0]
            if len(sflat) != len(flat):
                raise ValueError(
                    f"shardings tree has {len(sflat)} leaves, {prefix} tree has {len(flat)}"
                )
            shards = list(sflat)
        for name, (pth, like), sh in zip(names, flat, shards):
            entry = manifest["leaves"].get(name)
            if entry is None:
                raise ra.RawArrayError(f"{name}: missing from checkpoint manifest")
            want = tuple(like.shape)
            if "shape" in entry and tuple(entry["shape"]) != want:
                raise ValueError(f"{name}: checkpoint {tuple(entry['shape'])} vs model {want}")
            plan = _LeafPlan(
                name=name, fpath=_join(path, entry["file"]), entry=entry,
                want=want, sharding=sh, quant=_entry_quant_hint(entry),
            )
            # budget/scheduling cost is knowable from the manifest alone:
            # leaves hold their STORED element width host-side (u8 for
            # quantized), except quantized leaves decoded on the host,
            # which hold the logical float footprint
            elems = int(np.prod(want, dtype=np.int64)) if want else 1
            if plan.quant is not None:
                out_itemsize = np.dtype(plan.quant.orig_dtype).itemsize
                st.logical_bytes += elems * out_itemsize
                on_device = _decoded_on_device(plan.quant, sh, want)
                plan.cost = elems * (1 if on_device else out_itemsize)
            else:
                logical = elems * np.dtype(like.dtype).itemsize
                st.logical_bytes += logical
                plan.cost = max(logical, 1)
            plans.append(plan)
        tree_meta.append((prefix, treedef, names))

    by_name = {p.name: p for p in plans}
    st.leaves = len(plans)

    # ---- wave 1: pin versions + prewarm sockets (overlapped) --------------
    t_pins = obs.now()
    warmup: Optional[Tuple[threading.Thread, List[BaseException]]] = None
    # a thread that finishes a leaf wakes the scheduler / dispatches H2D
    # through the GIL, and CPython's default 5ms switch interval is the
    # latency of every such wake while the pool grinds task wrappers — at
    # hundreds of cross-thread wakes per restore that convoy tax rivals
    # the transfers themselves. Tighten it for the restore window only.
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(min(prev_switch, 0.001))
    try:
        # warmup needs only manifest-derived facts (want shape + quant hint),
        # so its jit compiles overlap the pin wave's round trips
        warmup = _start_warmup(plans, interpret)
        # one listing per checkpoint dir pins the whole remote version set
        stat_pins = _stat_pins(plans)

        order = sorted(plans, key=lambda p: p.cost, reverse=True)
        budget = _Budget(cap)
        first_err: List[BaseException] = []
        err_lock = threading.Lock()
        stats_lock = threading.Lock()
        all_done = threading.Event()
        done_count = [0]
        pins_done = threading.Event()
        pins_left = [len(order)]
        alloc = _prewarm_alloc(plans) if prewarm else {}

        def _fail(e: BaseException) -> None:
            with err_lock:
                if not first_err:
                    first_err.append(e)
            budget.abort()
            all_done.set()  # wake the waiting scheduler

        def _count_done() -> None:
            with stats_lock:
                done_count[0] += 1
                if done_count[0] == len(order):
                    all_done.set()

        def _pin_task(plan: _LeafPlan) -> None:
            """Version pin (+ this leaf's socket-prewarm share). All pin
            tasks are queued BEFORE any payload driver, so the pin set is
            established at restore start — but payload streaming of already
            -pinned leaves runs concurrently instead of waiting for the
            slowest HEAD of the whole checkpoint."""
            try:
                _pin_leaf(plan, stat_pins)
                n = alloc.get(plan.name, 0)
                if n and plan.src is not None and ra.is_url(plan.fpath):
                    got = plan.src.prewarm(n)
                    with stats_lock:
                        st.prewarmed_conns += got
            except BaseException as e:  # noqa: BLE001 — re-raised by driver
                plan.pin_err = e
            finally:
                plan.pinned.set()
                with stats_lock:
                    pins_left[0] -= 1
                    if pins_left[0] == 0:
                        st.resolve_s = obs.record("restore.pin", t_pins, obs.now(),
                                                  parent=restore_id, n=len(order)).seconds
                        pins_done.set()

        inline = (
            ra.engine.workers() == 1
            or ra.engine.sequential_forced()
            or ra.engine.on_engine_thread()
        )
        pool = None if inline else ra.engine.get_pool()

        for plan in order:
            plan.pinned = threading.Event()
        if pool is None:
            for plan in order:
                _pin_task(plan)
        else:
            for plan in order:
                pool.submit(_pin_task, plan)

        if _after_resolve is not None:
            # test hook: act as a barrier so a harness can mutate the
            # checkpoint strictly between "pins taken" and "payload read"
            pins_done.wait()
            _after_resolve()

        def _finish_leaf(plan: _LeafPlan, arr: np.ndarray) -> None:
            """Pin check + device_put (+ fused dequant) DISPATCH for one
            completed leaf. Runs on whichever pool thread finished the
            leaf's last payload task: a dedicated upload thread would
            re-acquire the GIL for every handoff while the pool grinds
            task wrappers, and those handoffs cost more than the uploads.
            Deliberately does NOT block on the transfer — a thread parked
            in ``block_until_ready`` re-enters the GIL convoy on every
            wakeup (measured ~10-40x inflation under pool churn); the
            enqueue is cheap, jax pins the source buffer until the copy
            lands, and one quiet ``block_until_ready`` over the whole tree
            runs after the wave drains."""
            try:
                _check_local_pin(plan)
                with obs.under(plan.span_id), obs.span("restore.h2d") as sp:
                    plan.out, nbytes = _put_leaf(arr, plan.quant, plan.sharding, device, interpret)
                    sp.n = nbytes
                with stats_lock:
                    st.h2d_s += sp.seconds
                    st.h2d_bytes += nbytes
                    st.dequant_leaves += plan.quant is not None
            except BaseException as e:  # noqa: BLE001 — forwarded
                _fail(e)
            finally:
                # the ledger tracks decode-side residency; the source
                # buffer may outlive the release by the (short) tail of an
                # async copy jax is still draining
                budget.release(plan.cost)
                obs.record("restore.leaf", plan.t_start, obs.now(), parent=restore_id,
                           n=int(plan.hdr.data_length), id=plan.span_id)
                _count_done()

        def _drive_leaf(plan: _LeafPlan) -> None:
            """Resolve header/table/quant, then fan out the payload tasks —
            runs on the pool, so many leaves resolve concurrently and their
            round trips hide under other leaves' fetch/decode."""
            # the leaf's span runs from here to the end of _finish_leaf, on
            # whichever thread finishes it; its payload tasks run under it
            plan.span_id, plan.t_start = obs.new_id(), obs.now()
            try:
                # FIFO guarantees this leaf's pin task was dequeued before
                # this driver, so the wait is at most one in-flight HEAD
                plan.pinned.wait()
                if plan.pin_err is not None:
                    raise plan.pin_err
                _resolve_leaf(plan)
                with stats_lock:
                    st.stored_bytes += int(plan.hdr.data_length)
                arr = np.empty(plan.hdr.shape, plan.hdr.dtype())
                tasks = _leaf_tasks(plan, arr)
            except BaseException as e:  # noqa: BLE001 — forwarded
                budget.release(plan.cost)
                _fail(e)
                _count_done()
                return
            if not tasks:
                _finish_leaf(plan, arr)
                return
            remaining = [len(tasks)]
            rlock = threading.Lock()

            def _wrap(t: Callable[[], None]) -> None:
                try:
                    if not first_err:
                        with obs.under(plan.span_id):
                            t()
                except BaseException as e:  # noqa: BLE001 — forwarded
                    _fail(e)
                finally:
                    with rlock:
                        remaining[0] -= 1
                        last = remaining[0] == 0
                if last and not first_err:
                    _finish_leaf(plan, arr)
                elif last:
                    budget.release(plan.cost)
                    _count_done()

            if pool is None:
                for t in tasks:
                    _wrap(t)
            else:
                for t in tasks:
                    pool.submit(_wrap, t)

        for plan in order:
            with obs.span("restore.admit_wait", plan.cost):
                admitted = budget.admit(plan.cost)
            if not admitted:
                _count_done()  # never scheduled; keep the ledger whole
                continue
            if first_err:
                budget.release(plan.cost)
                _count_done()
                continue
            if pool is None:
                _drive_leaf(plan)
            else:
                pool.submit(_drive_leaf, plan)
        all_done.wait()
        # an abort can fire while payload tasks are still draining; their
        # buffers stay alive via the closures, and the pool is process-wide
        # so nothing here tears it down underneath them

        if first_err:
            e = first_err[0]
            if isinstance(e, ra.RawArrayError) and "changed on server during read" in str(e):
                raise ra.RawArrayError(
                    f"checkpoint overwritten during restore: {e}"
                ) from e
            raise e

        # one quiet barrier for every async transfer/dequant the completion
        # threads enqueued — the pool is drained, so this wait runs without
        # GIL competition and finishes at memcpy speed
        with obs.span("restore.drain") as sp:
            jax.block_until_ready([p.out for p in plans])
        st.h2d_s += sp.seconds
    finally:
        sys.setswitchinterval(prev_switch)
        if warmup is not None:
            warmup[0].join()
        for p in plans:
            if p.fd is not None:
                try:
                    os.close(p.fd)
                except OSError:
                    pass

    if warmup is not None and warmup[1]:
        raise warmup[1][0]
    st.peak_inflight_bytes = budget.peak

    # ---- reassemble trees in original leaf order --------------------------
    outs: List[Any] = []
    for prefix, treedef, names in tree_meta:
        leaves = [by_name[n].out for n in names]
        outs.append(jax.tree_util.tree_unflatten(treedef, leaves))
    params = outs[0]
    opt = outs[1] if opt_like is not None else None
    return params, opt, manifest.get("extra", {})


def restore_naive(
    path: str,
    params_like: Any,
    opt_like: Any = None,
    *,
    device: Any = None,
    shardings: Any = None,
    opt_shardings: Any = None,
    interpret: Optional[bool] = None,
    stats: Optional[ColdStartStats] = None,
) -> Tuple[Any, Any, Dict[str, Any]]:
    """Phase-by-phase restore: fetch + decode EVERY leaf to host first, THEN
    device_put (+ on-device dequant) leaf by leaf. Runs the exact same
    per-leaf put and decode as :func:`restore_pipelined` (:func:`_put_leaf`),
    so the two paths are bit-exact by construction and their difference is
    pure overlap. The benchmark baseline and the tests' reference."""
    import jax

    from .store import _read_leaves_parallel

    st = stats if stats is not None else ColdStartStats()
    t_all = time.perf_counter()
    manifest = _load_manifest(path)

    trees: List[Tuple[str, Any, Any]] = [("param", params_like, shardings)]
    if opt_like is not None:
        trees.append(("opt", opt_like, opt_shardings))

    outs: List[Any] = []
    for prefix, tree, shtree in trees:
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        names = [_leaf_name(pth, prefix) for pth, _ in flat]
        shards: List[Any] = [None] * len(flat)
        if shtree is not None:
            shards = list(jax.tree_util.tree_flatten(shtree, is_leaf=lambda x: x is None)[0])
        # phase 1+2: fetch + decode everything to host (stored form)
        quants: Dict[str, Any] = {}
        arrays = _read_leaves_parallel(path, manifest, names, quants_out=quants)
        moved: List[Any] = []
        # phase 3: sequential per-leaf H2D + device dequant
        for name, (pth, like), sh in zip(names, flat, shards):
            arr = arrays[name]
            want = tuple(like.shape)
            if tuple(arr.shape) != want:
                raise ValueError(f"{name}: checkpoint {arr.shape} vs model {want}")
            quant = quants.get(name)
            st.leaves += 1
            st.logical_bytes += (
                arr.nbytes * np.dtype(quant.orig_dtype).itemsize if quant is not None else arr.nbytes
            )
            t0 = time.perf_counter()
            out, nbytes = _put_leaf(arr, quant, sh, device, interpret)
            jax.block_until_ready(out)
            st.h2d_s += time.perf_counter() - t0
            st.h2d_bytes += nbytes
            st.dequant_leaves += quant is not None
            moved.append(out)
        outs.append(jax.tree_util.tree_unflatten(treedef, moved))

    st.restore_s = time.perf_counter() - t_all
    params = outs[0]
    opt = outs[1] if opt_like is not None else None
    return params, opt, manifest.get("extra", {})
