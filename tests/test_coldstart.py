"""Cold-start restore pipeline (DESIGN.md §13): ``restore_pipelined`` must
agree bit-exactly with ``restore_naive`` over every storage variant and
transport, respect the in-flight byte budget, pin the checkpoint's version
set at restore start (and fail FAST — never a silently mixed checkpoint —
when that set changes mid-restore, the server dies, or auth is denied).

Like test_remote.py, everything runs against a real loopback HTTP server —
no mocks; the conftest per-test SIGALRM timeout turns any hang into a
failure."""

import http.server as _http_server
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import repro.core as ra
from repro import remote
from repro.checkpoint import (
    ColdStartStats,
    restore_naive,
    restore_pipelined,
    restore_resharded,
    save_checkpoint,
    shardings_from_specs,
)


@pytest.fixture()
def served(tmp_path):
    server = remote.serve(str(tmp_path), port=0)
    try:
        yield str(tmp_path), server.url
    finally:
        server.shutdown()
        server.server_close()
        remote.close_readers()
        remote.reset_shared_cache()


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((96, 64)).astype(np.float32),
        "inner": {
            "b": rng.standard_normal((64,)).astype(np.float32),
            "k": rng.standard_normal((32, 48)).astype(np.float32),
        },
    }


def _like(tree):
    import jax

    return jax.tree_util.tree_map(lambda x: np.empty(x.shape, x.dtype), tree)


def _cold():
    remote.close_readers()
    remote.reset_shared_cache()


def _assert_trees_equal(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        nx, ny = np.asarray(x), np.asarray(y)
        assert nx.dtype == ny.dtype
        np.testing.assert_array_equal(nx, ny)


# --------------------------------------------------- pipelined ≡ naive
@pytest.mark.parametrize("kw", [{}, {"chunked": True}, {"chunked": True, "quantize": "u8"}])
def test_pipelined_matches_naive_local(tmp_path, kw):
    tree = _tree()
    p = save_checkpoint(str(tmp_path), 1, tree, **kw)
    _cold()
    naive, _, _ = restore_naive(p, _like(tree))
    _cold()
    pipe, _, _ = restore_pipelined(p, _like(tree))
    _assert_trees_equal(pipe, naive)
    import jax

    for leaf in jax.tree_util.tree_leaves(pipe):
        assert isinstance(leaf, jax.Array)  # device-resident, not numpy


def test_pipelined_matches_naive_url_chunked_quant(served):
    root, base = served
    tree = _tree(1)
    p = save_checkpoint(root, 1, tree, chunked=True, quantize="u8")
    url = f"{base}/{os.path.basename(p)}"
    _cold()
    naive, _, _ = restore_naive(url, _like(tree))
    _cold()
    st = ColdStartStats()
    pipe, _, _ = restore_pipelined(url, _like(tree), stats=st)
    _assert_trees_equal(pipe, naive)
    assert st.leaves == 3
    assert st.restore_s > 0


def test_pipelined_restores_opt_state_too(tmp_path):
    tree = _tree(2)
    opt = {"m": np.zeros((96, 64), np.float32), "v": np.ones((96, 64), np.float32)}
    p = save_checkpoint(str(tmp_path), 3, tree, opt_state=opt, chunked=True,
                        extra={"step": 3})
    got_p, got_o, extra = restore_pipelined(p, _like(tree), _like(opt))
    _assert_trees_equal(got_p, tree)
    _assert_trees_equal(got_o, opt)
    assert extra["step"] == 3


def test_shape_mismatch_raises(tmp_path):
    tree = _tree()
    p = save_checkpoint(str(tmp_path), 1, tree)
    bad = _like(tree)
    bad["w"] = np.empty((8, 8), np.float32)
    with pytest.raises(ValueError, match="checkpoint"):
        restore_pipelined(p, bad)


# -------------------------------------------------- resharded onto a mesh
@pytest.mark.parametrize("transport", ["local", "url"])
def test_resharded_restore_onto_mesh(served, transport):
    import jax
    from jax.sharding import Mesh, PartitionSpec

    root, base = served
    tree = _tree(3)
    p = save_checkpoint(root, 1, tree, chunked=True, quantize="u8")
    path = p if transport == "local" else f"{base}/{os.path.basename(p)}"

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    specs = {"w": PartitionSpec("data", None), "inner": {"b": None, "k": None}}
    sh = shardings_from_specs(mesh, specs)

    # naive with the SAME shardings: sharded quantized leaves dequantize
    # host-side in both paths, so bit-exactness is by construction
    _cold()
    naive, _, _ = restore_naive(path, _like(tree), shardings=sh)
    _cold()
    pipe, _, _ = restore_pipelined(path, _like(tree), shardings=sh)
    _assert_trees_equal(pipe, naive)
    assert pipe["w"].sharding.mesh == mesh


def test_restore_resharded_rows_dequantize(tmp_path):
    tree = _tree(4)
    p = save_checkpoint(str(tmp_path), 1, tree, chunked=True, quantize="u8")
    # host-side dequant reference (restore_resharded dequantizes host-side)
    ref = np.asarray(ra.read(os.path.join(p, "param__w.ra"), dequantize=True))
    rows = restore_resharded(p, "param__w", row_start=16, row_stop=48, dequantize=True)
    np.testing.assert_array_equal(rows, ref[16:48])


# ------------------------------------------------------- in-flight budget
def test_inflight_cap_bounds_peak(tmp_path):
    tree = {f"l{i}": np.random.default_rng(i).standard_normal((128, 128)).astype(np.float32)
            for i in range(6)}  # 6 × 64 KiB
    p = save_checkpoint(str(tmp_path), 1, tree)
    leaf = 128 * 128 * 4
    cap = leaf + leaf // 2  # > largest single leaf, < 2 leaves — forces queuing
    st = ColdStartStats()
    got, _, _ = restore_pipelined(p, _like(tree), inflight_bytes=cap, stats=st)
    _assert_trees_equal(got, tree)
    assert 0 < st.peak_inflight_bytes <= cap
    assert st.inflight_cap == cap
    # uncapped: the whole wave may be resident at once
    st2 = ColdStartStats()
    restore_pipelined(p, _like(tree), stats=st2)
    assert st2.peak_inflight_bytes >= st.peak_inflight_bytes


def test_oversized_leaf_admitted_alone(tmp_path):
    """A cap smaller than the largest leaf must bound CONCURRENCY (that
    leaf streams alone), never deadlock the scheduler."""
    tree = _tree(5)
    p = save_checkpoint(str(tmp_path), 1, tree)
    largest = max(x.nbytes for x in [tree["w"], tree["inner"]["b"], tree["inner"]["k"]])
    st = ColdStartStats()
    got, _, _ = restore_pipelined(p, _like(tree), inflight_bytes=largest // 4, stats=st)
    _assert_trees_equal(got, tree)
    assert st.peak_inflight_bytes <= largest


# ------------------------------------------------ version pins: fail fast
def test_local_overwrite_mid_restore_fails_fast(tmp_path):
    tree = _tree(6)
    p = save_checkpoint(str(tmp_path), 1, tree, chunked=True)
    leaf = os.path.join(p, "param__w.ra")

    def clobber():
        ra.write(leaf, _tree(7)["w"], chunked=True)
        st = os.stat(leaf)
        os.utime(leaf, ns=(st.st_mtime_ns + 10_000_000, st.st_mtime_ns + 10_000_000))

    with pytest.raises(ra.RawArrayError, match="during restore"):
        restore_pipelined(p, _like(tree), _after_resolve=clobber)


def test_url_overwrite_mid_restore_fails_fast(served):
    """Same-shape overwrite between pin and payload read: the stored bytes
    would parse fine, so only the ETag pin can catch it."""
    root, base = served
    tree = _tree(8)
    p = save_checkpoint(root, 1, tree, chunked=True, quantize="u8")
    url = f"{base}/{os.path.basename(p)}"
    leaf = os.path.join(p, "param__w.ra")

    def clobber():
        st = os.stat(leaf)
        os.utime(leaf, ns=(st.st_mtime_ns + 10_000_000, st.st_mtime_ns + 10_000_000))

    _cold()
    with pytest.raises(ra.RawArrayError, match="overwritten during restore"):
        restore_pipelined(url, _like(tree), _after_resolve=clobber)


def test_server_death_mid_restore_raises_not_hangs(tmp_path):
    tree = _tree(9)
    p = save_checkpoint(str(tmp_path), 1, tree, chunked=True)
    server = remote.serve(str(tmp_path), port=0)
    url = f"{server.url}/{os.path.basename(p)}"
    killed = []

    def kill():
        server.shutdown()
        server.server_close()
        killed.append(True)

    try:
        _cold()
        with pytest.raises(ra.RawArrayError):
            restore_pipelined(url, _like(tree), _after_resolve=kill)
        assert killed  # the pipeline got as far as the pin wave
    finally:
        if not killed:
            server.shutdown()
            server.server_close()
        _cold()


class _DenyingHandler(_http_server.BaseHTTPRequestHandler):
    def _deny(self):
        body = b"denied\n"
        self.send_response(401)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    do_GET = do_HEAD = _deny

    def log_message(self, fmt, *args):
        pass


def test_auth_denial_fails_fast():
    srv = _http_server.ThreadingHTTPServer(("127.0.0.1", 0), _DenyingHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}/step_00000001"
        with pytest.raises(remote.RemoteAuthError):
            restore_pipelined(url, _like(_tree()))
    finally:
        srv.shutdown()
        srv.server_close()
        _cold()


# ------------------------------------------- /stat listing + pinned readers
def test_stat_endpoint_lists_sizes_and_etags(served):
    root, base = served
    tree = _tree(10)
    p = save_checkpoint(root, 1, tree)
    rel = os.path.basename(p)
    with urllib.request.urlopen(f"{base}/stat/{rel}") as resp:
        assert resp.status == 200
        files = json.loads(resp.read())["files"]
    on_disk = {n for n in os.listdir(p) if os.path.isfile(os.path.join(p, n))}
    assert set(files) == on_disk
    for name, ent in files.items():
        assert ent["size"] == os.path.getsize(os.path.join(p, name))
        assert ent["etag"]

    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(f"{base}/stat/no_such_dir")
    with pytest.raises(urllib.error.HTTPError):  # escape attempt -> 404
        urllib.request.urlopen(f"{base}/stat/../etc")


def test_stat_dir_and_pinned_reader(served):
    root, base = served
    tree = _tree(11)
    p = save_checkpoint(root, 1, tree)
    dir_url = f"{base}/{os.path.basename(p)}"
    listing = remote.stat_dir(dir_url)
    assert "manifest.json" in listing

    # pinned construction skips the HEAD yet reads real bytes
    name = "param__w.ra"
    r = remote.get_reader(f"{dir_url}/{name}", pinned=listing[name])
    assert (r.size, r.etag) == listing[name]
    got = r.read_range(0, 8)
    with open(os.path.join(p, name), "rb") as f:
        assert got == f.read(8)

    # a stale pin fails loudly on the FIRST ranged response
    _cold()
    size, _ = listing[name]
    r2 = remote.get_reader(f"{dir_url}/{name}", pinned=(size, '"stale-0"'))
    with pytest.raises(ra.RawArrayError, match="changed on server"):
        r2.read_range(0, 8)

    with pytest.raises(ra.RawArrayError):
        remote.stat_dir(f"{base}/no_such_dir")


def test_prewarm_stats(served):
    root, base = served
    tree = {"big": np.random.default_rng(0).standard_normal((512, 512)).astype(np.float32)}
    p = save_checkpoint(root, 1, tree, chunked=True)
    url = f"{base}/{os.path.basename(p)}"
    _cold()
    st = ColdStartStats()
    restore_pipelined(url, _like(tree), stats=st)
    assert st.prewarmed_conns >= 1
    _cold()
    st2 = ColdStartStats()
    restore_pipelined(url, _like(tree), prewarm=False, stats=st2)
    assert st2.prewarmed_conns == 0


# ------------------------------------------------------------ racat inspect
def test_racat_inspect_checkpoint(tmp_path, capsys):
    from repro.core.racat import main as racat_main

    tree = _tree(12)
    p = save_checkpoint(str(tmp_path), 1, tree, chunked=True, quantize="u8")
    assert racat_main(["inspect", p]) == 0
    out = capsys.readouterr().out
    assert "param__w" in out
    assert "param__inner__b" in out
    assert "u8" in out  # quant schema surfaced


# ------------------------------------------------ bf16 leaves, warmup errors
def test_bf16_leaves_quantize_and_restore_in_bound(tmp_path):
    """bfloat16 (an ml_dtypes type numpy does not call floating) is stored
    as u8 codes like any float leaf and restores as bf16 within the
    quantization bound."""
    import jax.numpy as jnp

    tree = {k: v.astype(jnp.bfloat16) for k, v in _tree(3).items() if k == "w"}
    p = save_checkpoint(str(tmp_path), 1, tree, chunked=True, quantize="u8")
    with open(os.path.join(p, "manifest.json")) as f:
        entry = json.load(f)["leaves"]["param__w"]
    assert entry["stored_dtype"] == "uint8" and entry["quant"]["orig_dtype"] == "bfloat16"
    got, _, _ = restore_pipelined(p, _like(tree))
    w = np.asarray(got["w"])
    assert w.dtype == np.dtype(jnp.bfloat16)
    scale = np.asarray(entry["quant"]["scale"], np.float32)
    err = np.abs(w.astype(np.float32) - tree["w"].astype(np.float32))
    # half a code step, plus bf16 rounding of the dequantized value
    assert (err <= scale / 2 + np.abs(tree["w"].astype(np.float32)) / 128).all()


def test_warmup_compile_error_fails_the_restore(tmp_path, monkeypatch):
    """A dequant kernel the device refuses must fail the restore, not be
    swallowed by the overlapped warmup."""
    from repro.kernels import ops

    real = ops.dequant_u8

    class Refusing:
        def __call__(self, *a, **kw):
            return real(*a, **kw)

        def lower(self, *a, **kw):
            raise NotImplementedError("Unsupported cast: uint8 -> float32")

    p = save_checkpoint(str(tmp_path), 1, _tree(4), quantize="u8")
    monkeypatch.setattr(ops, "dequant_u8", Refusing())
    with pytest.raises(NotImplementedError, match="Unsupported cast"):
        restore_pipelined(p, _like(_tree(4)))
