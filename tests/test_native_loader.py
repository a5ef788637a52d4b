"""Native C++ loader vs Python parser parity."""

import numpy as np
import pytest

from tests.conftest import dataset_path


def test_native_parser_parity(tmp_path):
    from libgrape_lite_tpu.io.native import available, parse_file_native
    from libgrape_lite_tpu.io.line_parser import _parse_columns

    if not available():
        pytest.skip("native toolchain unavailable")

    src, dst, w = parse_file_native(dataset_path("p2p-31.e"), 2, True)
    with open(dataset_path("p2p-31.e"), "rb") as f:
        cols = _parse_columns(f.read(), 2, 3)
    assert np.array_equal(src, cols[0])
    assert np.array_equal(dst, cols[1])
    assert np.allclose(w, cols[2])

    oids = parse_file_native(dataset_path("p2p-31.v"), 1, False)[0]
    with open(dataset_path("p2p-31.v"), "rb") as f:
        vcols = _parse_columns(f.read(), 1, 1)
    assert np.array_equal(oids, vcols[0])


def test_native_parser_edge_cases(tmp_path):
    from libgrape_lite_tpu.io.native import available, parse_file_native

    if not available():
        pytest.skip("native toolchain unavailable")

    p = tmp_path / "t.e"
    p.write_text(
        "# comment line\n"
        "1 2 0.5\n"
        "\n"
        "9007199254740993 4 1.25\n"  # 2^53+1: must stay int64-exact
        "-3 7 2.0\n"
    )
    src, dst, w = parse_file_native(str(p), 2, True)
    assert src.tolist() == [1, 9007199254740993, -3]
    assert dst.tolist() == [2, 4, 7]
    assert w.tolist() == [0.5, 1.25, 2.0]


def test_native_parser_missing_file(tmp_path):
    from libgrape_lite_tpu.io.native import available, parse_file_native

    if not available():
        pytest.skip("native toolchain unavailable")
    with pytest.raises(FileNotFoundError):
        parse_file_native(str(tmp_path / "nope.e"), 2, True)


def test_native_edge_sort_parity():
    from libgrape_lite_tpu.io.native import available, sort_edges_native

    if not available():
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(3)
    n_rows, n_cols, e = 500, 900, 20000
    src = rng.integers(0, n_rows, e)
    nbr = rng.integers(0, n_cols, e)
    w = rng.random(e)
    out = sort_edges_native(src, nbr, w, n_rows, n_cols)
    order = np.lexsort((nbr, src))
    assert np.array_equal(out[0], src[order])
    assert np.array_equal(out[1], nbr[order])
    assert np.allclose(out[2], w[order])
    counts = np.bincount(src, minlength=n_rows)
    ip = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=ip[1:])
    assert np.array_equal(out[3], ip)
    # unweighted path
    out2 = sort_edges_native(src, nbr, None, n_rows, n_cols)
    assert out2[2] is None and np.array_equal(out2[0], src[order])


def test_varint_native_matches_numpy_and_detects_corruption():
    """Native LEB128 codec: byte-identical to the numpy encoder, and a
    truncated stream raises instead of silently dropping the tail."""
    import numpy as np
    import pytest

    from libgrape_lite_tpu.io.native import (
        varint_decode_native, varint_encode_native,
    )

    if varint_encode_native(np.zeros(1, np.uint64), False) is None:
        pytest.skip("native library unavailable")

    rng = np.random.default_rng(4)
    vals = np.concatenate([
        rng.integers(0, 128, 50), rng.integers(0, 1 << 40, 50),
        [0, 1, 127, 128, (1 << 64) - 1],
    ]).astype(np.uint64)

    import libgrape_lite_tpu.io.native as nat
    import libgrape_lite_tpu.utils.archive as arc

    enc_nat = varint_encode_native(vals, False)
    orig = nat.varint_encode_native
    nat.varint_encode_native = lambda *a, **k: None
    try:
        enc_np = arc.varint_encode(vals)
    finally:
        nat.varint_encode_native = orig
    assert enc_nat == enc_np
    assert np.array_equal(varint_decode_native(enc_nat, False), vals)

    srt = np.sort(vals)
    assert np.array_equal(
        varint_decode_native(varint_encode_native(srt, True), True), srt
    )

    # truncate mid-value: last byte keeps its continuation bit
    bad = enc_nat[:-1]
    if bad[-1] & 0x80:
        with pytest.raises(ValueError, match="corrupt varint"):
            varint_decode_native(bad, False)


def test_failed_native_build_warns_before_the_python_parser(monkeypatch, tmp_path):
    """No library and a failing `make`: the loader says why (with the
    compiler's output) before callers take the Python parser — it used
    to return None without a word."""
    import subprocess

    from libgrape_lite_tpu.io import native

    def failing_make(cmd, **kw):
        raise subprocess.CalledProcessError(
            2, cmd, stderr="loader.cc:1: error: no such toolchain")

    monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "absent.so"))
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native.subprocess, "run", failing_make)
    with pytest.warns(RuntimeWarning, match="no such toolchain"):
        assert native.available() is False
    # a library that exists but does not dlopen warns too
    (tmp_path / "bad.so").write_bytes(b"not an ELF")
    monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "bad.so"))
    monkeypatch.setattr(native, "_tried", False)
    with pytest.warns(RuntimeWarning, match="did not load"):
        assert native.available() is False


def test_memory_stats_failure_is_not_zeros():
    """`None` (the CPU backend) reads as no allocator stats; a call
    that FAILS raises instead of reporting an empty device."""
    from libgrape_lite_tpu.utils.memory import get_memory_stats

    class NoStats:
        def memory_stats(self):
            return None

    class Broken:
        def memory_stats(self):
            raise RuntimeError("allocator unreachable")

    assert get_memory_stats(NoStats()).device_bytes_in_use == 0
    with pytest.raises(RuntimeError, match="allocator unreachable"):
        get_memory_stats(Broken())
