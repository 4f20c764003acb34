"""Native C++ parser: build (if toolchain present), parity vs Python path."""

import json
import shutil
import subprocess
from pathlib import Path

import numpy as np
import pytest

NATIVE_DIR = Path(__file__).parent.parent / "storm_tpu" / "native"


@pytest.fixture(scope="module")
def native_lib():
    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain")
    r = subprocess.run(["make", "-C", str(NATIVE_DIR)], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    import storm_tpu.native as n

    # force (re)load after build
    n._load_attempted = False
    n._lib = None
    if not n.native_available():
        pytest.skip("native lib failed to load")
    return n


def test_native_parity_with_python(native_lib):
    from storm_tpu.api.schema import decode_instances

    x = np.random.RandomState(0).rand(3, 5, 5, 2).astype(np.float32)
    payload = json.dumps({"instances": x.tolist(), "meta": {"k": [1, "s"]}})
    got = native_lib.parse_instances_native(payload)
    np.testing.assert_allclose(got, x, rtol=1e-6)
    # and through the public decode path
    inst = decode_instances(payload)
    np.testing.assert_allclose(inst.data, x, rtol=1e-6)


@pytest.mark.parametrize(
    "bad",
    [
        '{"instances": [[1,2],[3]]}',  # ragged
        '{"instances": [[1,2],[3,[4]]]}',  # mixed depth
        '{"nope": 1}',
        '{"instances": "x"}',
        "junk",
        '{"instances": []}',
        '{"instances": [[1,2]] } trailing',
    ],
)
def test_native_rejects_malformed(native_lib, bad):
    from storm_tpu.api.schema import SchemaError

    with pytest.raises(SchemaError):
        native_lib.parse_instances_native(bad)


def test_native_number_formats(native_lib):
    payload = '{"instances": [[1, -2.5, 3e2, 0.125e-2, 1E+2, -0.0]]}'
    got = native_lib.parse_instances_native(payload)
    np.testing.assert_allclose(
        got, np.array([[1, -2.5, 300, 0.00125, 100, -0.0]], np.float32), rtol=1e-6
    )


def test_python_fallback_when_disabled(native_lib, monkeypatch):
    monkeypatch.setenv("STORM_TPU_NO_NATIVE", "1")
    import storm_tpu.native as n

    n._load_attempted = False
    n._lib = None
    assert n.parse_instances_native('{"instances": [[1]]}') is None
    from storm_tpu.api.schema import decode_instances

    assert decode_instances('{"instances": [[1.0, 2.0]]}').data.shape == (1, 2)
    n._load_attempted = False
    n._lib = None


# ---- native predictions serializer -------------------------------------------


def test_format_predictions_native_roundtrip():
    from storm_tpu.api.schema import decode_predictions
    from storm_tpu.native import format_predictions_native, native_available

    if not native_available():
        pytest.skip("native library not built")
    a = np.array(
        [[0.1234567891, 0.5, 1e-9, 123456.789], [1.0, 0.0, -0.25, 3.14159265]],
        np.float32,
    )
    s = format_predictions_native(a)
    assert s is not None and s.startswith('{"predictions": [[')
    back = decode_predictions(s)
    np.testing.assert_allclose(back.data, a, rtol=1e-6, atol=1e-7)


def test_format_predictions_matches_python_path(monkeypatch):
    from storm_tpu.api import schema
    from storm_tpu.native import native_available

    if not native_available():
        pytest.skip("native library not built")
    rng = np.random.RandomState(0)
    a = rng.rand(4, 10).astype(np.float32)
    s_native = schema.encode_predictions(a)
    # Force the Python path and compare numerically.
    monkeypatch.setattr(
        "storm_tpu.native.format_predictions_native", lambda arr: None
    )
    s_py = schema.encode_predictions(a)
    d1 = schema.decode_predictions(s_native).data
    d2 = schema.decode_predictions(s_py).data
    np.testing.assert_allclose(d1, d2, rtol=1e-6, atol=1e-7)


def test_format_predictions_1d_and_nonfinite():
    from storm_tpu.api.schema import decode_predictions
    from storm_tpu.native import format_predictions_native, native_available

    if not native_available():
        pytest.skip("native library not built")
    s = format_predictions_native(np.array([0.25, 0.75], np.float32))
    assert decode_predictions(s).data.shape == (1, 2)
    s = format_predictions_native(np.array([[np.nan, np.inf, -np.inf]], np.float32))
    # json module accepts NaN/Infinity tokens (python json.dumps emits them too)
    back = decode_predictions(s).data
    assert np.isnan(back[0, 0]) and np.isinf(back[0, 1]) and back[0, 2] < 0


# ---------------------------------------------------------------------------
# Arrow IPC tensor marshaller (arrow_tensor.cpp) — the C++ zero-copy
# host<->engine boundary (SURVEY.md §2.2), wire-compatible with pyarrow.
# ---------------------------------------------------------------------------


def _need_native_tensor():
    from storm_tpu.native import _load, native_available

    if not native_available() or not hasattr(_load(), "stpu_tensor_encode"):
        pytest.skip("native tensor marshaller not built")


@pytest.mark.parametrize("dt", [
    np.float32, np.float64, np.float16, np.uint8, np.int8, np.uint16,
    np.int16, np.uint32, np.int32, np.uint64, np.int64,
], ids=lambda dt: dt.__name__)
def test_arrow_tensor_roundtrip_all_dtypes(dt):
    _need_native_tensor()
    from storm_tpu.native import decode_tensor_native, encode_tensor_native

    rng = np.random.RandomState(0)
    for shp in [(4,), (2, 3), (1, 28, 28, 1), (3, 1, 2)]:
        x = (rng.rand(*shp) * 100).astype(dt)
        y = decode_tensor_native(encode_tensor_native(x))
        assert y.dtype == x.dtype and y.shape == x.shape
        np.testing.assert_array_equal(y, x)


@pytest.mark.parametrize("dt", [np.float32, np.float16, np.uint8, np.int64],
                         ids=lambda dt: dt.__name__)
def test_arrow_tensor_pyarrow_cross_compat(dt):
    _need_native_tensor()
    pa = pytest.importorskip("pyarrow")
    from storm_tpu.native import decode_tensor_native, encode_tensor_native

    x = (np.random.RandomState(1).rand(2, 5, 3) * 50).astype(dt)
    # native writer -> pyarrow reader
    z = pa.ipc.read_tensor(pa.py_buffer(encode_tensor_native(x))).to_numpy()
    np.testing.assert_array_equal(z, x)
    # pyarrow writer -> native reader
    sink = pa.BufferOutputStream()
    pa.ipc.write_tensor(pa.Tensor.from_numpy(x), sink)
    w = decode_tensor_native(sink.getvalue().to_pybytes())
    assert w.dtype == x.dtype
    np.testing.assert_array_equal(w, x)


def test_arrow_tensor_decode_is_zero_copy_view():
    _need_native_tensor()
    from storm_tpu.native import decode_tensor_native, encode_tensor_native

    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    y = decode_tensor_native(encode_tensor_native(x))
    # A view over the message bytes: no ownership, read-only.
    assert not y.flags.owndata
    assert not y.flags.writeable
    np.testing.assert_array_equal(y, x)


@pytest.mark.parametrize("bad", [
    b"", b"\x00" * 12, b"\xff\xff\xff\xff\x10\x00\x00\x00" + b"\x00" * 32,
    b"garbage" * 5,
], ids=["empty", "zeros", "continuation and nothing", "garbage"])
def test_arrow_tensor_malformed_rejected(bad):
    _need_native_tensor()
    from storm_tpu.native import decode_tensor_native

    with pytest.raises(ValueError):
        decode_tensor_native(bad)


def test_marshal_prefers_native_path(monkeypatch):
    _need_native_tensor()
    from storm_tpu.serve import marshal

    calls = []
    real = marshal.encode_tensor_native

    def spy(x):
        calls.append(x.shape)
        return real(x)

    monkeypatch.setattr(marshal, "encode_tensor_native", spy)
    x = np.ones((2, 4), np.float32)
    buf = marshal.encode_tensor(x)
    assert calls == [(2, 4)]
    np.testing.assert_array_equal(marshal.decode_tensor(buf), x)


def test_arrow_tensor_fortran_order_falls_back():
    _need_native_tensor()
    pa = pytest.importorskip("pyarrow")
    from storm_tpu.native import decode_tensor_native
    from storm_tpu.serve.marshal import decode_tensor

    x = np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    sink = pa.BufferOutputStream()
    pa.ipc.write_tensor(pa.Tensor.from_numpy(x), sink)
    buf = sink.getvalue().to_pybytes()
    # Valid-but-unsupported layout: native path declines (None), the public
    # decode_tensor falls back to pyarrow and still returns the array.
    assert decode_tensor_native(buf) is None
    np.testing.assert_array_equal(decode_tensor(buf), x)


@pytest.mark.parametrize("evil", [-1, 2**62])
def test_arrow_tensor_adversarial_dims_rejected(evil):
    _need_native_tensor()
    from storm_tpu.native import decode_tensor_native, encode_tensor_native

    good = encode_tensor_native(np.ones((2, 3), np.float32))
    idx = good.find((2).to_bytes(8, "little", signed=True), 8)
    assert idx > 0
    patched = bytearray(good)
    patched[idx : idx + 8] = evil.to_bytes(8, "little", signed=True)
    with pytest.raises(ValueError):
        decode_tensor_native(bytes(patched))


@pytest.mark.parametrize("cast", [bytes, bytearray, memoryview])
def test_arrow_tensor_accepts_any_buffer_type(cast):
    _need_native_tensor()
    from storm_tpu.native import decode_tensor_native, encode_tensor_native

    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    y = decode_tensor_native(cast(encode_tensor_native(x)))
    assert y is not None and not y.flags.owndata
    np.testing.assert_array_equal(y, x)


def test_arrow_tensor_unsupported_rank_falls_back():
    _need_native_tensor()
    pa = pytest.importorskip("pyarrow")
    from storm_tpu.native import decode_tensor_native
    from storm_tpu.serve.marshal import decode_tensor

    x = np.ones((1,) * 9, np.float32)  # rank 9 > the fast path's max rank 8
    sink = pa.BufferOutputStream()
    pa.ipc.write_tensor(pa.Tensor.from_numpy(x), sink)
    buf = sink.getvalue().to_pybytes()
    assert decode_tensor_native(buf) is None  # fallback signal, not an error
    np.testing.assert_array_equal(decode_tensor(buf), x)
