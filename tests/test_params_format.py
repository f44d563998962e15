"""``.params`` container format: spec-fixture import proof + golden
byte lock (reference: ``src/ndarray/ndarray.cc :: NDArray::Save/Load``,
magics ``kMXAPINDArrayListMagic=0x112`` / ``NDARRAY_V2_MAGIC=
0xF993FAC9``).

The point of these tests (an early review finding): the format must be
demonstrated, not asserted.  ``_spec_write`` below is an INDEPENDENT
implementation of the documented binary layout -- written from the
spec, byte by byte with ``struct``, sharing no code with
``mx.nd.save`` -- and a file it produces must load into the zoo
ResNet-50.  The golden-bytes test then locks the writer's exact output
so the layout cannot drift silently.
"""
import struct

import numpy as np
import pytest

import mxnet_tpu as mx

# documented layout constants (spec, not imported from the library)
LIST_MAGIC = 0x112
ND_MAGIC = 0xF993FAC9
DTYPE_FLAG = {np.dtype("float32"): 0, np.dtype("float64"): 1,
              np.dtype("float16"): 2, np.dtype("uint8"): 3,
              np.dtype("int32"): 4, np.dtype("int8"): 5,
              np.dtype("int64"): 6}


def _spec_write(f, named_arrays):
    """Write a .params container from the documented spec:

    header:   uint64 LE list-magic 0x112; uint64 reserved 0;
              uint64 array count
    per array: uint32 ndarray-magic 0xF993FAC9; int32 storage type
              (0 = dense); uint32 ndim; int64 x ndim dims;
              int32 dev_type (1 = cpu) + int32 dev_id; int32 dtype
              flag; raw C-order element bytes
    trailer:  uint64 name count; per name uint64 byte length + utf-8
    """
    names = list(named_arrays)
    f.write(struct.pack("<Q", LIST_MAGIC))
    f.write(struct.pack("<Q", 0))
    f.write(struct.pack("<Q", len(names)))
    for n in names:
        a = np.ascontiguousarray(named_arrays[n])
        f.write(struct.pack("<I", ND_MAGIC))
        f.write(struct.pack("<i", 0))
        f.write(struct.pack("<I", a.ndim))
        for d in a.shape:
            f.write(struct.pack("<q", d))
        f.write(struct.pack("<ii", 1, 0))
        f.write(struct.pack("<i", DTYPE_FLAG[a.dtype]))
        f.write(a.tobytes())
    f.write(struct.pack("<Q", len(names)))
    for n in names:
        b = n.encode("utf-8")
        f.write(struct.pack("<Q", len(b)))
        f.write(b)


def test_spec_fixture_loads_into_zoo_resnet50(tmp_path):
    """A container hand-written from the spec (not via mx.nd.save)
    must load into zoo ResNet-50 and install exactly the written
    weights."""
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    net = resnet50_v1()
    net.initialize(ctx=mx.cpu())
    x = mx.nd.ones((1, 3, 224, 224))
    net(x)                                   # materialize all shapes
    params = net._collect_params_with_prefix()
    rng = np.random.RandomState(7)
    fixture = {}
    for name, p in params.items():
        a = p.data().asnumpy()
        v = rng.randn(*a.shape) * 0.01
        if "var" in name:        # BN variances must stay positive
            v = np.abs(v) + 1.0
        fixture[name] = v.astype(a.dtype)
    path = str(tmp_path / "spec_resnet50.params")
    with open(path, "wb") as f:
        _spec_write(f, fixture)

    net.load_parameters(path, ctx=mx.cpu())
    for name, p in net._collect_params_with_prefix().items():
        np.testing.assert_array_equal(p.data().asnumpy(), fixture[name],
                                      err_msg=name)
    # and the loaded net must actually run
    out = net(x)
    assert out.shape == (1, 1000)
    assert np.isfinite(out.asnumpy()).all()


def test_spec_fixture_mx_nd_load_mixed_dtypes(tmp_path):
    """mx.nd.load must read a spec-written file across dtypes and
    ranks (including the empty-name list form)."""
    fixture = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "idx": np.array([3, 1, 2], dtype=np.int64),
        "bytes": np.arange(8, dtype=np.uint8).reshape(2, 2, 2),
        "scalar": np.array(2.5, dtype=np.float64).reshape(()),
    }
    path = str(tmp_path / "mixed.params")
    with open(path, "wb") as f:
        _spec_write(f, fixture)
    loaded = mx.nd.load(path)
    assert set(loaded) == set(fixture)
    # 64-bit values land as the package's canonical 32-bit device
    # dtypes (TPU-native convention, same as mx.nd.array's float64 ->
    # float32); values are preserved exactly for these fixtures
    canon = {np.dtype("int64"): np.dtype("int32"),
             np.dtype("float64"): np.dtype("float32")}
    for k, v in fixture.items():
        got = loaded[k].asnumpy()
        assert got.dtype == canon.get(v.dtype, v.dtype), k
        np.testing.assert_array_equal(got, v.astype(got.dtype),
                                      err_msg=k)


def test_save_matches_spec_writer_byte_for_byte(tmp_path):
    """mx.nd.save's output must equal the independent spec writer's,
    byte for byte -- the two implementations lock each other."""
    fixture = {
        "w": np.arange(6, dtype=np.float32).reshape(2, 3),
        "b": np.array([0.5, -1.5], dtype=np.float32),
    }
    lib_path = str(tmp_path / "lib.params")
    mx.nd.save(lib_path, {k: mx.nd.array(v) for k, v in fixture.items()})
    spec_path = str(tmp_path / "spec.params")
    with open(spec_path, "wb") as f:
        _spec_write(f, fixture)
    assert open(lib_path, "rb").read() == open(spec_path, "rb").read()


# Golden bytes for {"g": float32 [[1, 2]]}: locks the on-disk layout
# against silent drift in BOTH the library and the spec writer.
_GOLDEN_HEX = (
    "1201000000000000"          # uint64 list magic 0x112
    "0000000000000000"          # uint64 reserved
    "0100000000000000"          # uint64 count = 1
    "c9fa93f9"                  # uint32 ndarray magic 0xF993FAC9
    "00000000"                  # int32 stype = dense
    "02000000"                  # uint32 ndim = 2
    "0100000000000000"          # int64 dim 0 = 1
    "0200000000000000"          # int64 dim 1 = 2
    "01000000" "00000000"       # dev_type=1 (cpu), dev_id=0
    "00000000"                  # int32 dtype flag = float32
    "0000803f" "00000040"       # 1.0f, 2.0f LE
    "0100000000000000"          # uint64 name count = 1
    "0100000000000000"          # uint64 name length = 1
    "67"                        # "g"
)


def test_golden_bytes_lock(tmp_path):
    arr = np.array([[1.0, 2.0]], dtype=np.float32)
    path = str(tmp_path / "g.params")
    mx.nd.save(path, {"g": mx.nd.array(arr)})
    assert open(path, "rb").read().hex() == _GOLDEN_HEX
    loaded = mx.nd.load(path)
    np.testing.assert_array_equal(loaded["g"].asnumpy(), arr)
    # and the golden bytes themselves load
    gpath = str(tmp_path / "golden.params")
    open(gpath, "wb").write(bytes.fromhex(_GOLDEN_HEX))
    loaded2 = mx.nd.load(gpath)
    np.testing.assert_array_equal(loaded2["g"].asnumpy(), arr)
