"""Serialization round-trip unit tests — a gap the reference never covered
(SURVEY §4: "no serialization round-trip unit tests")."""

import numpy as np
import pytest

from tensorlink_tpu.core import serialization as ser
from tensorlink_tpu.core import shm


def _assert_tree_equal(a, b):
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_tree_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    else:
        assert a == b and type(a) is type(b)


def test_roundtrip_nested():
    obj = {
        "x": np.arange(12, dtype=np.float32).reshape(3, 4),
        "meta": {"ids": [1, 2, 3], "name": "layer.0", "flag": True, "none": None},
        "pair": (np.ones((2, 2), np.int64), -1.5),
        "blob": b"\x00\xffraw",
        "empty": np.zeros((0, 4), np.float32),
    }
    out = ser.decode(ser.encode(obj))
    _assert_tree_equal(obj, out)


def test_roundtrip_bfloat16():
    import jax.numpy as jnp

    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((16, 8)), dtype=jnp.bfloat16
    )
    out = ser.decode(ser.encode({"w": x}))
    np.testing.assert_array_equal(np.asarray(x), out["w"])
    assert str(out["w"].dtype) == "bfloat16"


def test_roundtrip_jax_array():
    import jax.numpy as jnp

    x = jnp.linspace(0, 1, 64).reshape(8, 8)
    out = ser.decode(ser.encode(x))
    np.testing.assert_allclose(np.asarray(x), out)


def test_alignment():
    data = ser.encode([np.ones(3, np.int8), np.ones(5, np.float64)])
    out = ser.decode(data)
    np.testing.assert_array_equal(out[0], np.ones(3, np.int8))
    np.testing.assert_array_equal(out[1], np.ones(5, np.float64))


def test_rejects_unknown_types():
    class Weird:
        pass

    with pytest.raises(TypeError):
        ser.encode(Weird())


def test_rejects_bad_magic():
    with pytest.raises(ValueError):
        ser.decode(b"XXXX\x01\x00\x00\x00\x00")


def test_struct_registry():
    class Cache:
        def __init__(self, k, v):
            self.k, self.v = k, v

    ser.register_struct(
        "test.Cache",
        Cache,
        lambda c: {"k": c.k, "v": c.v},
        lambda t: Cache(t["k"], t["v"]),
    )
    c = Cache(np.ones((2, 3), np.float32), np.zeros((2, 3), np.float32))
    out = ser.decode(ser.encode({"cache": c}))
    assert isinstance(out["cache"], Cache)
    np.testing.assert_array_equal(out["cache"].k, c.k)


def test_shared_memory_roundtrip():
    obj = {
        "t": np.random.default_rng(1)
        .standard_normal((32, 32))
        .astype(np.float32),
        "tag": "fwd",
    }
    size, name = shm.store(obj)
    out = shm.load(size, name)
    np.testing.assert_array_equal(obj["t"], out["t"])
    assert out["tag"] == "fwd"


def test_file_spill_roundtrip(tmp_path):
    obj = {"big": np.zeros((1024, 256), np.float32)}
    p = tmp_path / "frame.tlts"
    n = ser.encode_to_file(obj, p)
    assert p.stat().st_size == n
    out = ser.decode_from_file(p)
    np.testing.assert_array_equal(out["big"], obj["big"])


# ---------------------------------------------------------------------------
# a request's token ids ride the frame as one array (``__ints__``)
# ---------------------------------------------------------------------------

N_IDS = 13_000


def _generate_body(prompts, **more):
    """The keys ``ml/module.py`` puts on a GENERATE frame."""
    return {
        "job_id": "j" * 8, "prompts": prompts, "max_new_tokens": 64,
        "start_step": 0, "continuous": True, "temperature": 0.5, "top_k": 0,
        "eos_ids": [2, 7], "seed": 9, **more,
    }


def _ids(n=N_IDS, lo=0, hi=100_000):
    return [int(v) for v in np.random.default_rng(5).integers(lo, hi, n)]


def _golden_body():
    return dict(
        _generate_body([[11, 22, 33, 44, 55]]), pair=(1, 2), none=None,
        k=np.arange(6, dtype=np.int32).reshape(2, 3), blob=b"\x00\xff",
    )


# what the parent's ``encode`` gave for ``_golden_body()`` (PR 57's tree)
_GOLDEN_FRAME = bytes.fromhex(
    "544c545301910100007b2274726565223a207b225f5f646963745f5f223a205b5b226a"
    "6f625f6964222c20226a6a6a6a6a6a6a6a225d2c205b2270726f6d707473222c205b5b"
    "31312c2032322c2033332c2034342c2035355d5d5d2c205b226d61785f6e65775f746f"
    "6b656e73222c2036345d2c205b2273746172745f73746570222c20305d2c205b22636f"
    "6e74696e756f7573222c20747275655d2c205b2274656d7065726174757265222c2030"
    "2e355d2c205b22746f705f6b222c20305d2c205b22656f735f696473222c205b322c20"
    "375d5d2c205b2273656564222c20395d2c205b2270616972222c207b225f5f7475706c"
    "655f5f223a205b312c20325d7d5d2c205b226e6f6e65222c206e756c6c5d2c205b226b"
    "222c207b225f5f6172725f5f223a20307d5d2c205b22626c6f62222c207b225f5f6279"
    "7465735f5f223a202230306666227d5d5d7d2c2022617272617973223a205b7b226474"
    "797065223a2022696e743332222c20227368617065223a205b322c20335d2c20226f66"
    "66736574223a20302c20226e6279746573223a2032347d5d7d00000000010000000200"
    "0000030000000400000005000000"
)
_GOLDEN_DIGEST = "f6d22a80a90449767cff87b8bc14d6feec55eead9dde9b09253d367888d263aa"


def _counted(fn):
    """``fn()`` and by how much it moved this process's three counters."""
    before = ser.counters()
    out = fn()
    after = ser.counters()
    return out, {k: after[k] - before[k] for k in after}


def _round_trip(obj, *, packed_lists, packed_ints=None, version):
    """Through ``encode`` / ``decode``: equal element for element and type
    for type, the version byte, and the counters' movement."""
    frame, enc = _counted(lambda: bytes(ser.encode(obj)))
    out, dec = _counted(lambda: ser.decode(frame, copy=True))
    _assert_tree_equal(obj, out)
    assert frame[4] == version
    assert enc == {"tlts_lists_packed": packed_lists,
                   "tlts_ints_packed": packed_ints or 0,
                   "tlts_lists_unpacked": 0}
    assert dec == {"tlts_lists_packed": 0, "tlts_ints_packed": 0,
                   "tlts_lists_unpacked": packed_lists}
    return frame, out


def _case_long_prompt(fx):
    ids = _ids()
    frame, out = _round_trip(_generate_body([ids]), packed_lists=1,
                             packed_ints=N_IDS, version=ser.VERSION_PACKED)
    row = out["prompts"][0]
    assert row == ids and type(row) is list
    assert set(map(type, row)) == {int}
    assert len(frame) < 5 * N_IDS  # four bytes an id, not a decimal number


def _case_under_the_length(fx):
    ids = _ids(ser.PACK_MIN_INTS - 1)
    _round_trip({"tokens": ids, "stream": "s"}, packed_lists=0,
                version=ser.VERSION)
    _round_trip({"tokens": ids + [1], "stream": "s"}, packed_lists=1,
                packed_ints=ser.PACK_MIN_INTS, version=ser.VERSION_PACKED)


def _case_bools(fx):
    _round_trip({"mask": [True, False] * 200}, packed_lists=0,
                version=ser.VERSION)
    _round_trip({"mask": _ids(200) + [True]}, packed_lists=0,
                version=ser.VERSION)


def _case_mixed_with_float_none_numpy_scalar(fx):
    for odd in (1.5, None, "7"):
        _round_trip({"row": _ids(200) + [odd]}, packed_lists=0,
                    version=ser.VERSION)
    # a NumPy scalar comes back the 0-d array it always came back as
    frame = ser.encode({"row": _ids(200) + [np.int32(3)]})
    assert frame[4] == ser.VERSION
    out = ser.decode(frame)["row"]
    assert isinstance(out[-1], np.ndarray) and out[-1] == 3
    assert out[:-1] == _ids(200)


def _case_value_over_int64(fx):
    for big in (2**63, -(2**63) - 1, 2**80):
        _round_trip({"row": _ids(200) + [big]}, packed_lists=0,
                    version=ser.VERSION)


def _case_values_over_int32_ride_int64(fx):
    for edge, dtype in ((2**31 - 1, "int32"), (-(2**31), "int32"),
                        (2**31, "int64"), (-(2**31) - 1, "int64"),
                        (2**63 - 1, "int64"), (-(2**63), "int64")):
        row = _ids(200) + [edge]
        frame, out = _round_trip({"row": row}, packed_lists=1,
                                 packed_ints=201, version=ser.VERSION_PACKED)
        assert f'"dtype": "{dtype}"'.encode() in frame
        assert out["row"][-1] == edge and type(out["row"][-1]) is int


def _case_two_rows(fx):
    rows = [_ids(300), _ids(500, hi=50)]
    _round_trip(_generate_body(rows), packed_lists=2, packed_ints=800,
                version=ser.VERSION_PACKED)
    # a short row beside a long one keeps the element path, alone
    _round_trip(_generate_body([_ids(300), [1, 2, 3]]), packed_lists=1,
                packed_ints=300, version=ser.VERSION_PACKED)


def _case_empty_list(fx):
    _round_trip(_generate_body([[]], eos_ids=[]), packed_lists=0,
                version=ser.VERSION)


def _case_tuple_of_ints(fx):
    _round_trip({"shape": tuple(_ids(400)), "key": {(1, 2): "v"}},
                packed_lists=0, version=ser.VERSION)


def _case_inside_a_struct_and_a_tuple(fx):
    class Blob:
        def __init__(self, ids):
            self.ids = ids

    ser.register_struct("test.Blob", Blob, lambda b: {"ids": b.ids},
                        lambda t: Blob(t["ids"]))
    ids = _ids(300)
    frame = ser.encode({"b": Blob(ids), "t": (ids, "x")})
    assert frame[4] == ser.VERSION_PACKED
    out = ser.decode(frame)
    assert out["b"].ids == ids and type(out["b"].ids) is list
    assert out["t"] == (ids, "x") and type(out["t"][0]) is list


def _case_golden_frame_without_a_long_list(fx):
    frame, moved = _counted(lambda: bytes(ser.encode(_golden_body())))
    assert frame == _GOLDEN_FRAME and frame[4] == 1
    assert not any(moved.values())
    _assert_tree_equal(ser.decode(_GOLDEN_FRAME), _golden_body())


def _case_array_only_digest_unchanged(fx):
    arr = {"k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
           "v": np.ones((3, 5), np.int8)}
    assert ser.content_digest(arr) == _GOLDEN_DIGEST


def _case_old_decoder_refuses_by_version(fx):
    frame = ser.encode(_generate_body([_ids()]))
    plain = ser.encode(_generate_body([_ids(8)]))
    fx.monkeypatch.setattr(ser, "VERSIONS_READ", (ser.VERSION,))
    with pytest.raises(ValueError, match="unsupported TLTS version 2"):
        ser.decode(frame)
    assert ser.decode(plain)["prompts"] == [_ids(8)]


def _case_lists_do_not_alias_the_buffer(fx):
    ids = _ids()
    buf = bytearray(ser.encode(_generate_body([ids])))
    views = ser.decode(buf)  # copy=False: arrays view, a list never does
    copied = ser.decode(buf, copy=True)
    path = fx.tmp_path / "frame.tlts"
    ser.encode_to_file(_generate_body([ids]), path)
    filed = ser.decode_from_file(path)
    buf[:] = bytes(len(buf))
    for out in (views, copied, filed):
        assert out["prompts"] == [ids]
        assert type(out["prompts"][0]) is list


def _case_a_marker_that_names_no_int_vector_is_malformed(fx):
    import json

    frame = bytes(ser.encode({"x": np.ones((2, 2), np.float32)}))
    hlen = int.from_bytes(frame[5:9], "little")
    header = json.loads(frame[9 : 9 + hlen])
    header["tree"] = {"__ints__": 0}
    h = json.dumps(header).encode()
    forged = (frame[:4] + bytes([ser.VERSION_PACKED])
              + len(h).to_bytes(4, "little") + h + frame[9 + hlen :])
    with pytest.raises(ValueError, match="malformed node"):
        ser.decode(forged)


_INT_LIST_CASES = (
    _case_long_prompt, _case_under_the_length, _case_bools,
    _case_mixed_with_float_none_numpy_scalar, _case_value_over_int64,
    _case_values_over_int32_ride_int64, _case_two_rows, _case_empty_list,
    _case_tuple_of_ints, _case_inside_a_struct_and_a_tuple,
    _case_golden_frame_without_a_long_list, _case_array_only_digest_unchanged,
    _case_old_decoder_refuses_by_version, _case_lists_do_not_alias_the_buffer,
    _case_a_marker_that_names_no_int_vector_is_malformed,
)


@pytest.mark.parametrize(
    "case", _INT_LIST_CASES,
    ids=[c.__name__.removeprefix("_case_") for c in _INT_LIST_CASES])
def test_int_lists_on_the_wire(case, monkeypatch, tmp_path):
    """The codec's contract for lists of ints: list in, list out, equal
    element for element and type for type, whichever path a list takes."""
    import types

    case(types.SimpleNamespace(monkeypatch=monkeypatch, tmp_path=tmp_path))


def test_the_counters_add_up_under_threads():
    """Pool threads and the engine's thread frame at once: no count lost."""
    import sys
    import threading

    body = _generate_body([_ids(256)])
    frame = bytes(ser.encode(body))
    before = ser.counters()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(200):
                ser.encode(body)
                ser.decode(frame)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    after = ser.counters()
    assert after["tlts_lists_packed"] - before["tlts_lists_packed"] == 1600
    assert after["tlts_ints_packed"] - before["tlts_ints_packed"] == 1600 * 256
    assert after["tlts_lists_unpacked"] - before["tlts_lists_unpacked"] == 1600
