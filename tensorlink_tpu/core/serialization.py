"""Pickle-free structured array serialization.

Re-specification of the reference's safe tensor wire format
(ml/utils.py:569-660: JSON structure skeleton + safetensors blob, handling
Tensor/dict/list/tuple/DynamicCache/ModelOutput with *no pickle*), designed
for JAX arrays and a single contiguous frame:

    MAGIC "TLTS" | version u8 | header_len u32le | header JSON | payload

The header carries the container tree with ``{"__arr__": i}`` placeholders and
an array table (dtype, shape, offset, nbytes). The payload is the raw
little-endian array bytes, 64-byte aligned so a receiver can map them
zero-copy into jax/numpy. bfloat16 and fp8 ride on ``ml_dtypes``.

A ``list`` of :data:`PACK_MIN_INTS` or more plain Python ``int``s (a request's
token ids) rides the payload as ONE ``int32`` array (``int64`` when a value
needs it) under ``{"__ints__": i}`` and comes back a ``list`` of ``int``s:
list in, list out, never walked element by element in Python. A frame that
holds such a list says version 2 in its version byte; every other frame is
byte for byte the version-1 frame it always was, and ``decode`` reads both.

Custom structured objects (KV caches, model outputs) register with
:func:`register_struct` — symmetric named encode/decode, never code execution.
"""

from __future__ import annotations

import json
import threading
from typing import Any, Callable

import numpy as np

try:  # ml_dtypes ships with jax; gives numpy bfloat16/fp8 dtypes
    import ml_dtypes

    _EXTRA_DTYPES = {
        "bfloat16": np.dtype(ml_dtypes.bfloat16),
        "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
        "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
    }
except ImportError:  # pragma: no cover
    _EXTRA_DTYPES = {}

MAGIC = b"TLTS"
VERSION = 1
# a frame that holds a packed int list (``__ints__``): a peer that predates
# the marker refuses it by its version byte, not as a malformed node
VERSION_PACKED = 2
VERSIONS_READ = (VERSION, VERSION_PACKED)
_ALIGN = 64
# Shortest list the array path takes. Under it the element path is as fast
# or faster (the array path's fixed cost: the type test, one conversion,
# a table entry, an aligned copy; measured crossover in docs/SERVING.md,
# "Wire"), and a ``TOKEN`` frame's 1-8 ids or a request's ``eos_ids`` keep
# the frame they had. A constant of the codec: no config field reads it.
PACK_MIN_INTS = 32
_INT32 = np.iinfo(np.int32)
_INT_DTYPES = (np.dtype(np.int32), np.dtype(np.int64))

# this process's packed lists: {tlts_lists_packed, tlts_ints_packed} count
# on the encode side, tlts_lists_unpacked on the decode side (read through
# :func:`counters`; API pool threads and the engine's thread both frame)
# tlint: disable=TL006(process counters — every write under _COUNTS_LOCK)
_COUNTS = {"tlts_lists_packed": 0, "tlts_ints_packed": 0,
           "tlts_lists_unpacked": 0}  #: guarded by _COUNTS_LOCK
_COUNTS_LOCK = threading.Lock()


def counters() -> dict[str, int]:
    """This process's count of int lists framed as one array
    (``tlts_lists_packed``, ``tlts_ints_packed`` their elements) and read
    back (``tlts_lists_unpacked``)."""
    with _COUNTS_LOCK:
        return dict(_COUNTS)


def _pack_ints(x: list) -> np.ndarray | None:
    """``x`` as one int32 / int64 array when every element is a plain
    ``int`` (no ``bool``, no NumPy scalar) that int64 holds; else None and
    the caller walks it element by element as before."""
    if set(map(type, x)) != {int}:  # at C speed: no Python-level loop
        return None
    try:
        a = np.fromiter(x, np.int64, len(x))
    except OverflowError:  # a value beyond int64 keeps the element path
        return None
    if _INT32.min <= int(a.min()) and int(a.max()) <= _INT32.max:
        a = a.astype(np.int32)
    return a

# name -> (to_tree, from_tree); to_tree returns a JSON-able tree possibly
# containing arrays, from_tree reconstructs the object.
# tlint: disable=TL006(codec registry — populated once at import by register_struct, read-only after)
_STRUCTS: dict[str, tuple[Callable[[Any], Any], Callable[[Any], Any]]] = {}
# tlint: disable=TL006(codec registry — populated once at import by register_struct, read-only after)
_STRUCT_TYPES: dict[type, str] = {}


def register_struct(name: str, cls: type, to_tree, from_tree) -> None:
    _STRUCTS[name] = (to_tree, from_tree)
    _STRUCT_TYPES[cls] = name


def _dtype_name(dt: np.dtype) -> str:
    for name, d in _EXTRA_DTYPES.items():
        if dt == d:
            return name
    return dt.name


def _dtype_from_name(name: str) -> np.dtype:
    if name in _EXTRA_DTYPES:
        return _EXTRA_DTYPES[name]
    return np.dtype(name)


def _is_array(x: Any) -> bool:
    if isinstance(x, np.ndarray):
        return True
    # jax.Array without importing jax at module load (network proc must not
    # import jax — same reason the reference keeps torch out of its network
    # process, SURVEY §1).
    return type(x).__module__.startswith("jax") and hasattr(x, "__array__")


def encode(obj: Any) -> memoryview:
    """Serialize a nested container of arrays/scalars into one frame —
    returned as a bytes-compatible ``memoryview`` built in place with ONE
    copy per array (``bytes(encode(x))`` where a true ``bytes`` is
    required, e.g. ctypes ``c_char_p``)."""
    arrays: list[np.ndarray] = []
    table: list[dict[str, Any]] = []
    packed: list[int] = []  # the length of each list framed as an array

    def add_array(a: np.ndarray) -> int:
        arrays.append(a)
        table.append({"dtype": _dtype_name(a.dtype), "shape": list(a.shape)})
        return len(arrays) - 1

    def walk(x: Any) -> Any:
        if _is_array(x):
            a = np.asarray(x)
            if not a.flags.c_contiguous:
                a = np.ascontiguousarray(a)
            return {"__arr__": add_array(a)}
        if isinstance(x, (np.generic,)):
            return walk(np.asarray(x))
        if isinstance(x, bytes):
            return {"__bytes__": x.hex()}
        if isinstance(x, dict):
            return {"__dict__": [[walk(k), walk(v)] for k, v in x.items()]}
        if isinstance(x, tuple):
            return {"__tuple__": [walk(v) for v in x]}
        if isinstance(x, list):
            if len(x) >= PACK_MIN_INTS:
                a = _pack_ints(x)
                if a is not None:
                    packed.append(len(x))
                    return {"__ints__": add_array(a)}
            return [walk(v) for v in x]
        if x is None or isinstance(x, (bool, int, str)):
            return x
        if isinstance(x, float):
            return x
        name = _STRUCT_TYPES.get(type(x))
        if name is not None:
            return {"__struct__": name, "tree": walk(_STRUCTS[name][0](x))}
        raise TypeError(
            f"cannot serialize {type(x).__name__} without register_struct()"
        )

    tree = walk(obj)
    offset = 0
    for a, meta in zip(arrays, table):
        offset = (offset + _ALIGN - 1) // _ALIGN * _ALIGN
        meta["offset"] = offset
        meta["nbytes"] = a.nbytes
        offset += a.nbytes

    header = json.dumps({"tree": tree, "arrays": table}).encode()
    # single-copy, single-touch assembly: np.empty (no zero-fill — a
    # bytearray would pay a full memory write just being created) and
    # np.copyto each array straight into place; only the alignment gaps are
    # explicitly zeroed so no uninitialized heap bytes ever leave the
    # process. tobytes()+join paid TWO full copies per array — a 256 MB
    # activation framed in ~700 ms on this box vs ~60 ms here. Returns the
    # buffer's memoryview (bytes-compatible for socket/file/shm writes).
    prefix = 9 + len(header)
    buf = np.empty(prefix + offset, np.uint8)
    mv = memoryview(buf)
    mv[0:4] = MAGIC
    mv[4] = VERSION_PACKED if packed else VERSION
    mv[5:9] = len(header).to_bytes(4, "little")
    mv[9:prefix] = header
    pos = 0
    for a, meta in zip(arrays, table):
        if meta["offset"] != pos:  # zero the alignment gap
            buf[prefix + pos : prefix + meta["offset"]] = 0
        n = meta["nbytes"]
        if n:
            np.copyto(
                buf[prefix + meta["offset"] : prefix + meta["offset"] + n],
                a.reshape(-1).view(np.uint8),
            )
        pos = meta["offset"] + n
    if packed:
        with _COUNTS_LOCK:
            _COUNTS["tlts_lists_packed"] += len(packed)
            _COUNTS["tlts_ints_packed"] += sum(packed)
    return mv


def decode(data: bytes | memoryview, *, copy: bool = False) -> Any:
    """Inverse of :func:`encode`. Arrays come back as numpy views over the
    input buffer (zero-copy) unless ``copy=True``."""
    mv = memoryview(data)
    if len(mv) < 9:
        raise ValueError(f"truncated TLTS frame: {len(mv)} bytes")
    if bytes(mv[:4]) != MAGIC:
        raise ValueError("bad magic: not a TLTS frame")
    if mv[4] not in VERSIONS_READ:
        raise ValueError(f"unsupported TLTS version {mv[4]}")
    hlen = int.from_bytes(mv[5:9], "little")
    if 9 + hlen > len(mv):
        raise ValueError("truncated TLTS frame: header exceeds buffer")
    header = json.loads(bytes(mv[9 : 9 + hlen]).decode())
    payload = mv[9 + hlen :]

    unpacked = 0

    def view(i: int) -> np.ndarray:
        meta = header["arrays"][i]
        dt = _dtype_from_name(meta["dtype"])
        if meta["offset"] + meta["nbytes"] > len(payload):
            raise ValueError(
                f"truncated TLTS frame: array {i} needs bytes up to "
                f"{meta['offset'] + meta['nbytes']}, payload has {len(payload)}"
            )
        raw = payload[meta["offset"] : meta["offset"] + meta["nbytes"]]
        return np.frombuffer(raw, dtype=dt).reshape(meta["shape"])

    def walk(x: Any) -> Any:
        nonlocal unpacked
        if isinstance(x, dict):
            if "__arr__" in x:
                a = view(x["__arr__"])
                return a.copy() if copy else a
            if "__ints__" in x:
                # fresh Python ints: nothing of the list aliases the buffer
                a = view(x["__ints__"])
                if a.dtype not in _INT_DTYPES or a.ndim != 1:
                    raise ValueError(
                        f"malformed node: __ints__ of {a.dtype}{list(a.shape)}")
                unpacked += 1
                return a.tolist()
            if "__bytes__" in x:
                return bytes.fromhex(x["__bytes__"])
            if "__dict__" in x:
                return {walk(k): walk(v) for k, v in x["__dict__"]}
            if "__tuple__" in x:
                return tuple(walk(v) for v in x["__tuple__"])
            if "__struct__" in x:
                name = x["__struct__"]
                if name not in _STRUCTS:
                    raise ValueError(f"unknown struct {name!r}")
                return _STRUCTS[name][1](walk(x["tree"]))
            raise ValueError(f"malformed node: {list(x)[:3]}")
        if isinstance(x, list):
            return [walk(v) for v in x]
        return x

    out = walk(header["tree"])
    if unpacked:
        with _COUNTS_LOCK:
            _COUNTS["tlts_lists_unpacked"] += unpacked
    return out


def content_digest(obj: Any) -> str:
    """Stable sha256 over an object's TLTS encoding — an integrity tag for
    payloads that cross the wire AND a process boundary (migration blobs:
    the importer recomputes the digest before adopting KV bytes, so a
    corrupted or reordered-and-reassembled transfer fails loudly into the
    re-prefill fallback instead of decoding from garbage pages)."""
    import hashlib

    return hashlib.sha256(bytes(encode(obj))).hexdigest()


def encode_to_file(obj: Any, path) -> int:
    """Spill large frames to disk (reference connection.py:110-128 spills
    >20 MB buffers to tmp files). Returns bytes written."""
    data = encode(obj)
    with open(path, "wb") as f:
        f.write(data)
    return len(data)


def decode_from_file(path) -> Any:
    with open(path, "rb") as f:
        return decode(f.read(), copy=True)
