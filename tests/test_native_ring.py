"""Native shm message ring (C++ tlring) — build, round-trips, cross-process
transport, oversize spill, close semantics. Skipped wholesale if the
toolchain can't build the library (fallback mode is mp.Queue and is covered
by every other e2e test)."""

import multiprocessing as mp
import queue
import time

import numpy as np
import pytest

from tensorlink_tpu.core.ring import RingChannel, ring_supported

pytestmark = pytest.mark.skipif(
    not ring_supported(), reason="native tlring not buildable here"
)


def test_roundtrip_objects():
    ch = RingChannel(1 << 20)
    try:
        items = [
            ("work", {"a": 1, "b": [1.5, None, True]}),
            ("fwd", {"tokens": np.arange(12, dtype=np.int32).reshape(3, 4)}),
            (7, "verb", {"x": b"\x00\xffbytes"}),
        ]
        for it in items:
            ch.put(it)
        got0 = ch.get(timeout=5)
        assert tuple(got0)[0] == "work" and got0[1]["b"][0] == 1.5
        got1 = ch.get(timeout=5)
        np.testing.assert_array_equal(
            got1[1]["tokens"], np.arange(12, dtype=np.int32).reshape(3, 4)
        )
        got2 = ch.get(timeout=5)
        assert got2[2]["x"] == b"\x00\xffbytes"
    finally:
        ch.release()


def test_get_timeout_raises_empty():
    ch = RingChannel(1 << 16)
    try:
        t0 = time.monotonic()
        with pytest.raises(queue.Empty):
            ch.get(timeout=0.2)
        assert 0.1 < time.monotonic() - t0 < 2.0
    finally:
        ch.release()


def test_oversize_spills_to_file():
    ch = RingChannel(1 << 16)  # 64 KB ring
    try:
        big = np.random.default_rng(0).standard_normal((64, 1024))  # 512 KB
        ch.put({"big": big})
        got = ch.get(timeout=5)
        np.testing.assert_array_equal(got["big"], big)
    finally:
        ch.release()


@pytest.mark.parametrize("capacity", [1 << 20, 1 << 16],
                         ids=["in-the-ring", "spilled-to-a-file"])
def test_a_prompt_of_13k_ids_crosses_as_one_array(capacity):
    """A GENERATE body as the bridges carry it (``(rid, verb, payload)``):
    the ids come back the list of ints they were, framed as one array."""
    from tensorlink_tpu.core import serialization as ser

    ids = [int(v) for v in np.random.default_rng(3).integers(0, 150_000, 13_000)]
    body = {"job_id": "j", "prompts": [ids], "max_new_tokens": 64,
            "eos_ids": [2], "seed": 1, "continuous": True}
    ch = RingChannel(capacity)
    try:
        before = ser.counters()
        ch.put((7, "generate", body))
        rid, verb, got = ch.get(timeout=5)
        after = ser.counters()
    finally:
        ch.release()
    assert (rid, verb) == (7, "generate")
    assert got == body and type(got["prompts"][0]) is list
    assert set(map(type, got["prompts"][0])) == {int}
    assert got["eos_ids"] == [2]
    moved = {k: after[k] - before[k] for k in after}
    assert moved == {"tlts_lists_packed": 1, "tlts_ints_packed": 13_000,
                     "tlts_lists_unpacked": 1}


def test_close_unblocks_reader():
    ch = RingChannel(1 << 16)
    try:
        import threading

        err = {}

        def reader():
            try:
                ch.get(timeout=30)
            except EOFError:
                err["eof"] = True
            except Exception as e:  # pragma: no cover
                err["other"] = e

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.2)
        ch.close()
        t.join(timeout=5)
        assert err.get("eof"), err
    finally:
        ch.release()


def _child(req, resp, n):
    for i in range(n):
        item = req.get(timeout=30)
        resp.put({"i": i, "sum": float(item["arr"].sum())})


def test_cross_process_transport():
    ctx = mp.get_context("spawn")
    req = RingChannel(4 << 20)
    resp = RingChannel(1 << 20)
    try:
        n = 5
        proc = ctx.Process(target=_child, args=(req, resp, n), daemon=True)
        proc.start()
        rng = np.random.default_rng(1)
        sums = []
        for i in range(n):
            arr = rng.standard_normal((128, 128)).astype(np.float32)
            sums.append(float(arr.sum()))
            req.put({"arr": arr})
        for i in range(n):
            out = resp.get(timeout=30)
            assert out["i"] == i
            assert out["sum"] == pytest.approx(sums[i], rel=1e-6)
        proc.join(timeout=10)
        assert proc.exitcode == 0
    finally:
        req.release()
        resp.release()


def test_wrap_around_many_messages():
    ch = RingChannel(1 << 16)
    try:
        payload = np.arange(1000, dtype=np.float32)  # 4 KB per message
        for round_ in range(50):  # >> capacity in total traffic
            ch.put({"r": round_, "p": payload})
            got = ch.get(timeout=5)
            assert got["r"] == round_
            np.testing.assert_array_equal(got["p"], payload)
    finally:
        ch.release()


def test_sweep_orphans_reaps_dead_creators(tmp_path):
    """A SIGKILLed owner can't unlink its shm segment; creating a new ring
    reaps segments whose embedded creator pid is gone — and never touches a
    live creator's segment."""
    import os
    from pathlib import Path

    from tensorlink_tpu.core.ring import RingChannel, ring_supported, sweep_orphans

    if not ring_supported():
        import pytest

        pytest.skip("native ring unavailable")
    shm = Path("/dev/shm")
    # fabricate an orphan: a segment named for a pid that cannot exist
    orphan = shm / "tlring-999999999-deadbeef0000"
    orphan.write_bytes(b"\x00" * 64)
    live = RingChannel(1 << 16)  # triggers a sweep on creation
    try:
        assert not orphan.exists()
        # the live ring's own segment survived its creation-time sweep
        assert (shm / live.name.lstrip("/")).exists()
        sweep_orphans()  # explicit call with a live creator: still safe
        assert (shm / live.name.lstrip("/")).exists()
    finally:
        live.release()
    assert not (shm / live.name.lstrip("/")).exists()  # owner unlinked
