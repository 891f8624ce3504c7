"""The ring slot header's publish stamp (experimental/channel.py): the
writer's flight-recorder clock at ``_publish``, left on the reading
channel as ``last_publish_mono`` — what ``dag.stream_ingress`` is
measured from. The slot's geometry must not move for it."""

from __future__ import annotations

import os
import time
import uuid

import pytest

from ray_tpu.experimental import channel as chan
from ray_tpu.util import flight_recorder as fr


@pytest.fixture()
def ring():
    path = chan.channel_path(f"stamp_{uuid.uuid4().hex[:8]}")
    w = chan.ShmChannel(path, capacity=256, create=True, n_slots=4)
    r = chan.ShmChannel(path)
    saved = fr._on[0]
    yield w, r
    fr._on[0] = saved
    r.close()
    w.close(unlink=True)


@pytest.mark.parametrize("tag", [chan.TAG_DATA, chan.TAG_BYTES,
                                 chan.TAG_STREAM])
def test_stamp_round_trip(ring, tag):
    """Every message carries its own publish time, on the clock the
    reader shares, and tag and payload come through beside it."""
    w, r = ring
    fr.configure(enabled=True)
    before = time.monotonic()
    w.write(b"first", tag=tag)
    between = time.monotonic()
    time.sleep(0.002)
    w.write(b"second", tag=tag)
    after = time.monotonic()
    assert r.read(timeout=5) == (tag, b"first")
    # whole microseconds: the stamp may round down by up to 1 us
    assert before - 1e-6 <= r.last_publish_mono <= between
    assert r.read(timeout=5) == (tag, b"second")
    assert between <= r.last_publish_mono <= after


def test_stamp_zero_when_recorder_off(ring):
    w, r = ring
    fr.configure(enabled=False)
    w.write(b"x")
    fr.configure(enabled=True)
    w.write(b"y")
    assert r.read(timeout=5) == (chan.TAG_DATA, b"x")
    assert r.last_publish_mono == 0.0
    assert r.read(timeout=5) == (chan.TAG_DATA, b"y")
    assert r.last_publish_mono > 0.0


def test_stamp_leaves_the_slot_layout_alone(ring):
    """24-byte slot header, tag still the byte at offset 16: a stamp of
    56 set bits must not reach it."""
    w, _ = ring
    assert chan._SHDR.size == 24
    assert os.path.getsize(w.path) == chan._HDR_SIZE + 4 * (24 + 256)
    buf = bytearray(24)
    chan._SHDR.pack_into(buf, 0, 1, 5, chan.TAG_STREAM | (2 ** 56 - 1) << 8)
    assert buf[16] == chan.TAG_STREAM and bytes(buf[17:]) == b"\xff" * 7
