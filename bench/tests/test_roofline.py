"""The roofline byte count, against bytes counted by hand."""

import pytest

from bench import roofline


def test_bprr_bytes_by_hand():
    # 30,000 objects x 50 nodes x 64 int32 slots = 384,000,000 B a plane.
    # Reads: delta, state, 5 buffers; writes: state, 5 buffers = 13 planes.
    assert roofline.round_bytes("bprr", 30000, 50, 4, 64) == 13 * 384_000_000


def test_classic_bytes_by_hand():
    # One buffer: reads delta, state, buffer; writes state, buffer.
    assert roofline.round_bytes("classic", 30000, 50, 4, 64) == \
        5 * 384_000_000


def test_small_store_by_hand():
    # 2 objects x 3 nodes x 4 slots x 4 B = 96 B a plane; degree 2 -> 3
    # bprr buffers: 2 + 3 reads and 1 + 3 writes = 9 planes.
    assert roofline.round_bytes("bprr", 2, 3, 2, 4) == 9 * 96


def test_unknown_algorithm_raises():
    with pytest.raises(ValueError):
        roofline.round_bytes("state", 1, 1, 2, 1)
