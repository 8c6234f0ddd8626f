"""Operations and bytes from shapes, against counts made by hand."""
import pytest

import work


def test_extend_bytes_and_bound():
    assert work.extend_bytes(2048, 128) == 2048 * 512
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_seconds(work.extend_flops(2048, 128),
                                  work.extend_bytes(2048, 128), peak)
    assert bound == "bytes"
    assert t == pytest.approx(2048 * 512 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9000")
