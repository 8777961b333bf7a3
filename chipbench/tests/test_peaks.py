import pytest

from chipbench import peaks


def test_known_device():
    p = peaks.for_kind("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind(kind)
