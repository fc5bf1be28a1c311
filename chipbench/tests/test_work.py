"""Work functions and peaks against hand-computed counts."""
import pytest

from chipbench.work import bmm_quant_call, least_time_s, load_peaks


def test_bmm_quant_call_counts():
    # B=64 queries x one 1024-lane tile of D=1536 int8 values
    flops, nbytes = bmm_quant_call(64, 1024, 1536, 1)
    assert flops == 2 * 64 * 1024 * 1536 == 201_326_592
    assert nbytes == 1024 * 1536 + 4 * 64 * 1536 + 4 * 64 * 1024 == 2_228_224
    flops, nbytes = bmm_quant_call(1, 128, 96, 0.5)
    assert (flops, nbytes) == (24_576, 6144 + 384 + 512)


def test_least_time_on_v5e():
    peaks = load_peaks("TPU v5 lite")
    t, bound = least_time_s(*bmm_quant_call(64, 1024, 1536, 1), peaks)
    assert bound == "memory"
    assert t == pytest.approx(2_228_224 / 819e9)
    t, bound = least_time_s(197e12, 1.0, peaks)
    assert (t, bound) == (1.0, "compute")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        load_peaks("TPU v99")
