"""Operations, bytes, roofline and MFU arithmetic at the configs' shapes."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import pytest

from harness import costs, loader

V5E = "TPU v5 lite"


def shapes(name):
    return costs.layer_shapes(loader.config(name))


def test_layer_shapes():
    assert shapes("mlp-gsc-int8") == [(512, 512), (512, 512), (512, 256),
                                      (256, 256), (256, 128), (128, 128),
                                      (128, 12)]
    assert shapes("lenet-300-100-fp32") == [(784, 300), (300, 100),
                                            (100, 10)]


def test_ops_per_row():
    assert costs.ops(shapes("mlp-gsc-int8"), 1) == 1_543_168
    assert costs.ops(shapes("lenet-300-100-fp32"), 1) == 532_400
    assert costs.ops(shapes("lenet-300-100-fp32"), 65536) == 532_400 * 65536


def test_bytes_of_an_offline_call():
    s = shapes("lenet-300-100-fp32")
    weights = (784 * 300 + 300 * 100 + 100 * 10) / 2
    epilogue = 8 * (300 + 100 + 10) + 3 * 20
    assert costs.bytes_moved(s, 65536) == \
        65536 * 784 * 4 + 65536 * 10 * 4 + weights + epilogue


def test_peaks_table():
    p = costs.peaks(V5E)
    assert p["bfloat16_ops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert costs.peak_ops(V5E, "int8") == 393e12
    assert costs.peak_ops(V5E, "float32") == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9000")
    with pytest.raises(KeyError):
        costs.mfu(1.0, shapes("mlp-gsc-int8"), 1, "cpu", "int8")


def test_roofline_bounds():
    s = shapes("lenet-300-100-fp32")
    n_ops, n_bytes = costs.ops(s, 65536), costs.bytes_moved(s, 65536)
    t_mem = n_bytes / 819e9
    share, bound = costs.roofline(n_ops, n_bytes, 2 * t_mem, V5E, "float32")
    assert bound == "hbm" and share == pytest.approx(50.0)
    g = shapes("mlp-gsc-int8")
    n_ops, n_bytes = costs.ops(g, 65536), costs.bytes_moved(g, 65536)
    t_ops = n_ops / 393e12
    share, bound = costs.roofline(n_ops, n_bytes, 4 * t_ops, V5E, "int8")
    assert bound == "compute" and share == pytest.approx(25.0)


def test_mfu():
    g = shapes("mlp-gsc-int8")
    rows = 393e12 / 1_543_168
    assert costs.mfu(rows, g, 1, V5E, "int8") == pytest.approx(100.0)
    assert costs.mfu(rows, g, 4, V5E, "int8") == pytest.approx(25.0)
