"""The operation and byte arithmetic against counts worked by hand for
AlexNet3D_Dropout on the 121x145x121 volume."""
import pytest

from benchmarks.lib import flops, peaks
from benchmarks.reference import alexnet3d, resnet3d

VOLUME = (121, 145, 121)
# conv: 2 * out positions * taps * C_in * C_out
STEM = 2 * (59 * 71 * 59) * 125 * 1 * 64          # 3,954,416,000
CONV2 = 2 * (17 * 21 * 17) * 27 * 64 * 128        # 2,684,547,072
CONV3 = 2 * (5 * 7 * 5) * 27 * 128 * 192
CONV4 = 2 * (5 * 7 * 5) * 27 * 192 * 192
CONV5 = 2 * (5 * 7 * 5) * 27 * 192 * 128
DENSE = 2 * 256 * 64 + 2 * 64


def test_alexnet3d_shapes_and_forward_counts():
    rows = {r["name"]: r for r in alexnet3d.layers(VOLUME)}
    assert rows["conv1"]["out"] == (59, 71, 59, 64)
    assert rows["pool1"]["out"] == (19, 23, 19, 64)
    assert rows["conv2"]["out"] == (17, 21, 17, 128)
    assert rows["pool5"]["out"] == (1, 2, 1, 128)
    assert rows["dense1"]["in"] == (256,)
    assert flops.forward_flops(rows["conv1"]) == STEM == 3_954_416_000
    assert flops.forward_flops(rows["conv2"]) == CONV2
    assert flops.forward_flops(rows["norm1"]) == 0
    assert sum(flops.forward_flops(r) for r in rows.values()) == (
        STEM + CONV2 + CONV3 + CONV4 + CONV5 + DENSE)


def test_training_counts_forward_and_backward_once_each():
    rows = alexnet3d.layers(VOLUME)
    # the stem's input is data: forward + weight gradient; every other
    # layer also computes its input gradient
    want = 2 * STEM + 3 * (CONV2 + CONV3 + CONV4 + CONV5 + DENSE)
    assert flops.train_flops_per_sample(rows) == want
    assert want == pytest.approx(18.4e9, rel=0.01)


def test_bytes_and_floor():
    rows = {r["name"]: r for r in alexnet3d.layers(VOLUME)}
    stem_out = 59 * 71 * 59 * 64
    assert flops.forward_bytes(rows["conv1"], 2) == 2 * (
        121 * 145 * 121 + 125 * 64 + stem_out)
    assert flops.backward_bytes(rows["norm1"], 2) == 2 * 3 * stem_out
    assert flops.backward_bytes(rows["conv1"], 2) == 2 * (
        stem_out + 121 * 145 * 121 + 125 * 64)
    v5e = peaks.peaks_for("TPU v5 lite")
    floor, table = flops.step_floor(list(rows.values()), 16, 2, v5e)
    bound = {(r["layer"], r["pass"]): r["bound"] for r in table}
    assert bound["conv1", "forward"] == "memory"      # C_in = 1
    assert bound["conv2", "forward"] == "compute"
    assert floor == pytest.approx(sum(r["floor_s"] for r in table))
    t = 16 * flops.forward_bytes(rows["conv1"], 2) / 819e9
    assert table[0]["floor_s"] == pytest.approx(t)


def test_resnet_l3_shapes():
    rows = {r["name"]: r for r in resnet3d.layers(VOLUME)}
    assert rows["stem"]["out"] == (63, 75, 63, 64)
    assert rows["stem_pool"]["out"] == (32, 38, 32, 64)
    assert rows["block3.conv1"]["out"] == (16, 19, 16, 128)
    assert rows["block3.skip"]["taps"] == 1
    assert rows["block6.conv2"]["out"] == (8, 10, 8, 256)
    assert rows["dense1"]["in"] == (2 * 3 * 2 * 256,)
    assert "block1.skip" not in rows


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9")
