"""Operation and byte counts against hand counts at small sizes."""
import pytest

from _tiny import ROOT  # noqa: F401  (puts the repo on sys.path)
from nlzbench import device, flops


def test_dnn_forward_flops_hand_count():
    # 16x16 sample, 2 channels in, widths (4, 4, 6, 6, 8), skip connections.
    macs = (9 * 2 * 4 * 16 * 16      # conv_in at 16x16
            + 9 * 4 * 4 * 8 * 8      # down1 -> 8x8
            + 9 * 4 * 6 * 4 * 4      # down2 -> 4x4
            + 9 * 6 * 6 * 2 * 2      # down3 -> 2x2
            + 9 * 6 * 8 * 1 * 1      # down4 -> 1x1
            + 9 * 8 * 6 * 1 * 1      # up1 from 1x1
            + 9 * 12 * 6 * 2 * 2     # up2 from 2x2 (6 up + 6 skip in)
            + 9 * 12 * 4 * 4 * 4     # up3 from 4x4
            + 9 * 8 * 4 * 8 * 8      # up4 from 8x8
            + 9 * 8 * 1 * 16 * 16)   # conv_out (4 up + 4 skip in)
    assert macs == 79632
    assert flops.dnn_forward_flops(16, 16, 2) == 2 * macs
    # Planes are padded to multiples of 16 before the network runs.
    assert flops.dnn_forward_flops(13, 9, 2) == 2 * macs
    assert flops.dnn_forward_flops(20, 20, 2) == flops.dnn_forward_flops(
        32, 32, 2)


def test_dnn_train_flops_hand_count():
    layers = flops.dnn_layers(16, 16, 2)
    fwd = sum(m for _, m in layers)
    # weight gradients: one forward; input gradients: all but conv_in.
    assert flops.dnn_train_flops(16, 16, 2) == 2 * (2 * fwd
                                                    + fwd - 9 * 2 * 4 * 256)


def test_no_skip_variant_has_narrower_up_path():
    assert flops.dnn_forward_flops(16, 16, 1, skip=False) < \
        flops.dnn_forward_flops(16, 16, 1)


@pytest.mark.parametrize("n,epochs,batch,want", [
    (64, 5, 10, 300),    # 6 drop-last batches of 10 per epoch
    (100, 5, 10, 500),
    (7, 2, 10, 14),      # batch shrinks to the slice count
])
def test_trained_samples(n, epochs, batch, want):
    assert flops.trained_samples(n, epochs, batch) == want


def test_interp_quantizer_least_is_memory_bound_on_v5e():
    fl, by = flops.interp_quantizer_least(1000)
    assert (fl, by) == (14000, 12000)
    t, bound = flops.least_time(fl, by, device.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(12000 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
