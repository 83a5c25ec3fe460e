"""Cost functions against hand counts at a small shape, and the peaks
table."""
import pytest

from bench.cost import decoder, kernels
from bench.peaks import peak

CONF = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
        "intermediate_size": 16, "vocab_size": 10, "num_hidden_layers": 1}


def test_matmul_params_by_hand():
    # q, o: 8x8 each; k, v: 8x8 each; mlp 3 x 8x16; head 10x8
    assert decoder.matmul_params(CONF) == 128 + 128 + 384 + 80


def test_forward_flops_by_hand():
    # one query over 3 keys: 2 matmuls x 2 heads x 4 dims x 2 ops x 3
    assert decoder.attn_flops_per_token(CONF, 3) == 96
    assert decoder.forward_flops_token(CONF, 3) == 2 * 720 + 96
    # a causal sequence is the sum of its tokens at their positions
    seq = sum(decoder.forward_flops_token(CONF, p + 1) for p in range(3))
    assert decoder.forward_flops_sequence(CONF, 3) == seq == 4512
    assert decoder.train_flops_sequence(CONF, 3) == 3 * 4512


@pytest.mark.parametrize("momentum,flops,nbytes", [
    (False, 2 * 256, 3 * 256 * 4), (True, 4 * 256, 5 * 256 * 4)])
def test_dbl_apply_by_hand(momentum, flops, nbytes):
    assert kernels.dbl_apply_flat2d(2, momentum=momentum) == (flops, nbytes)


def test_peaks_v5e():
    p = peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        peak("TPU v99")
