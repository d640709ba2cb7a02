"""Required FLOPs and bytes on known shapes, and the peaks table."""
import pytest

import roofline
from harness import Dispatch
from spec import load_spec


def test_packed_matmul_call_known_shape():
    f, b = roofline.packed_matmul_call(8, 4096, 16384)
    assert f == 2 * 8 * 4096 * 16384
    assert b == 16384 * 2048 + 4 * (4096 + 16384) + 2 * 8 * 4096 \
        + 2 * 8 * 16384


def test_packed_matmul_work_counts_every_layer_once_per_dispatch():
    spec = load_spec("minitron-8b.int4")
    peak = roofline.peaks("TPU v5 lite")
    one = roofline.packed_matmul_work(spec, [Dispatch("step", 1, 1)],
                                      peak)
    chunk = roofline.packed_matmul_work(
        spec, [Dispatch("chunk", 1, 16)], peak)
    assert chunk[0] == pytest.approx(16 * one[0])
    # one decode row reads every packed layer weight once: 3.09 GB
    assert 3.0e9 < one[1] < 3.2e9
    assert one[2] == pytest.approx(one[1] / 819e9, rel=1e-3)
    # a chunk of 16 tokens needs the weights once too, not 16 times
    assert chunk[1] < 1.05 * one[1]
    # decode is bound by bytes; 8 bursts of 16 rows x 16 tokens by FLOPs
    burst = roofline.packed_matmul_work(
        spec, [Dispatch("chunk", 8, 16)], peak)
    assert burst[2] == pytest.approx(burst[0] / 197e12, rel=1e-3)


def test_model_flops_counts_weights_head_and_context():
    spec = load_spec("minicpm-2b.int4")
    per_tok = 2 * spec.layers * spec.layer_params()
    head = 2 * spec.d_model * spec.vocab
    attn = 4 * spec.layers * spec.n_heads * spec.head_dim
    assert roofline.model_flops(spec, 10, 0, 1, 55) == \
        10 * per_tok + head + 55 * attn
    assert roofline.model_flops(spec, 0, 3, 0, 0) == 3 * (per_tok + head)
    # minicpm-2b's layers hold 2.44 G weights
    assert 2.4e9 < spec.layers * spec.layer_params() < 2.5e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9000")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
