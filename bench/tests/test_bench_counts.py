"""counts.py against hand-counted tiny conv, dense and pool layers."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import counts  # noqa: E402

X = np.array([[1, 0, 2], [0, 3, 0], [4, 0, 5]], np.float32)[None, :, :, None]
W = np.array([[1, 0], [0, 1]], np.float32)[:, :, None, None]


@pytest.mark.parametrize("stride,padding,macs", [(1, 0, 4), (2, 1, 5)])
def test_conv_counts_products_with_two_nonzero_operands(stride, padding,
                                                        macs):
    layer = dict(kind="conv", out_ch=1, k=2, stride=stride, padding=padding)
    m, act, y = counts.layer_counts(layer, W, X)
    assert y.shape == (1, 2, 2, 1)
    assert int(m[0]) == macs
    assert int(act[0]) == 5 + 4          # nnz(x) + numel(out)


def test_dense_counts_nonzero_inputs_times_nonzero_weights_in_their_row():
    x = np.array([[1, 0, 2], [0, 0, 0]], np.float32)
    w = np.array([[1, 0], [1, 1], [0, 0]], np.float32)
    m, act, _ = counts.layer_counts(dict(kind="fc", out=2, last=True), w, x)
    assert m.tolist() == [1, 0]
    assert act.tolist() == [2 + 2, 0 + 2]


def test_pool_moves_bytes_and_does_no_products():
    m, act, y = counts.layer_counts(dict(kind="pool", k=2, stride=1), None, X)
    assert np.asarray(y)[0, :, :, 0].tolist() == [[3, 3], [4, 5]]
    assert m.tolist() == [0]
    assert act.tolist() == [5 + 4]


def test_pool_counts_walk_the_network_and_bound_a_step():
    cfg = dict(input_shape=[3, 3, 1], layers=[
        dict(kind="conv", out_ch=1, k=2, stride=1, padding=0),
        dict(kind="pool", k=2, stride=1),
        dict(kind="fc", out=2)])
    w_fc = np.array([[2.0, 0.0]], np.float32)
    c = counts.pool_counts(cfg, [W, None, w_fc], np.stack([X[0], -X[0]]),
                           block=4)
    # image 0: conv out [[1+3, 0], [0, 3+5]] -> ReLU -> pool max 8 -> fc.
    # image 1: all conv outputs negative -> ReLU zeros -> nothing live.
    assert c["macs"].tolist() == [[4, 4], [0, 0], [1, 0]]
    assert c["elements"].tolist() == [[9, 9], [2 + 1, 0 + 1], [1 + 2, 0 + 2]]
    assert c["weight_nnz"].tolist() == [2, 0, 1]
    peaks = dict(bf16_flops_per_s=1.0, hbm_bytes_per_s=4.0)
    # per layer max(2 * macs, 4 * (elements + weight nnz) / 4)
    idx = np.array([0, 0, 1])
    want = (max(2 * 12, 27 + 2) + max(0, 7 + 0) + max(2 * 2, 8 + 1))
    assert counts.step_bound_s(c, idx, peaks) == pytest.approx(want)
    assert counts.useful_flops(c, idx) == 2 * (12 + 2)
