"""RNN family (SimpleRNN/LSTM/GRU), CTC loss and sync_batch_norm tests.

Torch is the numerics oracle for the recurrent layers and CTC (reference:
python/paddle/nn/layer/rnn.py matches torch gate order/math exactly — LSTM
i,f,g,o; GRU r,z,n — and warpctc matches torch's ctc_loss), per VERDICT r2
item 3: these capabilities were previously misclassified as "no TPU analog".
"""

import numpy as np
import pytest
import torch

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def _copy_lstm_weights(pd, th, num_layers, bidirectional):
    """Copy torch RNN-family weights into the paddle layer (same layout)."""
    D = 2 if bidirectional else 1
    for layer in range(num_layers):
        for d in range(D):
            suffix = f"_l{layer}" + ("_reverse" if d else "")
            cell = pd.cells[layer * D + d]
            cell.weight_ih._data = paddle.to_tensor(
                getattr(th, "weight_ih" + suffix).detach().numpy())._data
            cell.weight_hh._data = paddle.to_tensor(
                getattr(th, "weight_hh" + suffix).detach().numpy())._data
            cell.bias_ih._data = paddle.to_tensor(
                getattr(th, "bias_ih" + suffix).detach().numpy())._data
            cell.bias_hh._data = paddle.to_tensor(
                getattr(th, "bias_hh" + suffix).detach().numpy())._data


@pytest.mark.parametrize("bidirectional", [False, True])
@pytest.mark.parametrize("num_layers", [1, 2])
def test_lstm_vs_torch(rng, bidirectional, num_layers):
    B, T, I, H = 3, 7, 5, 8
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    th = torch.nn.LSTM(I, H, num_layers=num_layers, batch_first=True,
                       bidirectional=bidirectional)
    pd = nn.LSTM(I, H, num_layers=num_layers,
                 direction="bidirect" if bidirectional else "forward")
    _copy_lstm_weights(pd, th, num_layers, bidirectional)

    y, (h, c) = pd(paddle.to_tensor(x))
    ty, (th_h, th_c) = th(torch.from_numpy(x))
    np.testing.assert_allclose(y.numpy(), ty.detach().numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h.numpy(), th_h.detach().numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c.numpy(), th_c.detach().numpy(),
                               rtol=1e-5, atol=1e-5)


def test_gru_vs_torch(rng):
    B, T, I, H = 2, 6, 4, 5
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    th = torch.nn.GRU(I, H, num_layers=2, batch_first=True)
    pd = nn.GRU(I, H, num_layers=2)
    _copy_lstm_weights(pd, th, 2, False)
    y, h = pd(paddle.to_tensor(x))
    ty, th_h = th(torch.from_numpy(x))
    np.testing.assert_allclose(y.numpy(), ty.detach().numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h.numpy(), th_h.detach().numpy(),
                               rtol=1e-5, atol=1e-5)


def test_simple_rnn_vs_torch(rng):
    B, T, I, H = 2, 5, 3, 4
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    th = torch.nn.RNN(I, H, batch_first=True, nonlinearity="tanh")
    pd = nn.SimpleRNN(I, H, activation="tanh")
    _copy_lstm_weights(pd, th, 1, False)
    y, h = pd(paddle.to_tensor(x))
    ty, th_h = th(torch.from_numpy(x))
    np.testing.assert_allclose(y.numpy(), ty.detach().numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h.numpy(), th_h.detach().numpy(),
                               rtol=1e-5, atol=1e-5)


def test_lstm_grads_vs_torch(rng):
    B, T, I, H = 2, 5, 4, 6
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    th = torch.nn.LSTM(I, H, batch_first=True)
    pd = nn.LSTM(I, H)
    _copy_lstm_weights(pd, th, 1, False)

    xt = paddle.to_tensor(x)
    y, _ = pd(xt)
    loss = y.sum()
    loss.backward()

    tx = torch.from_numpy(x)
    ty, _ = th(tx)
    ty.sum().backward()
    np.testing.assert_allclose(
        pd.cells[0].weight_ih.grad.numpy(),
        th.weight_ih_l0.grad.numpy(), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        pd.cells[0].weight_hh.grad.numpy(),
        th.weight_hh_l0.grad.numpy(), rtol=1e-4, atol=1e-4)


def test_lstm_cell_single_step(rng):
    B, I, H = 3, 4, 5
    x = rng.standard_normal((B, I)).astype(np.float32)
    cell = nn.LSTMCell(I, H)
    tcell = torch.nn.LSTMCell(I, H)
    tcell.weight_ih.data = torch.from_numpy(cell.weight_ih.numpy())
    tcell.weight_hh.data = torch.from_numpy(cell.weight_hh.numpy())
    tcell.bias_ih.data = torch.from_numpy(cell.bias_ih.numpy())
    tcell.bias_hh.data = torch.from_numpy(cell.bias_hh.numpy())
    h, (h2, c2) = cell(paddle.to_tensor(x))
    th_h, th_c = tcell(torch.from_numpy(x))
    np.testing.assert_allclose(h.numpy(), th_h.detach().numpy(),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c2.numpy(), th_c.detach().numpy(),
                               rtol=1e-5, atol=1e-5)


def test_rnn_sequence_length_masks(rng):
    """State freezes and outputs zero past each row's length (reference
    sequence_length semantics)."""
    B, T, I, H = 3, 8, 4, 5
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    lens = np.asarray([8, 5, 2], np.int32)
    lstm = nn.LSTM(I, H)
    y, (h, c) = lstm(paddle.to_tensor(x), sequence_length=paddle.to_tensor(lens))
    yn = y.numpy()
    for b, ln in enumerate(lens):
        assert np.all(yn[b, ln:] == 0.0)
        # final state equals running the trimmed sequence alone
        y1, (h1, c1) = lstm(paddle.to_tensor(x[b:b + 1, :ln]))
        np.testing.assert_allclose(h.numpy()[0, b], h1.numpy()[0, 0],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(yn[b, :ln], y1.numpy()[0],
                                   rtol=1e-5, atol=1e-5)


def test_rnn_runs_custom_cell(rng):
    """nn.RNN must step arbitrary user cells through their own forward
    (reference RNN contract), not only the three built-ins."""
    import paddle_tpu

    class Residual(nn.RNNCellBase):
        def __init__(self, size):
            super().__init__()
            self.lin = nn.Linear(size, size)

        @property
        def state_shape(self):
            return (self.lin.out_features,)

        def forward(self, x, states=None):
            h = states if states is not None else self.get_initial_states(x)
            out = paddle_tpu.tanh(self.lin(x) + h)
            return out, out

    B, T, H = 2, 4, 3
    x = rng.standard_normal((B, T, H)).astype(np.float32)
    cell = Residual(H)
    runner = nn.RNN(cell)
    y, h = runner(paddle.to_tensor(x))
    assert tuple(y.shape) == (B, T, H)
    # oracle: manual unroll through the cell itself
    state = None
    for t in range(T):
        out, state = cell(paddle.to_tensor(x[:, t]), state)
        np.testing.assert_allclose(y.numpy()[:, t], out.numpy(),
                                   rtol=1e-6, atol=1e-6)


def test_birnn_wrapper(rng):
    B, T, I, H = 2, 6, 3, 4
    x = rng.standard_normal((B, T, I)).astype(np.float32)
    bi = nn.BiRNN(nn.GRUCell(I, H), nn.GRUCell(I, H))
    y, (s_fw, s_bw) = bi(paddle.to_tensor(x))
    assert tuple(y.shape) == (B, T, 2 * H)
    # forward half equals a plain forward RNN with the same cell
    runner = nn.RNN(bi.cell_fw)
    y_fw, _ = runner(paddle.to_tensor(x))
    np.testing.assert_allclose(y.numpy()[..., :H], y_fw.numpy(),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# CTC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_ctc_loss_vs_torch(rng, reduction):
    T, B, C, L = 12, 3, 6, 4
    logits = rng.standard_normal((T, B, C)).astype(np.float32)
    labels = rng.integers(1, C, (B, L)).astype(np.int32)   # 0 is blank
    in_lens = np.asarray([12, 10, 7], np.int32)
    lab_lens = np.asarray([4, 3, 2], np.int32)

    got = F.ctc_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                     paddle.to_tensor(in_lens), paddle.to_tensor(lab_lens),
                     blank=0, reduction=reduction)

    t_logp = torch.log_softmax(torch.from_numpy(logits), dim=-1)
    expect = torch.nn.functional.ctc_loss(
        t_logp, torch.from_numpy(labels.astype(np.int64)),
        torch.from_numpy(in_lens.astype(np.int64)),
        torch.from_numpy(lab_lens.astype(np.int64)),
        blank=0, reduction=reduction, zero_infinity=False)
    np.testing.assert_allclose(np.asarray(got.numpy(), np.float32),
                               expect.numpy(), rtol=1e-4, atol=1e-4)


def test_ctc_loss_grad_flows(rng):
    T, B, C, L = 8, 2, 5, 3
    logits = rng.standard_normal((T, B, C)).astype(np.float32)
    labels = rng.integers(1, C, (B, L)).astype(np.int32)
    x = paddle.to_tensor(logits)
    x.stop_gradient = False
    loss = F.ctc_loss(x, paddle.to_tensor(labels),
                      paddle.to_tensor(np.asarray([8, 6], np.int32)),
                      paddle.to_tensor(np.asarray([3, 2], np.int32)))
    loss.backward()
    g = x.grad.numpy()
    assert g.shape == logits.shape and np.isfinite(g).all() and \
        np.abs(g).sum() > 0

    t_in = torch.from_numpy(logits).requires_grad_(True)
    t_logp = torch.log_softmax(t_in, dim=-1)
    expect = torch.nn.functional.ctc_loss(
        t_logp, torch.from_numpy(labels.astype(np.int64)),
        torch.tensor([8, 6]), torch.tensor([3, 2]), blank=0)
    expect.backward()
    np.testing.assert_allclose(g, t_in.grad.numpy(), rtol=1e-4, atol=1e-4)


def test_ctc_loss_layer():
    loss_layer = nn.CTCLoss(blank=0, reduction="sum")
    logits = np.zeros((4, 1, 3), np.float32)
    out = loss_layer(paddle.to_tensor(logits),
                     paddle.to_tensor(np.asarray([[1, 2]], np.int32)),
                     paddle.to_tensor(np.asarray([4], np.int32)),
                     paddle.to_tensor(np.asarray([2], np.int32)))
    assert np.isfinite(float(out.numpy()))


# ---------------------------------------------------------------------------
# sync_batch_norm
# ---------------------------------------------------------------------------

def test_sync_batch_norm_matches_global_stats(rng):
    """psum-combined stats over a 4-way dp shard == serial batch norm over
    the full batch (the reference's NCCL-allreduce semantics)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.core.tensor import Tensor

    B, C, H, W = 8, 3, 4, 4
    x = rng.standard_normal((B, C, H, W)).astype(np.float32)
    w = rng.standard_normal((C,)).astype(np.float32)
    b = rng.standard_normal((C,)).astype(np.float32)

    class G:
        axis_name = "dp"
        nranks = 4

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

    def local_fn(xs, ws, bs):
        out = F.sync_batch_norm(Tensor(xs), None, None, Tensor(ws),
                                Tensor(bs), training=True, group=G())
        return out._data

    got = jax.shard_map(local_fn, mesh=mesh,
                        in_specs=(P("dp"), P(), P()), out_specs=P("dp"),
                        check_vma=True)(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))

    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    expect = (x - mean[None, :, None, None]) / np.sqrt(
        var[None, :, None, None] + 1e-5) * w[None, :, None, None] + \
        b[None, :, None, None]
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-4, atol=1e-4)


def test_sync_batch_norm_layer_single_process(rng):
    """Outside any parallel context it degenerates to BatchNorm exactly."""
    x = rng.standard_normal((4, 3, 5, 5)).astype(np.float32)
    paddle.seed(0)
    sbn = nn.SyncBatchNorm(3)
    bn = nn.BatchNorm2D(3)
    sbn.train(), bn.train()
    a = sbn(paddle.to_tensor(x)).numpy()
    e = bn(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sbn._mean.numpy(), bn._mean.numpy(),
                               rtol=1e-5, atol=1e-5)


def test_convert_sync_batchnorm():
    net = nn.Sequential(nn.Conv2D(3, 4, 3), nn.BatchNorm2D(4), nn.ReLU())
    out = nn.SyncBatchNorm.convert_sync_batchnorm(net)
    kinds = [type(l).__name__ for l in out]
    assert "SyncBatchNorm" in kinds and "BatchNorm2D" not in kinds