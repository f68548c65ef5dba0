"""Vision model zoo tests (reference: python/paddle/vision/models/).

Small spatial inputs keep single-CPU CI fast; every family is constructed
and run forward, and one family is trained one step to check grads flow.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M

T = paddle.to_tensor


def _img(rng, size=64, batch=1):
    return T(rng.standard_normal((batch, 3, size, size)).astype("float32"))


def _check(out, num_classes=10):
    assert tuple(out.shape) == (1, num_classes)
    assert np.isfinite(np.asarray(out._data)).all()


def test_alexnet(rng):
    _check(M.alexnet(num_classes=10)(_img(rng)))


def test_vgg(rng):
    _check(M.vgg11(num_classes=10)(_img(rng)))
    _check(M.vgg11(batch_norm=True, num_classes=10)(_img(rng)))


def test_squeezenet(rng):
    _check(M.squeezenet1_0(num_classes=10)(_img(rng)))
    _check(M.squeezenet1_1(num_classes=10)(_img(rng)))


def test_mobilenets(rng):
    _check(M.mobilenet_v1(scale=0.25, num_classes=10)(_img(rng, 32)))
    _check(M.mobilenet_v2(scale=0.35, num_classes=10)(_img(rng, 32)))


def test_mobilenet_v3(rng):
    _check(M.mobilenet_v3_small(scale=0.5, num_classes=10)(_img(rng, 32)))


def test_shufflenet(rng):
    _check(M.shufflenet_v2_x0_25(num_classes=10)(_img(rng, 32)))


def test_densenet(rng):
    _check(M.densenet121(num_classes=10)(_img(rng, 32)))


def test_googlenet(rng):
    m = M.googlenet(num_classes=10)
    m.eval()
    _check(m(_img(rng, 64)))
    m.train()
    out = m(_img(rng, 128))
    assert isinstance(out, tuple) and len(out) == 3
    for o in out:
        _check(o)


def test_inception_v3(rng):
    _check(M.inception_v3(num_classes=10)(_img(rng, 96)))


def test_resnext(rng):
    _check(M.resnext50_32x4d(num_classes=10)(_img(rng, 32)))


def test_wide_resnet(rng):
    _check(M.wide_resnet101_2(num_classes=10)(_img(rng, 32)))


@pytest.mark.parametrize("family,kw", [("mobilenet_v2", {"scale": 0.25}),
                                       ("resnet18", {})])
def test_vision_model_trains(rng, family, kw):
    """A few SGD steps on one batch: every loss finite, the last under the
    first, parameters moved."""
    paddle.seed(0)
    m = getattr(M, family)(num_classes=4, **kw)
    opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=m.parameters())
    x = _img(rng, 32, batch=2)
    y = T(np.asarray([0, 3], "int64"))
    first = next(p for p in m.parameters() if p.trainable)
    before = np.asarray(first._data).copy()
    losses = []
    for _ in range(4):
        loss = paddle.nn.CrossEntropyLoss()(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss._data))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    assert not np.allclose(before, np.asarray(first._data))


# ---------------- widened transforms ----------------

def test_widened_transforms(rng):
    from paddle_tpu.vision import transforms as TR
    img = rng.integers(0, 256, (32, 48, 3)).astype("uint8")
    np.random.seed(0)
    assert TR.RandomVerticalFlip(1.0)(img).shape == (32, 48, 3)
    assert TR.Pad(4)(img).shape == (40, 56, 3)
    assert TR.Pad((1, 2))(img).shape == (36, 50, 3)
    assert TR.Grayscale(3)(img).shape == (32, 48, 3)
    assert TR.RandomRotation(30)(img).shape == (32, 48, 3)
    assert TR.RandomResizedCrop(16)(img).shape == (16, 16, 3)
    assert TR.ColorJitter(0.4, 0.4, 0.4, 0.1)(img).shape == (32, 48, 3)
    out = TR.RandomErasing(1.0, value=7)(img)
    assert (out == 7).any()
    assert TR.RandomAffine(20, translate=(0.1, 0.1),
                           scale=(0.8, 1.2))(img).shape == (32, 48, 3)


def test_transform_functional_numerics(rng):
    from paddle_tpu.vision import transforms as TR
    img = rng.integers(0, 256, (8, 8, 3)).astype("uint8")
    np.testing.assert_array_equal(TR.hflip(img), img[:, ::-1])
    np.testing.assert_array_equal(TR.vflip(img), img[::-1])
    np.testing.assert_array_equal(TR.crop(img, 2, 3, 4, 5),
                                  img[2:6, 3:8])
    g = TR.to_grayscale(img, 1)
    want = (0.299 * img[..., 0] + 0.587 * img[..., 1]
            + 0.114 * img[..., 2]).astype("uint8")
    assert np.abs(g[..., 0].astype(int) - want.astype(int)).max() <= 1
    # hue round-trip: identity shift and full-turn shift are no-ops
    h0 = TR.adjust_hue(img, 0.0)
    assert np.abs(h0.astype(int) - img.astype(int)).max() <= 2
    # brightness on float images has no clipping at 1.0
    f = img.astype("float32") / 255.0
    np.testing.assert_allclose(TR.adjust_brightness(f, 2.0), f * 2.0,
                               rtol=1e-6)
    r = TR.rotate(f, 0.0)
    np.testing.assert_allclose(r, f, rtol=1e-6)
