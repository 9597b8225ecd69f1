"""Vision model zoo + metric tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.metric import Accuracy, Auc, Precision, Recall, accuracy
from paddle_tpu.vision.models import (
    LeNet, MobileNetV2, resnet18, vgg11,
)
from paddle_tpu.vision import transforms
from paddle_tpu.vision.datasets import MNIST


def test_lenet_forward_backward():
    m = LeNet()
    x = paddle.to_tensor(np.random.randn(2, 1, 28, 28).astype(np.float32),
                         stop_gradient=False)
    out = m(x)
    assert out.shape == [2, 10]
    out.sum().backward()
    assert m.features[0].weight.grad is not None


def test_resnet18_forward():
    m = resnet18(num_classes=10)
    m.eval()
    x = paddle.to_tensor(np.random.randn(2, 3, 64, 64).astype(np.float32))
    assert m(x).shape == [2, 10]


def test_resnet_state_dict_structure():
    m = resnet18(num_classes=10)
    sd = m.state_dict()
    assert "conv1.weight" in sd
    assert "layer1.0.conv1.weight" in sd
    assert "fc.weight" in sd


def test_resnet_nhwc_matches_nchw():
    """data_format='NHWC' (the TPU-native conv layout)
    must be numerically identical to NCHW — same weights, transposed
    input/activations only."""
    x = np.random.default_rng(0).standard_normal((2, 3, 64, 64)).astype(
        np.float32)
    paddle.seed(0)
    m1 = resnet18(num_classes=10)
    m1.eval()
    paddle.seed(0)
    m2 = resnet18(num_classes=10, data_format="NHWC")
    m2.eval()
    o1 = m1(paddle.to_tensor(x)).numpy()
    o2 = m2(paddle.to_tensor(np.transpose(x, (0, 2, 3, 1)))).numpy()
    np.testing.assert_allclose(o1, o2, atol=2e-4)
    # NHWC state dict keys/shapes identical (weights stay OIHW)
    assert {k: tuple(v.shape) for k, v in m1.state_dict().items()} == \
        {k: tuple(v.shape) for k, v in m2.state_dict().items()}
    # train-mode fwd/bwd works and running stats update
    m2.train()
    before = m2.bn1._mean.numpy().copy()
    out = m2(paddle.to_tensor(np.transpose(x, (0, 2, 3, 1))))
    (out ** 2).mean().backward()
    assert m2.conv1.weight.grad is not None
    assert np.isfinite(m2.conv1.weight.grad.numpy()).all()
    assert not np.array_equal(before, m2.bn1._mean.numpy())


@pytest.mark.slow
def test_mobilenet_vgg_forward():
    x = paddle.to_tensor(np.random.randn(1, 3, 64, 64).astype(np.float32))
    assert MobileNetV2(num_classes=7)(x).shape == [1, 7]
    assert vgg11(num_classes=5)(
        paddle.to_tensor(np.random.randn(1, 3, 224, 224).astype(np.float32))
    ).shape == [1, 5]


def test_transforms_pipeline():
    t = transforms.Compose([
        transforms.Resize(32),
        transforms.CenterCrop(28),
        transforms.RandomHorizontalFlip(0.5),
        transforms.ToTensor(),
        transforms.Normalize(mean=[0.5] * 3, std=[0.5] * 3),
    ])
    img = (np.random.rand(48, 56, 3) * 255).astype(np.uint8)
    out = t(img)
    assert out.shape == (3, 28, 28)
    assert out.dtype == np.float32


def test_dataset_dataloader():
    ds = MNIST(mode="train", transform=transforms.ToTensor())
    img, label = ds[0]
    assert img.shape == (1, 28, 28)
    loader = paddle.io.DataLoader(ds, batch_size=16, shuffle=True)
    batch_img, batch_label = next(iter(loader))
    assert np.asarray(batch_img).shape == (16, 1, 28, 28)
    assert np.asarray(batch_label).shape == (16, 1)


def test_accuracy_metric():
    acc = Accuracy(topk=(1, 2))
    pred = paddle.to_tensor(np.array(
        [[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]], np.float32))
    label = paddle.to_tensor(np.array([[1], [2]], np.int64))
    correct = acc.compute(pred, label)
    acc.update(correct)
    top1, top2 = acc.accumulate()
    assert top1 == pytest.approx(0.5)
    assert top2 == pytest.approx(0.5)  # sample2's label 2 not in top2? idx=[0,2] contains 2 -> 1.0
    acc.reset()
    assert acc.count == [0, 0]


def test_accuracy_functional():
    pred = paddle.to_tensor(np.array(
        [[0.1, 0.9], [0.9, 0.1]], np.float32))
    label = paddle.to_tensor(np.array([[1], [0]], np.int64))
    a = accuracy(pred, label, k=1)
    assert float(a.item()) == pytest.approx(1.0)


def test_precision_recall_auc():
    p = Precision()
    r = Recall()
    preds = np.array([0.9, 0.8, 0.2, 0.1], np.float32)
    labels = np.array([1, 0, 1, 0], np.int64)
    p.update(preds, labels)
    r.update(preds, labels)
    assert p.accumulate() == pytest.approx(0.5)
    assert r.accumulate() == pytest.approx(0.5)
    auc = Auc()
    auc.update(preds, labels)
    assert 0.0 <= auc.accumulate() <= 1.0


def test_nms():
    from paddle_tpu.vision.ops import nms
    boxes = paddle.to_tensor(np.array([
        [0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]], np.float32))
    scores = paddle.to_tensor(np.array([0.9, 0.8, 0.7], np.float32))
    keep = nms(boxes, iou_threshold=0.3, scores=scores)
    assert np.asarray(keep._data).tolist() == [0, 2]
