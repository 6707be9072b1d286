"""ResNet family (reference: python/paddle/vision/models/resnet.py —
BASELINE.md config 1: ResNet-50 ImageNet).

TPU notes: NCHW inputs for API parity with the reference (XLA on TPU
re-layouts convs internally); BatchNorm stats update only in train mode.
"""
from __future__ import annotations

from .. import nn
from .. import ops
from ..nn import functional as F


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, data_format="NCHW"):
        super().__init__()
        df = self._data_format = data_format
        width = int(planes * (base_width / 64.0)) * groups
        self.conv1 = nn.Conv2D(inplanes, width, 1, bias_attr=False,
                               data_format=df)
        self.bn1 = nn.BatchNorm2D(width, data_format=df)
        self.conv2 = nn.Conv2D(width, width, 3, padding=dilation,
                               stride=stride, groups=groups,
                               dilation=dilation, bias_attr=False,
                               data_format=df)
        self.bn2 = nn.BatchNorm2D(width, data_format=df)
        self.conv3 = nn.Conv2D(width, planes * self.expansion, 1,
                               bias_attr=False, data_format=df)
        self.bn3 = nn.BatchNorm2D(planes * self.expansion, data_format=df)
        self.relu = nn.ReLU()
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, data_format="NCHW"):
        super().__init__()
        df = data_format
        self.conv1 = nn.Conv2D(inplanes, planes, 3, padding=1, stride=stride,
                               bias_attr=False, data_format=df)
        self.bn1 = nn.BatchNorm2D(planes, data_format=df)
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False,
                               data_format=df)
        self.bn2 = nn.BatchNorm2D(planes, data_format=df)
        self.relu = nn.ReLU()
        self.downsample = downsample

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class ResNet(nn.Layer):
    """data_format="NHWC" runs the whole network channels-last — on TPU the
    MXU-native conv layout (lane dim = channels), saving the relayout
    transposes XLA inserts around NCHW convs (BASELINE config 1 MFU work)."""

    def __init__(self, block, depth_cfg, num_classes=1000, with_pool=True,
                 data_format="NCHW"):
        super().__init__()
        df = self.data_format = data_format
        self.inplanes = 64
        self.conv1 = nn.Conv2D(3, 64, 7, stride=2, padding=3, bias_attr=False,
                               data_format=df)
        self.bn1 = nn.BatchNorm2D(64, data_format=df)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(kernel_size=3, stride=2, padding=1,
                                    data_format=df)
        self.layer1 = self._make_layer(block, 64, depth_cfg[0])
        self.layer2 = self._make_layer(block, 128, depth_cfg[1], stride=2)
        self.layer3 = self._make_layer(block, 256, depth_cfg[2], stride=2)
        self.layer4 = self._make_layer(block, 512, depth_cfg[3], stride=2)
        self.with_pool = with_pool
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1), data_format=df)
        self.num_classes = num_classes
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)

    def _make_layer(self, block, planes, blocks, stride=1):
        df = self.data_format
        downsample = None
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1,
                          stride=stride, bias_attr=False, data_format=df),
                nn.BatchNorm2D(planes * block.expansion, data_format=df),
            )
        layers = [block(self.inplanes, planes, stride, downsample,
                        data_format=df)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes, data_format=df))
        return nn.Sequential(*layers)

    def forward(self, x):
        x = self.relu(self.bn1(self.conv1(x)))
        x = self.maxpool(x)
        x = self.layer1(x)
        x = self.layer2(x)
        x = self.layer3(x)
        x = self.layer4(x)
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            x = ops.flatten(x, 1)
            x = self.fc(x)
        return x


def resnet18(num_classes=1000, **kw):
    return ResNet(BasicBlock, [2, 2, 2, 2], num_classes=num_classes, **kw)


def resnet34(num_classes=1000, **kw):
    return ResNet(BasicBlock, [3, 4, 6, 3], num_classes=num_classes, **kw)


def resnet50(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes=num_classes, **kw)


def resnet101(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes=num_classes, **kw)


def resnet152(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 8, 36, 3], num_classes=num_classes, **kw)


def wide_resnet50_2(num_classes=1000, **kw):
    """Reference: vision/models/resnet.py wide_resnet50_2 (2x width)."""
    m = ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes=num_classes, **kw)
    return m


def wide_resnet101_2(num_classes=1000, **kw):
    m = ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes=num_classes, **kw)
    return m
