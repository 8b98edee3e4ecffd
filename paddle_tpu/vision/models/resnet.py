"""ResNet family (ref: python/paddle/vision/models/resnet.py:168 — the
BASELINE.json config-2 flagship, "PaddleClas ResNet-50").

TPU notes: layout is selectable.  ``data_format="NCHW"`` matches the
reference default; ``"NHWC"`` runs every conv/BN/pool channels-last —
the TPU-native layout (C rides the 128-lane minor dim, XLA stops
materializing layout conversions around each conv).  Parameters keep
the reference OIHW layout either way, so checkpoints are
layout-portable.  BasicBlock for 18/34, BottleneckBlock for 50/101/152.
"""
from __future__ import annotations

from ... import nn


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None,
                 data_format="NCHW"):
        super().__init__()
        self.conv1 = nn.Conv2D(inplanes, planes, 3, stride=stride, padding=1,
                               bias_attr=False, data_format=data_format)
        self.bn1 = nn.BatchNorm2D(planes, data_format=data_format)
        self.relu = nn.ReLU()
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False,
                               data_format=data_format)
        self.bn2 = nn.BatchNorm2D(planes, data_format=data_format)
        self.downsample = downsample

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None,
                 data_format="NCHW"):
        super().__init__()
        self.conv1 = nn.Conv2D(inplanes, planes, 1, bias_attr=False,
                               data_format=data_format)
        self.bn1 = nn.BatchNorm2D(planes, data_format=data_format)
        self.conv2 = nn.Conv2D(planes, planes, 3, stride=stride, padding=1,
                               bias_attr=False, data_format=data_format)
        self.bn2 = nn.BatchNorm2D(planes, data_format=data_format)
        self.conv3 = nn.Conv2D(planes, planes * self.expansion, 1,
                               bias_attr=False, data_format=data_format)
        self.bn3 = nn.BatchNorm2D(planes * self.expansion,
                                  data_format=data_format)
        self.relu = nn.ReLU()
        self.downsample = downsample

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class ResNet(nn.Layer):
    """ref resnet.py ResNet(Layer): depth in {18,34,50,101,152}."""

    _cfg = {18: (BasicBlock, (2, 2, 2, 2)),
            34: (BasicBlock, (3, 4, 6, 3)),
            50: (BottleneckBlock, (3, 4, 6, 3)),
            101: (BottleneckBlock, (3, 4, 23, 3)),
            152: (BottleneckBlock, (3, 8, 36, 3))}

    def __init__(self, depth=50, num_classes=1000, with_pool=True,
                 in_channels=3, data_format="NCHW"):
        super().__init__()
        block, layers = self._cfg[depth]
        self.num_classes = num_classes
        self.with_pool = with_pool
        self.inplanes = 64
        self.data_format = data_format
        self.conv1 = nn.Conv2D(in_channels, 64, 7, stride=2, padding=3,
                               bias_attr=False, data_format=data_format)
        self.bn1 = nn.BatchNorm2D(64, data_format=data_format)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(3, stride=2, padding=1,
                                    data_format=data_format)
        self.layer1 = self._make_layer(block, 64, layers[0])
        self.layer2 = self._make_layer(block, 128, layers[1], stride=2)
        self.layer3 = self._make_layer(block, 256, layers[2], stride=2)
        self.layer4 = self._make_layer(block, 512, layers[3], stride=2)
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1),
                                                data_format=data_format)
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)

    def _make_layer(self, block, planes, blocks, stride=1):
        downsample = None
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1,
                          stride=stride, bias_attr=False,
                          data_format=self.data_format),
                nn.BatchNorm2D(planes * block.expansion,
                               data_format=self.data_format))
        layers = [block(self.inplanes, planes, stride, downsample,
                        data_format=self.data_format)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes,
                                data_format=self.data_format))
        return nn.Sequential(*layers)

    def forward(self, x):
        x = self.maxpool(self.relu(self.bn1(self.conv1(x))))
        x = self.layer4(self.layer3(self.layer2(self.layer1(x))))
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            from ... import ops
            x = ops.flatten(x, 1, -1)
            x = self.fc(x)
        return x


def resnet18(**kw):
    return ResNet(18, **kw)


def resnet34(**kw):
    return ResNet(34, **kw)


def resnet50(**kw):
    return ResNet(50, **kw)


def resnet101(**kw):
    return ResNet(101, **kw)


def resnet152(**kw):
    return ResNet(152, **kw)
