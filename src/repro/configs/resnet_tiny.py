"""resnet-tiny — the MetaFed paper's own client model (~4.8M params).

Not part of the assigned-architecture pool; this is the architecture the
paper's Tables I/II are built on (MNIST / CIFAR-10 federated clients).
``chip_smoke.py`` runs ``CONFIG`` at this full width (P = 4,696,394); the
examples and table benchmarks use narrower ResNets of the same family.
"""
from repro.models.resnet import ResNetConfig

CONFIG = ResNetConfig(name="resnet-tiny", widths=(64, 128, 256), depths=(4, 4, 3), in_channels=3, num_classes=10)
CONFIG_MNIST = ResNetConfig(name="resnet-tiny-mnist", widths=(64, 128, 256), depths=(4, 4, 3), in_channels=1, num_classes=10)
