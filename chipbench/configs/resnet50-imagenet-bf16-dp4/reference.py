"""The plain reference of ResNet-50 over four chips is the one-chip
configuration's: one program over the global batch, BatchNorm's statistics
taken over all of it."""

import os

from chipbench.manifest import load_module

make_loss = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "resnet50-imagenet-bf16", "reference.py"),
    "chipbench_dyn_resnet50_imagenet_bf16_reference_for_dp4").make_loss
