"""ResNet-50 over four chips: the one-chip configuration's net and sizes,
driven by ``GSPMDTrainer`` over a ``data=4`` mesh with ZeRO-sharded
updater state. ``fit`` is this configuration's own, because the trainer,
not the net, takes the iterator.
"""

import os

from chipbench.manifest import load_module

_one = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "resnet50-imagenet-bf16", "model.py"),
    "chipbench_dyn_resnet50_imagenet_bf16_model_for_dp4")

param_spec = _one.param_spec
flops_per_sample = _one.flops_per_sample
n_matmuls = _one.n_matmuls
read_leaves = _one.read_leaves


def build(cfg, weights, chips: int = 4):
    from deeplearning4j_tpu.distributed import (GSPMDTrainer,
                                                ShardedTrainingPlan,
                                                ZeroPlan)
    from deeplearning4j_tpu.nn.augment import DeviceAugmentation
    from deeplearning4j_tpu.parallel.mesh import DeviceMesh
    import jax
    net = _one.build(cfg, weights)
    net.setDeviceAugmentation(DeviceAugmentation().scale_to(0.0, 1.0))
    mesh = DeviceMesh.create(data=int(cfg["mesh"]["data"]),
                             devices=jax.devices()[:chips])
    plan = ShardedTrainingPlan(mesh,
                               zero=ZeroPlan() if cfg.get("zero") else None)
    net.chipbench_trainer = GSPMDTrainer(net, plan)
    return net


def fit(net, iterator):
    """The call the window times: ``GSPMDTrainer.fit`` takes no
    ``augment=``, so ``build`` set the same scaling on the net."""
    net.chipbench_trainer.fit(iterator)
