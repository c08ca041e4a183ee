"""Model zoo — architecture builders.

Reference parity: ``org.deeplearning4j.zoo.model.{LeNet, SimpleCNN,
AlexNet, VGG16, VGG19, ResNet50, Darknet19, TinyYOLO, YOLO2, SqueezeNet,
UNet, Xception, FaceNetNN4Small2, TextGenerationLSTM}`` + ``ZooModel``
base (SURVEY.md §2.2 "Model zoo", L6). Each builder returns a
MultiLayerNetwork or ComputationGraph configured like the reference's
(layer counts/kernels/strides per the canonical papers the reference
follows). ``initPretrained`` requires downloaded weights — this
environment has no egress, so it loads from DL4J_TPU_DATA_DIR instead.
"""

from __future__ import annotations

import os
from typing import Tuple

from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph, ElementWiseVertex,
                                         LabelsVertex, MergeVertex)
from deeplearning4j_tpu.nn.layers import (ActivationLayer, BatchNormalization,
                                          CausalSelfAttentionLayer,
                                          ConvolutionLayer, DenseLayer,
                                          DropoutLayer,
                                          EmbeddingSequenceLayer, GatedMLP,
                                          GatedShortConvLayer,
                                          GlobalPoolingLayer,
                                          HyperConnectionIn,
                                          HyperConnectionOut,
                                          HyperConnectionRead,
                                          HyperConnectionWrite,
                                          LatentAttentionLayer,
                                          LocalResponseNormalization,
                                          LoopedLMOutputLayer, LSTM,
                                          MTPJoinLayer, MTPLMOutputLayer,
                                          OutputLayer, RMSNorm, RnnOutputLayer,
                                          SeparableConvolution2D,
                                          SparseExpertsLayer,
                                          SubsamplingLayer, Upsampling2D)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import updaters


class ZooModel:
    """Base (ref: org.deeplearning4j.zoo.ZooModel)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123,
                 input_shape: Tuple[int, int, int] = None, updater=None,
                 dtype: str = "float32"):
        self.num_classes = num_classes
        self.seed = seed
        self.input_shape = input_shape or self.default_input_shape()
        self.updater = updater or updaters.Adam(1e-3)
        self.dtype = dtype  # "bfloat16" enables the nn/ mixed-precision policy

    #: constructor arguments for the size the cost gates judge
    #: (``analysis --zoo --cost``, ``tools/lint.py``): the default,
    #: unless the published size cannot train replicated on one chip
    cost_gate_kwargs: dict = {}

    @classmethod
    def for_cost_gate(cls):
        return cls(**cls.cost_gate_kwargs)

    def default_input_shape(self):
        return (3, 224, 224)  # (channels, H, W)

    def init(self):
        net = self.conf_builder()
        net.conf.base.dtype = self.dtype
        net.init()
        return net

    def conf_builder(self):
        raise NotImplementedError

    def initPretrained(self, pretrained_type: str = "IMAGENET",
                       path: str = None):
        """ref: ZooModel.initPretrained — checksummed download; here: load
        from a local file (zero-egress environment). Accepts the native
        zip checkpoint format OR a Keras .h5 full-model save (routed
        through modelimport.keras — the reference's pretrained zoo zips
        are themselves Keras-derived)."""
        if path is None:
            base = os.path.join(
                os.environ.get("DL4J_TPU_DATA_DIR",
                               os.path.expanduser("~/.deeplearning4j_tpu")),
                "pretrained",
                f"{type(self).__name__.lower()}_{pretrained_type.lower()}")
            for cand in (base + ".zip", base + ".h5"):
                if os.path.exists(cand):
                    path = cand
                    break
            if path is None:
                raise FileNotFoundError(
                    f"pretrained weights not found at {base}.zip|.h5 (no "
                    f"network egress; place the checkpoint there manually)")
        if not os.path.exists(path):
            raise FileNotFoundError(path)
        if path.endswith((".h5", ".hdf5", ".keras")):
            from deeplearning4j_tpu.modelimport.keras import (Hdf5Archive,
                                                              KerasModelImport)
            arch = Hdf5Archive(path)
            try:
                kind = arch.model_config().get("class_name")
            finally:
                arch.close()
            if kind == "Sequential":
                return KerasModelImport.importKerasSequentialModelAndWeights(path)
            return KerasModelImport.importKerasModelAndWeights(path)
        try:
            return MultiLayerNetwork.load(path)
        except Exception:
            return ComputationGraph.load(path)


class LeNet(ZooModel):
    """ref: zoo.model.LeNet — the canonical MNIST config (BASELINE #0)."""

    def default_input_shape(self):
        return (1, 28, 28)

    def conf_builder(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        conf = (NeuralNetConfiguration.Builder()
                .seed(self.seed).updater(self.updater).weightInit("xavier")
                .list()
                .layer(ConvolutionLayer(kernelSize=(5, 5), stride=(1, 1),
                                        nOut=20, activation="identity"))
                .layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(kernelSize=(5, 5), stride=(1, 1),
                                        nOut=50, activation="identity"))
                .layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                        stride=(2, 2)))
                .layer(DenseLayer(nOut=500, activation="relu"))
                .layer(OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.convolutionalFlat(h, w, c))
                .build())
        return MultiLayerNetwork(conf)


class SimpleCNN(ZooModel):
    """ref: zoo.model.SimpleCNN."""

    def default_input_shape(self):
        return (3, 48, 48)

    def conf_builder(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .list())
        for n_out in (16, 16, 32, 32, 64, 64):
            b = b.layer(ConvolutionLayer(kernelSize=(3, 3), nOut=n_out,
                                         padding=(1, 1), activation="identity"))
            b = b.layer(BatchNormalization())
            b = b.layer(ActivationLayer("relu"))
            if n_out in (16, 32):
                b = b.layer(SubsamplingLayer(poolingType="max",
                                             kernelSize=(2, 2), stride=(2, 2)))
        b = (b.layer(GlobalPoolingLayer("avg"))
             .layer(DropoutLayer(dropOut=0.5))
             .layer(OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                activation="softmax"))
             .setInputType(InputType.convolutional(h, w, c)))
        return MultiLayerNetwork(b.build())


class AlexNet(ZooModel):
    """ref: zoo.model.AlexNet (one-tower variant with LRN)."""

    def conf_builder(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        conf = (NeuralNetConfiguration.Builder()
                .seed(self.seed).updater(self.updater).weightInit("relu")
                .list()
                .layer(ConvolutionLayer(kernelSize=(11, 11), stride=(4, 4),
                                        padding=(3, 3), nOut=96, activation="relu"))
                .layer(LocalResponseNormalization())
                .layer(SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(kernelSize=(5, 5), padding=(2, 2),
                                        nOut=256, activation="relu"))
                .layer(LocalResponseNormalization())
                .layer(SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                        nOut=384, activation="relu"))
                .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                        nOut=384, activation="relu"))
                .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                        nOut=256, activation="relu"))
                .layer(SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                        stride=(2, 2)))
                .layer(DenseLayer(nOut=4096, activation="relu", dropOut=0.5))
                .layer(DenseLayer(nOut=4096, activation="relu", dropOut=0.5))
                .layer(OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf)


def _vgg_blocks(b, plan):
    for n_convs, n_out in plan:
        for _ in range(n_convs):
            b = b.layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                         nOut=n_out, activation="relu"))
        b = b.layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                     stride=(2, 2)))
    return b


class VGG16(ZooModel):
    """ref: zoo.model.VGG16 (BASELINE config #1)."""

    PLAN = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]

    def conf_builder(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .list())
        b = _vgg_blocks(b, self.PLAN)
        b = (b.layer(DenseLayer(nOut=4096, activation="relu", dropOut=0.5))
             .layer(DenseLayer(nOut=4096, activation="relu", dropOut=0.5))
             .layer(OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                activation="softmax"))
             .setInputType(InputType.convolutional(h, w, c)))
        return MultiLayerNetwork(b.build())


class VGG19(VGG16):
    """ref: zoo.model.VGG19."""

    PLAN = [(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)]


class ResNet50(ZooModel):
    """ref: zoo.model.ResNet50 (BASELINE north-star model) — bottleneck
    residual blocks as a ComputationGraph with ElementWiseVertex adds."""

    def conf_builder(self) -> ComputationGraph:
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))

        # stem
        g.addLayer("stem_conv", ConvolutionLayer(kernelSize=(7, 7), stride=(2, 2),
                                                 padding=(3, 3), nOut=64,
                                                 activation="identity"), "input")
        g.addLayer("stem_bn", BatchNormalization(), "stem_conv")
        g.addLayer("stem_relu", ActivationLayer("relu"), "stem_bn")
        g.addLayer("stem_pool", SubsamplingLayer(poolingType="max",
                                                 kernelSize=(3, 3), stride=(2, 2),
                                                 padding=(1, 1)), "stem_relu")
        last = "stem_pool"
        stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
                  (3, 512, 2048, 2)]
        for si, (blocks, mid, out, first_stride) in enumerate(stages):
            for bi in range(blocks):
                stride = first_stride if bi == 0 else 1
                pref = f"s{si}b{bi}"
                # main path: 1x1 -> 3x3 -> 1x1 with BN
                g.addLayer(f"{pref}_c1", ConvolutionLayer(kernelSize=(1, 1),
                                                          stride=(stride, stride),
                                                          nOut=mid,
                                                          activation="identity"), last)
                g.addLayer(f"{pref}_bn1", BatchNormalization(), f"{pref}_c1")
                g.addLayer(f"{pref}_r1", ActivationLayer("relu"), f"{pref}_bn1")
                g.addLayer(f"{pref}_c2", ConvolutionLayer(kernelSize=(3, 3),
                                                          padding=(1, 1), nOut=mid,
                                                          activation="identity"),
                           f"{pref}_r1")
                g.addLayer(f"{pref}_bn2", BatchNormalization(), f"{pref}_c2")
                g.addLayer(f"{pref}_r2", ActivationLayer("relu"), f"{pref}_bn2")
                g.addLayer(f"{pref}_c3", ConvolutionLayer(kernelSize=(1, 1),
                                                          nOut=out,
                                                          activation="identity"),
                           f"{pref}_r2")
                g.addLayer(f"{pref}_bn3", BatchNormalization(), f"{pref}_c3")
                # shortcut
                if bi == 0:
                    g.addLayer(f"{pref}_sc", ConvolutionLayer(kernelSize=(1, 1),
                                                              stride=(stride, stride),
                                                              nOut=out,
                                                              activation="identity"),
                               last)
                    g.addLayer(f"{pref}_scbn", BatchNormalization(), f"{pref}_sc")
                    shortcut = f"{pref}_scbn"
                else:
                    shortcut = last
                g.addVertex(f"{pref}_add", ElementWiseVertex("Add"),
                            f"{pref}_bn3", shortcut)
                g.addLayer(f"{pref}_out", ActivationLayer("relu"), f"{pref}_add")
                last = f"{pref}_out"
        g.addLayer("avgpool", GlobalPoolingLayer("avg"), last)
        g.addLayer("fc", OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                     activation="softmax"), "avgpool")
        g.setOutputs("fc")
        return ComputationGraph(g.build())


class Darknet19(ZooModel):
    """ref: zoo.model.Darknet19 (YOLO backbone)."""

    def default_input_shape(self):
        return (3, 224, 224)

    def conf_builder(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .list())

        def conv_bn(b, n_out, k):
            pad = (k // 2, k // 2)
            b = b.layer(ConvolutionLayer(kernelSize=(k, k), padding=pad,
                                         nOut=n_out, activation="identity"))
            b = b.layer(BatchNormalization())
            return b.layer(ActivationLayer("leakyrelu"))

        def maxpool(b):
            return b.layer(SubsamplingLayer(poolingType="max",
                                            kernelSize=(2, 2), stride=(2, 2)))

        b = conv_bn(b, 32, 3)
        b = maxpool(b)
        b = conv_bn(b, 64, 3)
        b = maxpool(b)
        for trio in [(128, 64), (256, 128)]:
            big, small = trio
            b = conv_bn(b, big, 3)
            b = conv_bn(b, small, 1)
            b = conv_bn(b, big, 3)
            b = maxpool(b)
        for penta in [(512, 256), (1024, 512)]:
            big, small = penta
            b = conv_bn(b, big, 3)
            b = conv_bn(b, small, 1)
            b = conv_bn(b, big, 3)
            b = conv_bn(b, small, 1)
            b = conv_bn(b, big, 3)
            if big == 512:
                b = maxpool(b)
        b = b.layer(ConvolutionLayer(kernelSize=(1, 1), nOut=self.num_classes,
                                     activation="identity"))
        b = (b.layer(GlobalPoolingLayer("avg"))
             .layer(OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                activation="softmax"))
             .setInputType(InputType.convolutional(h, w, c)))
        return MultiLayerNetwork(b.build())


class SqueezeNet(ZooModel):
    """ref: zoo.model.SqueezeNet — fire modules via MergeVertex."""

    def conf_builder(self) -> ComputationGraph:
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))
        g.addLayer("stem", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                            nOut=64, activation="relu"), "input")
        g.addLayer("pool0", SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                             stride=(2, 2)), "stem")
        last = "pool0"

        def fire(g, name, inp, squeeze, expand):
            g.addLayer(f"{name}_sq", ConvolutionLayer(kernelSize=(1, 1),
                                                      nOut=squeeze,
                                                      activation="relu"), inp)
            g.addLayer(f"{name}_e1", ConvolutionLayer(kernelSize=(1, 1),
                                                      nOut=expand,
                                                      activation="relu"),
                       f"{name}_sq")
            g.addLayer(f"{name}_e3", ConvolutionLayer(kernelSize=(3, 3),
                                                      padding=(1, 1), nOut=expand,
                                                      activation="relu"),
                       f"{name}_sq")
            g.addVertex(f"{name}_cat", MergeVertex(), f"{name}_e1", f"{name}_e3")
            return f"{name}_cat"

        last = fire(g, "fire2", last, 16, 64)
        last = fire(g, "fire3", last, 16, 64)
        g.addLayer("pool3", SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                             stride=(2, 2)), last)
        last = fire(g, "fire4", "pool3", 32, 128)
        last = fire(g, "fire5", last, 32, 128)
        g.addLayer("pool5", SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                             stride=(2, 2)), last)
        last = fire(g, "fire6", "pool5", 48, 192)
        last = fire(g, "fire7", last, 48, 192)
        last = fire(g, "fire8", last, 64, 256)
        last = fire(g, "fire9", last, 64, 256)
        g.addLayer("drop", DropoutLayer(dropOut=0.5), last)
        g.addLayer("conv10", ConvolutionLayer(kernelSize=(1, 1),
                                              nOut=self.num_classes,
                                              activation="relu"), "drop")
        g.addLayer("gap", GlobalPoolingLayer("avg"), "conv10")
        g.addLayer("out", OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                      activation="softmax"), "gap")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class UNet(ZooModel):
    """ref: zoo.model.UNet — encoder/decoder with skip merges; output is a
    per-pixel sigmoid map."""

    def default_input_shape(self):
        return (3, 128, 128)

    def conf_builder(self) -> ComputationGraph:
        from deeplearning4j_tpu.nn.layers import LossLayer
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))

        def double_conv(g, name, inp, n):
            g.addLayer(f"{name}_c1", ConvolutionLayer(kernelSize=(3, 3),
                                                      padding=(1, 1), nOut=n,
                                                      activation="relu"), inp)
            g.addLayer(f"{name}_c2", ConvolutionLayer(kernelSize=(3, 3),
                                                      padding=(1, 1), nOut=n,
                                                      activation="relu"),
                       f"{name}_c1")
            return f"{name}_c2"

        enc_outs = []
        last = "input"
        for i, n in enumerate([32, 64, 128]):
            last = double_conv(g, f"enc{i}", last, n)
            enc_outs.append(last)
            g.addLayer(f"pool{i}", SubsamplingLayer(poolingType="max",
                                                    kernelSize=(2, 2),
                                                    stride=(2, 2)), last)
            last = f"pool{i}"
        last = double_conv(g, "bottom", last, 256)
        for i, n in zip(reversed(range(3)), [128, 64, 32]):
            g.addLayer(f"up{i}", Upsampling2D(size=2), last)
            g.addVertex(f"cat{i}", MergeVertex(), f"up{i}", enc_outs[i])
            last = double_conv(g, f"dec{i}", f"cat{i}", n)
        g.addLayer("head", ConvolutionLayer(kernelSize=(1, 1), nOut=1,
                                            activation="sigmoid"), last)
        g.addLayer("out", LossLayer(lossFunction="xent", activation="identity"),
                   "head")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class Xception(ZooModel):
    """ref: zoo.model.Xception — separable-conv stacks (middle flow
    shortened to 4 blocks for practicality; same structure)."""

    def conf_builder(self) -> ComputationGraph:
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))
        g.addLayer("stem1", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                             nOut=32, activation="relu"), "input")
        g.addLayer("stem2", ConvolutionLayer(kernelSize=(3, 3), nOut=64,
                                             activation="relu"), "stem1")
        last = "stem2"
        for i, n in enumerate([128, 256, 728]):
            pref = f"entry{i}"
            g.addLayer(f"{pref}_s1", SeparableConvolution2D(kernelSize=(3, 3),
                                                            padding=(1, 1), nOut=n,
                                                            activation="relu"), last)
            g.addLayer(f"{pref}_s2", SeparableConvolution2D(kernelSize=(3, 3),
                                                            padding=(1, 1), nOut=n,
                                                            activation="identity"),
                       f"{pref}_s1")
            g.addLayer(f"{pref}_pool", SubsamplingLayer(poolingType="max",
                                                        kernelSize=(3, 3),
                                                        stride=(2, 2),
                                                        padding=(1, 1)),
                       f"{pref}_s2")
            g.addLayer(f"{pref}_sc", ConvolutionLayer(kernelSize=(1, 1),
                                                      stride=(2, 2), nOut=n,
                                                      activation="identity"), last)
            g.addVertex(f"{pref}_add", ElementWiseVertex("Add"),
                        f"{pref}_pool", f"{pref}_sc")
            last = f"{pref}_add"
        for i in range(4):  # middle flow
            pref = f"mid{i}"
            inp = last
            cur = inp
            for j in range(3):
                g.addLayer(f"{pref}_s{j}", SeparableConvolution2D(
                    kernelSize=(3, 3), padding=(1, 1), nOut=728,
                    activation="relu"), cur)
                cur = f"{pref}_s{j}"
            g.addVertex(f"{pref}_add", ElementWiseVertex("Add"), cur, inp)
            last = f"{pref}_add"
        g.addLayer("exit_s1", SeparableConvolution2D(kernelSize=(3, 3),
                                                     padding=(1, 1), nOut=1024,
                                                     activation="relu"), last)
        g.addLayer("exit_s2", SeparableConvolution2D(kernelSize=(3, 3),
                                                     padding=(1, 1), nOut=1536,
                                                     activation="relu"), "exit_s1")
        g.addLayer("gap", GlobalPoolingLayer("avg"), "exit_s2")
        g.addLayer("out", OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                      activation="softmax"), "gap")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class FaceNetNN4Small2(ZooModel):
    """ref: zoo.model.FaceNetNN4Small2 — inception-style embedding net with
    an L2-normalized embedding output (triplet training uses the embedding)."""

    def default_input_shape(self):
        return (3, 96, 96)

    def conf_builder(self) -> ComputationGraph:
        from deeplearning4j_tpu.nn.graph import L2NormalizeVertex
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))
        g.addLayer("c1", ConvolutionLayer(kernelSize=(7, 7), stride=(2, 2),
                                          padding=(3, 3), nOut=64,
                                          activation="relu"), "input")
        g.addLayer("p1", SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                          stride=(2, 2), padding=(1, 1)), "c1")
        g.addLayer("c2", ConvolutionLayer(kernelSize=(1, 1), nOut=64,
                                          activation="relu"), "p1")
        g.addLayer("c3", ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                          nOut=192, activation="relu"), "c2")
        g.addLayer("p2", SubsamplingLayer(poolingType="max", kernelSize=(3, 3),
                                          stride=(2, 2), padding=(1, 1)), "c3")
        last = "p2"
        for i, (n1, n3r, n3) in enumerate([(64, 96, 128), (64, 96, 128),
                                           (128, 128, 256)]):
            pref = f"inc{i}"
            g.addLayer(f"{pref}_1", ConvolutionLayer(kernelSize=(1, 1), nOut=n1,
                                                     activation="relu"), last)
            g.addLayer(f"{pref}_3r", ConvolutionLayer(kernelSize=(1, 1), nOut=n3r,
                                                      activation="relu"), last)
            g.addLayer(f"{pref}_3", ConvolutionLayer(kernelSize=(3, 3),
                                                     padding=(1, 1), nOut=n3,
                                                     activation="relu"),
                       f"{pref}_3r")
            g.addVertex(f"{pref}_cat", MergeVertex(), f"{pref}_1", f"{pref}_3")
            last = f"{pref}_cat"
        g.addLayer("gap", GlobalPoolingLayer("avg"), last)
        g.addLayer("embed", DenseLayer(nOut=128, activation="identity"), "gap")
        g.addVertex("l2", L2NormalizeVertex(), "embed")
        g.addLayer("out", OutputLayer(nOut=self.num_classes, lossFunction="mcxent",
                                      activation="softmax"), "l2")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class TextGenerationLSTM(ZooModel):
    """ref: zoo.model.TextGenerationLSTM — char-level 2-layer LSTM."""

    def __init__(self, vocab_size: int = 77, **kw):
        self.vocab_size = vocab_size
        super().__init__(num_classes=vocab_size, **kw)

    def default_input_shape(self):
        return (self.vocab_size, 60)

    def conf_builder(self) -> MultiLayerNetwork:
        n_in, t = self.input_shape
        conf = (NeuralNetConfiguration.Builder()
                .seed(self.seed).updater(self.updater).weightInit("xavier")
                .gradientNormalization("clip_value", 5.0)
                .list()
                .layer(LSTM(nOut=256))
                .layer(LSTM(nOut=256))
                .layer(RnnOutputLayer(nOut=self.vocab_size, lossFunction="mcxent",
                                      activation="softmax"))
                .setInputType(InputType.recurrent(n_in, t))
                .build())
        return MultiLayerNetwork(conf)


class TinyYOLO(ZooModel):
    """ref: zoo.model.TinyYOLO (BASELINE config #2) — darknet-tiny backbone
    + Yolo2OutputLayer with the reference's VOC anchor priors."""

    ANCHORS = [[1.08, 1.19], [3.42, 4.41], [6.63, 11.38], [9.42, 5.11],
               [16.62, 10.52]]

    def __init__(self, num_classes: int = 20, **kw):
        super().__init__(num_classes=num_classes, **kw)

    def default_input_shape(self):
        return (3, 416, 416)

    def conf_builder(self) -> MultiLayerNetwork:
        from deeplearning4j_tpu.nn.objdetect import Yolo2OutputLayer
        c, h, w = self.input_shape
        n_boxes = len(self.ANCHORS)
        b = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .list())

        def conv_bn(b, n_out):
            b = b.layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                         nOut=n_out, activation="identity"))
            b = b.layer(BatchNormalization())
            return b.layer(ActivationLayer("leakyrelu"))

        for i, n_out in enumerate([16, 32, 64, 128, 256]):
            b = conv_bn(b, n_out)
            b = b.layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                         stride=(2, 2)))
        b = conv_bn(b, 512)
        b = b.layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                     stride=(1, 1), padding=(1, 1),
                                     convolutionMode="same"))
        b = conv_bn(b, 1024)
        b = conv_bn(b, 1024)
        b = b.layer(ConvolutionLayer(kernelSize=(1, 1),
                                     nOut=n_boxes * (5 + self.num_classes),
                                     activation="identity"))
        b = (b.layer(Yolo2OutputLayer(boundingBoxPriors=self.ANCHORS))
             .setInputType(InputType.convolutional(h, w, c)))
        return MultiLayerNetwork(b.build())


class YOLO2(ZooModel):
    """ref: zoo.model.YOLO2 (BASELINE config #2) — Darknet19 backbone +
    passthrough route + Yolo2OutputLayer, COCO anchors."""

    ANCHORS = [[0.57273, 0.677385], [1.87446, 2.06253], [3.33843, 5.47434],
               [7.88282, 3.52778], [9.77052, 9.16828]]

    def __init__(self, num_classes: int = 80, **kw):
        super().__init__(num_classes=num_classes, **kw)

    def default_input_shape(self):
        return (3, 416, 416)

    def conf_builder(self) -> ComputationGraph:
        from deeplearning4j_tpu.nn.graph import PreprocessorVertex
        from deeplearning4j_tpu.nn.objdetect import Yolo2OutputLayer
        c, h, w = self.input_shape
        n_boxes = len(self.ANCHORS)
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))

        def conv_bn(g, name, inp, n_out, k=3):
            pad = (k // 2, k // 2)
            g.addLayer(f"{name}_c", ConvolutionLayer(kernelSize=(k, k),
                                                     padding=pad, nOut=n_out,
                                                     activation="identity"), inp)
            g.addLayer(f"{name}_bn", BatchNormalization(), f"{name}_c")
            g.addLayer(name, ActivationLayer("leakyrelu"), f"{name}_bn")
            return name

        last = conv_bn(g, "c1", "input", 32)
        g.addLayer("p1", SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                          stride=(2, 2)), last)
        last = conv_bn(g, "c2", "p1", 64)
        g.addLayer("p2", SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                          stride=(2, 2)), last)
        spec = [(128, 64, "p3"), (256, 128, "p4")]
        inp = "p2"
        for big, small, pool in spec:
            a = conv_bn(g, f"{pool}a", inp, big)
            bmid = conv_bn(g, f"{pool}b", a, small, k=1)
            cend = conv_bn(g, f"{pool}c", bmid, big)
            g.addLayer(pool, SubsamplingLayer(poolingType="max",
                                              kernelSize=(2, 2), stride=(2, 2)),
                       cend)
            inp = pool
        # stage 5 (ends at 26x26 with 512 ch — the passthrough source)
        a = conv_bn(g, "s5a", "p4", 512)
        bmid = conv_bn(g, "s5b", a, 256, k=1)
        cend = conv_bn(g, "s5c", bmid, 512)
        d = conv_bn(g, "s5d", cend, 256, k=1)
        route = conv_bn(g, "s5e", d, 512)
        g.addLayer("p5", SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                          stride=(2, 2)), route)
        # stage 6 at 13x13
        a = conv_bn(g, "s6a", "p5", 1024)
        bmid = conv_bn(g, "s6b", a, 512, k=1)
        cend = conv_bn(g, "s6c", bmid, 1024)
        d = conv_bn(g, "s6d", cend, 512, k=1)
        e = conv_bn(g, "s6e", d, 1024)
        f = conv_bn(g, "det1", e, 1024)
        f = conv_bn(g, "det2", f, 1024)
        # passthrough: space_to_depth(route 26x26x512 -> 13x13x2048), concat
        from deeplearning4j_tpu.nn.preprocessors import Preprocessor

        class _SpaceToDepth(Preprocessor):
            def __call__(self, x):
                from deeplearning4j_tpu.ops.convolution import space_to_depth
                return space_to_depth(x, 2)

            def output_type(self, it):
                return InputType.convolutional(it.height // 2, it.width // 2,
                                               it.channels * 4)

        g.addVertex("passthrough", PreprocessorVertex(_SpaceToDepth()), route)
        g.addVertex("route_cat", MergeVertex(), "passthrough", f)
        last = conv_bn(g, "head", "route_cat", 1024)
        g.addLayer("conv_out", ConvolutionLayer(
            kernelSize=(1, 1), nOut=n_boxes * (5 + self.num_classes),
            activation="identity"), last)
        g.addLayer("yolo", Yolo2OutputLayer(boundingBoxPriors=self.ANCHORS),
                   "conv_out")
        g.setOutputs("yolo")
        return ComputationGraph(g.build())


class InceptionResNetV1(ZooModel):
    """ref: zoo.model.InceptionResNetV1 (the FaceNet backbone) — stem +
    residual inception blocks A/B/C with residual scaling via ScaleVertex,
    reduction blocks between stages (block counts shortened 5/10/5 ->
    2/3/2 for practicality; identical structure)."""

    def default_input_shape(self):
        return (3, 160, 160)

    def _scaled_residual(self, g, pref, inp, branches, n_out, scale):
        from deeplearning4j_tpu.nn.graph import ScaleVertex
        outs = []
        for bi, branch in enumerate(branches):
            cur = inp
            for li, (k, n, s, p) in enumerate(branch):
                g.addLayer(f"{pref}_b{bi}_c{li}",
                           ConvolutionLayer(kernelSize=(k, k), stride=(s, s),
                                            padding=(p, p), nOut=n,
                                            activation="relu"), cur)
                cur = f"{pref}_b{bi}_c{li}"
            outs.append(cur)
        if len(outs) > 1:
            g.addVertex(f"{pref}_cat", MergeVertex(), *outs)
            cat = f"{pref}_cat"
        else:
            cat = outs[0]
        g.addLayer(f"{pref}_up", ConvolutionLayer(kernelSize=(1, 1),
                                                  nOut=n_out,
                                                  activation="identity"), cat)
        g.addVertex(f"{pref}_scale", ScaleVertex(scale), f"{pref}_up")
        g.addVertex(f"{pref}_add", ElementWiseVertex("Add"), inp,
                    f"{pref}_scale")
        g.addLayer(f"{pref}_out", ActivationLayer("relu"), f"{pref}_add")
        return f"{pref}_out"

    def conf_builder(self) -> ComputationGraph:
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))
        # stem (ref: 3x conv -> maxpool -> 2x conv -> conv stride 2)
        g.addLayer("s1", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                          nOut=32, activation="relu"), "input")
        g.addLayer("s2", ConvolutionLayer(kernelSize=(3, 3), nOut=32,
                                          activation="relu"), "s1")
        g.addLayer("s3", ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                          nOut=64, activation="relu"), "s2")
        g.addLayer("s_pool", SubsamplingLayer(poolingType="max",
                                              kernelSize=(3, 3), stride=(2, 2)),
                   "s3")
        g.addLayer("s4", ConvolutionLayer(kernelSize=(1, 1), nOut=80,
                                          activation="relu"), "s_pool")
        g.addLayer("s5", ConvolutionLayer(kernelSize=(3, 3), nOut=192,
                                          activation="relu"), "s4")
        g.addLayer("s6", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                          nOut=256, activation="relu"), "s5")
        last = "s6"
        # inception-resnet-A x2 (scale 0.17)
        for i in range(2):
            last = self._scaled_residual(
                g, f"irA{i}", last,
                branches=[[(1, 32, 1, 0)],
                          [(1, 32, 1, 0), (3, 32, 1, 1)],
                          [(1, 32, 1, 0), (3, 32, 1, 1), (3, 32, 1, 1)]],
                n_out=256, scale=0.17)
        # reduction-A
        g.addLayer("redA_c", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                              nOut=384, activation="relu"),
                   last)
        g.addLayer("redA_p", SubsamplingLayer(poolingType="max",
                                              kernelSize=(3, 3),
                                              stride=(2, 2)), last)
        g.addVertex("redA", MergeVertex(), "redA_c", "redA_p")
        last = "redA"
        # inception-resnet-B x3 (scale 0.10), input channels 640
        for i in range(3):
            last = self._scaled_residual(
                g, f"irB{i}", last,
                branches=[[(1, 128, 1, 0)],
                          [(1, 128, 1, 0), (7, 128, 1, 3)]],
                n_out=640, scale=0.10)
        # reduction-B
        g.addLayer("redB_c", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                              nOut=256, activation="relu"),
                   last)
        g.addLayer("redB_p", SubsamplingLayer(poolingType="max",
                                              kernelSize=(3, 3),
                                              stride=(2, 2)), last)
        g.addVertex("redB", MergeVertex(), "redB_c", "redB_p")
        last = "redB"
        # inception-resnet-C x2 (scale 0.20), input channels 896
        for i in range(2):
            last = self._scaled_residual(
                g, f"irC{i}", last,
                branches=[[(1, 192, 1, 0)],
                          [(1, 192, 1, 0), (3, 192, 1, 1)]],
                n_out=896, scale=0.20)
        g.addLayer("gap", GlobalPoolingLayer("avg"), last)
        g.addLayer("bottleneck", DenseLayer(nOut=128, activation="identity"),
                   "gap")   # the FaceNet embedding layer
        from deeplearning4j_tpu.nn.graph import L2NormalizeVertex
        g.addVertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.addLayer("out", OutputLayer(nOut=self.num_classes,
                                      lossFunction="mcxent",
                                      activation="softmax"), "embeddings")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class NASNet(ZooModel):
    """ref: zoo.model.NASNet (NASNet-A mobile) — separable-conv normal
    cells with residual adds and reduction cells between stages (the
    learned 5-op cell simplified to its dominant separable-conv pair
    structure; 4/4/4 -> 2/2/2 cells for practicality)."""

    PENULTIMATE = 1056

    def default_input_shape(self):
        return (3, 224, 224)

    def _normal_cell(self, g, pref, inp, filters):
        # two stacked sep-convs per branch + residual add (the repeated
        # motif of the learned NASNet-A normal cell)
        g.addLayer(f"{pref}_adj", ConvolutionLayer(kernelSize=(1, 1),
                                                   nOut=filters,
                                                   activation="relu"), inp)
        a = f"{pref}_adj"
        g.addLayer(f"{pref}_s1a", SeparableConvolution2D(
            kernelSize=(5, 5), padding=(2, 2), nOut=filters,
            activation="relu"), a)
        g.addLayer(f"{pref}_s1b", SeparableConvolution2D(
            kernelSize=(3, 3), padding=(1, 1), nOut=filters,
            activation="identity"), f"{pref}_s1a")
        g.addVertex(f"{pref}_add1", ElementWiseVertex("Add"), f"{pref}_s1b", a)
        g.addLayer(f"{pref}_s2a", SeparableConvolution2D(
            kernelSize=(3, 3), padding=(1, 1), nOut=filters,
            activation="relu"), f"{pref}_add1")
        g.addVertex(f"{pref}_add2", ElementWiseVertex("Add"),
                    f"{pref}_s2a", f"{pref}_add1")
        g.addLayer(f"{pref}_out", ActivationLayer("relu"), f"{pref}_add2")
        return f"{pref}_out"

    def _reduction_cell(self, g, pref, inp, filters):
        g.addLayer(f"{pref}_s5", SeparableConvolution2D(
            kernelSize=(5, 5), stride=(2, 2), padding=(2, 2), nOut=filters,
            activation="relu"), inp)
        g.addLayer(f"{pref}_s7", SeparableConvolution2D(
            kernelSize=(7, 7), stride=(2, 2), padding=(3, 3), nOut=filters,
            activation="relu"), inp)
        g.addLayer(f"{pref}_mp", SubsamplingLayer(
            poolingType="max", kernelSize=(3, 3), stride=(2, 2),
            padding=(1, 1)), inp)
        g.addLayer(f"{pref}_mpc", ConvolutionLayer(
            kernelSize=(1, 1), nOut=filters, activation="relu"), f"{pref}_mp")
        g.addVertex(f"{pref}_add", ElementWiseVertex("Add"),
                    f"{pref}_s5", f"{pref}_s7")
        g.addVertex(f"{pref}_cat", MergeVertex(), f"{pref}_add", f"{pref}_mpc")
        return f"{pref}_cat"

    def conf_builder(self) -> ComputationGraph:
        c, h, w = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("relu")
             .graphBuilder()
             .addInputs("input")
             .setInputTypes(InputType.convolutional(h, w, c)))
        g.addLayer("stem", ConvolutionLayer(kernelSize=(3, 3), stride=(2, 2),
                                            nOut=32, activation="relu"),
                   "input")
        g.addLayer("stem_bn", BatchNormalization(), "stem")
        last = "stem_bn"
        filters = 44                     # NASNet-A mobile penultimate path
        for stage in range(3):
            for i in range(2):
                last = self._normal_cell(g, f"n{stage}_{i}", last, filters)
            if stage < 2:
                last = self._reduction_cell(g, f"r{stage}", last, filters * 2)
                filters *= 2
        g.addLayer("head", ConvolutionLayer(kernelSize=(1, 1),
                                            nOut=self.PENULTIMATE,
                                            activation="relu"), last)
        g.addLayer("gap", GlobalPoolingLayer("avg"), "head")
        g.addLayer("out", OutputLayer(nOut=self.num_classes,
                                      lossFunction="mcxent",
                                      activation="softmax"), "gap")
        g.setOutputs("out")
        return ComputationGraph(g.build())


class Ouro(ZooModel):
    """Ouro, a looped language model (ByteDance 2025, "Scaling Latent
    Reasoning via Looped Language Models", arXiv:2510.25741; defaults:
    Ouro-2.6B's config.json). ``num_layers`` decoder layers held ONCE and
    run ``total_ut_steps`` times a forward pass through a ``LoopVertex``:
    token embedding, then per pass the layers (sandwich RMSNorm: a norm
    before and after attention and before and after the SwiGLU MLP, each
    sub-block added to the stream) and the final norm, whose output the
    next pass starts from; after every pass an untied head and an exit
    gate, trained with the exit-weighted loss (``LoopedLMOutputLayer``).
    Trains on ``DataSet(int32 tokens [N, T], int32 next tokens [N, T])``.

    The published 48 layers hold 2.67 B parameters: 40 GiB with Adam's
    state, which no single chip trains replicated (the cost model's E120,
    rightly). The cost gates therefore judge the six-layer stage that the
    benchmark runs on one chip (``chipbench/configs/ouro-2.6b-l6-bf16``);
    the structural lints take the whole model."""

    cost_gate_kwargs = {"num_layers": 6}

    def __init__(self, num_layers: int = 48, hidden_size: int = 2048,
                 num_heads: int = 16, head_dim: int = 128,
                 intermediate_size: int = 5632, vocab_size: int = 49152,
                 total_ut_steps: int = 4, rms_norm_eps: float = 1e-6,
                 rope_theta: float = 1e6, seq_len: int = 4096,
                 beta: float = 0.1, **kw):
        self.num_layers, self.hidden_size = int(num_layers), int(hidden_size)
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.intermediate_size = int(intermediate_size)
        self.vocab_size, self.seq_len = int(vocab_size), int(seq_len)
        self.total_ut_steps = int(total_ut_steps)
        self.rms_norm_eps, self.rope_theta = rms_norm_eps, rope_theta
        self.beta = beta
        kw.setdefault("updater", updaters.Adam(3e-4, beta2=0.95))
        super().__init__(num_classes=vocab_size, **kw)

    def default_input_shape(self):
        return (self.vocab_size, self.seq_len)

    def conf_builder(self) -> ComputationGraph:
        vocab, seq_len = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("xavier")
             .graphBuilder())
        g.addInputs("tokens")
        g.setInputTypes(InputType.recurrent(vocab, seq_len))
        g.addLayer("embed", EmbeddingSequenceLayer(nOut=self.hidden_size),
                   "tokens")
        g.beginLoop("ut", "embed", steps=self.total_ut_steps)
        norm = lambda: RMSNorm(eps=self.rms_norm_eps)   # noqa: E731
        h = "ut"
        for i in range(self.num_layers):
            p = f"l{i}_"
            g.addLayer(p + "n1", norm(), h)
            g.addLayer(p + "attn", CausalSelfAttentionLayer(
                nHeads=self.num_heads, headSize=self.head_dim,
                ropeTheta=self.rope_theta), p + "n1")
            g.addLayer(p + "n2", norm(), p + "attn")
            g.addVertex(p + "add1", ElementWiseVertex("Add"), h, p + "n2")
            g.addLayer(p + "n3", norm(), p + "add1")
            g.addLayer(p + "mlp", GatedMLP(nHidden=self.intermediate_size),
                       p + "n3")
            g.addLayer(p + "n4", norm(), p + "mlp")
            g.addVertex(p + "add2", ElementWiseVertex("Add"), p + "add1",
                        p + "n4")
            h = p + "add2"
        g.addLayer("fnorm", norm(), h)
        g.endLoop("fnorm")
        g.addLayer("lm", LoopedLMOutputLayer(nOut=vocab, beta=self.beta),
                   "ut")
        g.setOutputs("lm")
        return ComputationGraph(g.build())



class Xing4(ZooModel):
    """Xing4.0-29B-A4B (XingChen-AGI 2026; defaults: its config.json), a
    DeepSeek-V3-style sparse decoder under manifold-constrained
    hyper-connections: token embedding copied into ``hc_mult`` residual
    streams; ``first_k_dense`` leading layers of latent attention and a
    dense SwiGLU MLP, then ``num_layers - first_k_dense`` of latent
    attention and sparse experts (a sigmoid-scored, bias-selected router
    over ``n_routed_experts``, ``num_experts_per_tok`` selected, a shared
    expert); every sub-block reads one mix of the streams and writes back
    through per-token maps whose stream-to-stream part is Sinkhorn-
    projected (arXiv:2512.24880); the streams summed, a final norm, an
    untied head; ``num_nextn_predict_layers`` multi-token-prediction
    modules (arXiv:2412.19437 §2.2), each one expert layer fed the main
    hidden states joined with the next token's embedding, sharing
    embedding, final norm and head with the main model (``tiedWith``).
    ``held_experts`` lists the routed experts THIS chip holds (default:
    all): one chip's share under expert parallelism, routed over all,
    the absent experts' part left out; ``keep_selected`` rows of each
    expert layer's last selection are kept in its state
    (``SparseExpertsLayer(keepSelected=...)``). The plain stack is rematerialised
    a sub-block at a time in a train step (``rematerializeStack``).
    Trains on ``DataSet(int32 tokens [N, T], int32 next tokens [N, T])``.

    The published 40 layers hold 29 B parameters, which no chip trains
    alone; the cost gates judge the cut the benchmark runs on one chip
    (``chipbench/configs/xing4.0-29b-a4b-l5-bf16``: one dense and four
    expert layers, 8 of 64 experts, an eighth of the vocabulary)."""

    cost_gate_kwargs = {"num_layers": 5, "first_k_dense": 1,
                        "held_experts": list(range(8)),
                        "vocab_size": 16384}

    def __init__(self, num_layers: int = 40, first_k_dense: int = 2,
                 hidden_size: int = 3584, num_heads: int = 32,
                 q_lora_rank: int = 768, kv_lora_rank: int = 512,
                 qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                 v_head_dim: int = 128, intermediate_size: int = 9216,
                 moe_intermediate_size: int = 1024,
                 n_routed_experts: int = 64, held_experts=None,
                 num_experts_per_tok: int = 4,
                 routed_scaling_factor: float = 2.0, hc_mult: int = 4,
                 hc_sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
                 mhc_h_res_clamp=(-30.0, 30.0), rms_norm_eps: float = 1e-6,
                 rope_theta: float = 10000.0, rope_scaling: dict = None,
                 vocab_size: int = 131072, seq_len: int = 4096,
                 num_nextn_predict_layers: int = 1, mtp_weight: float = 0.3,
                 keep_selected: int = 0, **kw):
        self.num_layers, self.first_k_dense = int(num_layers), \
            int(first_k_dense)
        self.hidden_size, self.num_heads = int(hidden_size), int(num_heads)
        self.attention = dict(
            nHeads=self.num_heads, qLoraRank=q_lora_rank,
            kvLoraRank=kv_lora_rank, qkNopeHeadDim=qk_nope_head_dim,
            qkRopeHeadDim=qk_rope_head_dim, vHeadDim=v_head_dim,
            ropeTheta=rope_theta, eps=rms_norm_eps,
            ropeScaling=rope_scaling if rope_scaling is not None else dict(
                factor=64, original_max_position_embeddings=4096,
                beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1))
        self.intermediate_size = int(intermediate_size)
        self.experts = dict(
            nExperts=n_routed_experts, nExpertsPerTok=num_experts_per_tok,
            nHidden=moe_intermediate_size, heldExperts=held_experts,
            routedScalingFactor=routed_scaling_factor,
            keepSelected=keep_selected)
        self.streams = dict(nStreams=hc_mult, eps=hc_eps)
        self.write = dict(sinkhornIters=hc_sinkhorn_iters,
                          clampMin=mhc_h_res_clamp[0],
                          clampMax=mhc_h_res_clamp[1], **self.streams)
        self.rms_norm_eps = rms_norm_eps
        self.vocab_size, self.seq_len = int(vocab_size), int(seq_len)
        self.n_mtp, self.mtp_weight = int(num_nextn_predict_layers), \
            float(mtp_weight)
        kw.setdefault("updater", updaters.Adam(3e-4, beta2=0.95))
        super().__init__(num_classes=vocab_size, **kw)

    def default_input_shape(self):
        return (self.vocab_size, self.seq_len)

    def _layer(self, g, p, h, dense: bool):
        """One decoder layer on the streams ``h``; returns its output's
        name. A sub-block is read, norm, the block, write."""
        norm = lambda: RMSNorm(eps=self.rms_norm_eps)   # noqa: E731
        block = (GatedMLP(nHidden=self.intermediate_size) if dense
                 else SparseExpertsLayer(**self.experts))
        for tag, layer in (("1", LatentAttentionLayer(**self.attention)),
                           ("2", block)):
            name = "attn" if tag == "1" else ("mlp" if dense else "moe")
            g.addLayer(p + "hr" + tag, HyperConnectionRead(**self.streams),
                       h)
            g.addLayer(p + "n" + tag, norm(), p + "hr" + tag)
            g.addLayer(p + name, layer, p + "n" + tag)
            g.addLayer(p + "hw" + tag, HyperConnectionWrite(**self.write),
                       h, p + name)
            h = p + "hw" + tag
        return h

    def conf_builder(self) -> ComputationGraph:
        vocab, seq_len = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("xavier")
             .graphBuilder())
        g.addInputs("tokens")
        g.setInputTypes(InputType.recurrent(vocab, seq_len))
        g.rematerializeStack()
        g.addLayer("embed", EmbeddingSequenceLayer(nOut=self.hidden_size),
                   "tokens")
        g.addLayer("hc_in", HyperConnectionIn(**self.streams), "embed")
        h = "hc_in"
        for i in range(self.num_layers):
            h = self._layer(g, f"l{i}_", h, dense=i < self.first_k_dense)
        g.addLayer("hc_out", HyperConnectionOut(**self.streams), h)
        g.addLayer("fnorm", RMSNorm(eps=self.rms_norm_eps), "hc_out")
        heads, prev = ["fnorm"], "hc_out"
        if self.n_mtp:
            g.addVertex("next", LabelsVertex(0))
        for d in range(1, self.n_mtp + 1):
            # module d reads token i + d's embedding at position i: the
            # labels moved d - 1 places left (d = 1: as they come)
            if d > 1:
                raise ValueError("Xing4: one multi-token-prediction module "
                                 "is what the published model has")
            p = "mtp_" if self.n_mtp == 1 else f"mtp{d}_"
            g.addLayer(p + "embed", EmbeddingSequenceLayer(
                nOut=self.hidden_size, tiedWith="embed"), "next")
            g.addLayer(p + "join", MTPJoinLayer(eps=self.rms_norm_eps),
                       prev, p + "embed")
            g.addLayer(p + "hc_in", HyperConnectionIn(**self.streams),
                       p + "join")
            h = self._layer(g, p, p + "hc_in", dense=False)
            g.addLayer(p + "hc_out", HyperConnectionOut(**self.streams), h)
            g.addLayer(p + "fnorm", RMSNorm(eps=self.rms_norm_eps,
                                            tiedWith="fnorm"), p + "hc_out")
            heads.append(p + "fnorm")
            prev = p + "hc_out"
        g.addLayer("lm", MTPLMOutputLayer(nOut=vocab,
                                          mtpWeight=self.mtp_weight), *heads)
        g.setOutputs("lm")
        return ComputationGraph(g.build())


class LFM2(ZooModel):
    """LFM2-24B-A2B (Liquid AI; defaults: its config.json), a hybrid
    decoder whose layers differ in kind by a pattern: ``layer_types[l]``
    says whether layer ``l`` mixes tokens by a gated short convolution
    (``"conv"``: :class:`GatedShortConvLayer`, kernel ``conv_L_cache``)
    or by grouped-query attention (``"full_attention"``:
    ``num_attention_heads`` query heads over ``num_key_value_heads``
    key/value heads, an RMS norm on every head of q and k, rotary
    positions); the first ``num_dense_layers`` have a dense SwiGLU MLP,
    the others sparse experts WITHOUT a shared expert (a sigmoid-scored,
    bias-selected router over ``num_experts``, ``num_experts_per_tok``
    selected, gates normalised over the selected). Each sub-block is
    ``h + F(RMSNorm(h))``; a final norm; the head is the embedding's table
    (``tiedWith``). ``layers`` lists the published layers held and run
    (default: all of ``layer_types``): a kept layer keeps its own kind.
    ``held_experts`` lists the routed experts THIS chip holds (default:
    all), ``keep_selected`` rows of each expert layer's last selection are
    kept in its state. The stack is rematerialised a sub-block at a time
    in a train step (``rematerializeStack``). Trains on ``DataSet(int32
    tokens [N, T], int32 next tokens [N, T])``.

    The published 40 layers hold 24 B parameters, which no chip trains
    alone; the cost gates judge the cut the benchmark runs on one chip
    (``chipbench/configs/lfm2-24b-a2b-l5-bf16``: one dense layer and one
    whole period of four expert layers, 8 of 64 experts, an eighth of the
    vocabulary)."""

    PERIOD = ("conv", "conv", "full_attention", "conv")
    cost_gate_kwargs = {"layers": [0, 2, 3, 4, 5], "num_dense_layers": 1,
                        "held_experts": list(range(8)), "vocab_size": 8192,
                        "seq_len": 8192}

    def __init__(self, layer_types=None, layers=None,
                 num_dense_layers: int = 2, hidden_size: int = 2048,
                 num_attention_heads: int = 32, num_key_value_heads: int = 8,
                 head_dim: int = None, conv_L_cache: int = 3,
                 intermediate_size: int = 11776,
                 moe_intermediate_size: int = 1536, num_experts: int = 64,
                 held_experts=None, num_experts_per_tok: int = 4,
                 routed_scaling_factor: float = 1.0, norm_eps: float = 1e-5,
                 rope_theta: float = 1e6, vocab_size: int = 65536,
                 seq_len: int = 8192, keep_selected: int = 0, **kw):
        self.layer_types = list(layer_types) if layer_types is not None \
            else list(self.PERIOD) * 10
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"LFM2: layer_types may say 'conv' or "
                             f"'full_attention', not {sorted(unknown)}")
        self.layers = list(range(len(self.layer_types))) if layers is None \
            else [int(i) for i in layers]
        self.num_dense_layers = int(num_dense_layers)
        self.hidden_size = int(hidden_size)
        self.attention = dict(
            nHeads=int(num_attention_heads),
            nKVHeads=int(num_key_value_heads),
            headSize=int(head_dim or hidden_size // num_attention_heads),
            ropeTheta=rope_theta, qkNorm=True, qkNormEps=norm_eps)
        self.conv = dict(kernelSize=int(conv_L_cache))
        self.intermediate_size = int(intermediate_size)
        self.experts = dict(
            nExperts=num_experts, nExpertsPerTok=num_experts_per_tok,
            nHidden=moe_intermediate_size, heldExperts=held_experts,
            routedScalingFactor=routed_scaling_factor, nSharedExperts=0,
            keepSelected=keep_selected)
        self.norm_eps = norm_eps
        self.vocab_size, self.seq_len = int(vocab_size), int(seq_len)
        kw.setdefault("updater", updaters.Adam(3e-4, beta2=0.95))
        super().__init__(num_classes=vocab_size, **kw)

    def default_input_shape(self):
        return (self.vocab_size, self.seq_len)

    def conf_builder(self) -> ComputationGraph:
        vocab, seq_len = self.input_shape
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed).updater(self.updater).weightInit("xavier")
             .graphBuilder())
        g.addInputs("tokens")
        g.setInputTypes(InputType.recurrent(vocab, seq_len))
        g.rematerializeStack()
        g.addLayer("embed", EmbeddingSequenceLayer(nOut=self.hidden_size),
                   "tokens")
        norm = lambda: RMSNorm(eps=self.norm_eps)   # noqa: E731
        h = "embed"
        # the n-th layer held is "l<n>_": leading dense layers count once
        for n, i in enumerate(self.layers):
            p = f"l{n}_"
            conv = self.layer_types[i] == "conv"
            dense = n < self.num_dense_layers
            mix, ff = p + ("conv" if conv else "attn"), \
                p + ("mlp" if dense else "moe")
            g.addLayer(p + "n1", norm(), h)
            g.addLayer(mix, GatedShortConvLayer(**self.conv) if conv
                       else CausalSelfAttentionLayer(**self.attention),
                       p + "n1")
            g.addVertex(p + "add1", ElementWiseVertex("Add"), h, mix)
            g.addLayer(p + "n2", norm(), p + "add1")
            g.addLayer(ff, GatedMLP(nHidden=self.intermediate_size) if dense
                       else SparseExpertsLayer(**self.experts), p + "n2")
            g.addVertex(p + "add2", ElementWiseVertex("Add"), p + "add1", ff)
            h = p + "add2"
        g.addLayer("fnorm", norm(), h)
        g.addLayer("lm", MTPLMOutputLayer(nOut=vocab, tiedWith="embed"),
                   "fnorm")
        g.setOutputs("lm")
        return ComputationGraph(g.build())


#: Name -> class registry of every shipped architecture (ref:
#: ZooModel.select-by-name in the reference's zoo). The analysis CLI's
#: ``--zoo`` mode lints each of these; ``all_zoo_models()`` instantiates
#: them with default constructors.
ZOO_MODELS = {cls.__name__: cls for cls in
              (LeNet, SimpleCNN, AlexNet, VGG16, VGG19, ResNet50,
               Darknet19, SqueezeNet, UNet, Xception, FaceNetNN4Small2,
               TextGenerationLSTM, TinyYOLO, YOLO2, InceptionResNetV1,
               NASNet, Ouro, Xing4, LFM2)}


def all_zoo_models():
    """[(name, uninitialized network)] for every registered architecture
    — configs only (``conf_builder``), no parameter allocation."""
    return [(name, cls().conf_builder()) for name, cls in ZOO_MODELS.items()]
