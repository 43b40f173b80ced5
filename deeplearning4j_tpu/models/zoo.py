"""Model zoo.

Reference: dl4j-zoo ``org.deeplearning4j.zoo.model.{LeNet, AlexNet, VGG16,
VGG19, ResNet50, SqueezeNet, Darknet19, TinyYOLO, UNet, SimpleCNN,
TextGenerationLSTM, ...}`` (SURVEY.md §2.3). Architectures follow the
reference's published configurations; ``init_pretrained`` loads
``PretrainedType``-keyed ModelSerializer containers from a LOCAL weight
cache (``DL4J_TPU_PRETRAINED_DIR``) — this environment has no egress, so a
missing entry raises with the exact path to populate instead of
downloading (see ``ZooModel``).

All CNN zoo models use NCHW like the reference; ResNet-50 is the
ComputationGraph flagship (north-star config 2).
"""

from __future__ import annotations

import math
import os
from typing import Optional, Sequence, Tuple

from ..learning.updaters import Adam, AdamW, Nesterovs
from ..nn.conf import layers as L
from ..nn.conf.builder import NeuralNetConfiguration
from ..nn.conf.inputs import InputType
from ..nn.graph import (ComputationGraph, ComputationGraphConfiguration,
                        ElementWiseVertex, MergeVertex, ScaleVertex)
from ..nn.multilayer import MultiLayerNetwork


class PretrainedType:
    """Reference org.deeplearning4j.zoo.PretrainedType."""

    IMAGENET = "imagenet"
    MNIST = "mnist"
    CIFAR10 = "cifar10"
    VGGFACE = "vggface"


class ZooModel:
    """Base (reference org.deeplearning4j.zoo.ZooModel).

    ``init_pretrained`` follows the reference's API shape (a
    ``PretrainedType``-keyed weight cache + ModelSerializer container) with
    ONE documented divergence: the reference downloads missing weights
    from Konduit's CDN; this environment has no network egress (SURVEY
    §0), so the cache is local-only — a missing entry raises with the
    exact path where a checkpoint must be placed. The cache directory is
    ``$DL4J_TPU_PRETRAINED_DIR`` (default ``~/.deeplearning4j_tpu/
    pretrained``); entries are ``<ModelClass>_<type>.zip`` ModelSerializer
    containers (write one with ``util.model_serializer.write_model``)."""

    def init(self):
        raise NotImplementedError

    @staticmethod
    def pretrained_cache_dir() -> str:
        return os.environ.get(
            "DL4J_TPU_PRETRAINED_DIR",
            os.path.join(os.path.expanduser("~"),
                         ".deeplearning4j_tpu", "pretrained"))

    def pretrained_path(self, kind: str = PretrainedType.IMAGENET) -> str:
        return os.path.join(self.pretrained_cache_dir(),
                            f"{type(self).__name__}_{kind}.zip")

    def pretrained_available(self,
                             kind: str = PretrainedType.IMAGENET) -> bool:
        return os.path.exists(self.pretrained_path(kind))

    def init_pretrained(self, kind: str = PretrainedType.IMAGENET):
        from ..util.model_serializer import restore_model

        path = self.pretrained_path(kind)
        if not os.path.exists(path):
            raise RuntimeError(
                f"{type(self).__name__}: no pretrained {kind!r} weights in "
                f"the local cache ({path}). This environment has no "
                "network egress, so automatic download is unavailable — "
                "place a ModelSerializer container at that path (or set "
                "DL4J_TPU_PRETRAINED_DIR), or train from scratch via "
                "init().")
        return restore_model(path)

    initPretrained = init_pretrained


class LeNet(ZooModel):
    """reference zoo.model.LeNet (MNIST)."""

    def __init__(self, num_classes: int = 10, seed: int = 123):
        self.num_classes = num_classes
        self.seed = seed

    def init(self) -> MultiLayerNetwork:
        conf = (NeuralNetConfiguration.builder()
                .seed(self.seed)
                .updater(Nesterovs(learning_rate=0.01, momentum=0.9))
                .activation("relu").weight_init("xavier")
                .list()
                .layer(L.ConvolutionLayer(n_out=20, kernel_size=(5, 5)))
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.ConvolutionLayer(n_out=50, kernel_size=(5, 5)))
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.DenseLayer(n_out=500))
                .layer(L.OutputLayer(n_out=self.num_classes, loss="mcxent",
                                     activation="softmax"))
                .set_input_type(InputType.convolutional(28, 28, 1))
                .build())
        return MultiLayerNetwork(conf).init()


class SimpleCNN(ZooModel):
    """reference zoo.model.SimpleCNN."""

    def __init__(self, num_classes: int = 10, input_shape=(3, 48, 48), seed: int = 123):
        self.num_classes = num_classes
        self.input_shape = input_shape
        self.seed = seed

    def init(self) -> MultiLayerNetwork:
        c, h, w = self.input_shape
        conf = (NeuralNetConfiguration.builder()
                .seed(self.seed).updater(Adam(5e-4)).activation("relu")
                .list()
                .layer(L.ConvolutionLayer(n_out=16, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.BatchNormalization())
                .layer(L.ConvolutionLayer(n_out=16, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.BatchNormalization())
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.ConvolutionLayer(n_out=32, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.BatchNormalization())
                .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
                .layer(L.DenseLayer(n_out=256))
                .layer(L.DropoutLayer(rate=0.5))
                .layer(L.OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())
        return MultiLayerNetwork(conf).init()


class AlexNet(ZooModel):
    """reference zoo.model.AlexNet (single-tower variant)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123):
        self.num_classes = num_classes
        self.seed = seed

    def init(self) -> MultiLayerNetwork:
        conf = (NeuralNetConfiguration.builder()
                .seed(self.seed)
                .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
                .activation("relu").weight_init("relu")
                .list()
                .layer(L.ConvolutionLayer(n_out=96, kernel_size=(11, 11), stride=(4, 4)))
                .layer(L.LocalResponseNormalization())
                .layer(L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(L.ConvolutionLayer(n_out=256, kernel_size=(5, 5), padding=(2, 2)))
                .layer(L.LocalResponseNormalization())
                .layer(L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(L.ConvolutionLayer(n_out=384, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.ConvolutionLayer(n_out=384, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.ConvolutionLayer(n_out=256, kernel_size=(3, 3), padding=(1, 1)))
                .layer(L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)))
                .layer(L.DenseLayer(n_out=4096, dropout=0.5))
                .layer(L.DenseLayer(n_out=4096, dropout=0.5))
                .layer(L.OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(227, 227, 3))
                .build())
        return MultiLayerNetwork(conf).init()


class VGG16(ZooModel):
    """reference zoo.model.VGG16."""

    def __init__(self, num_classes: int = 1000, seed: int = 123):
        self.num_classes = num_classes
        self.seed = seed

    def _blocks(self) -> Sequence[Tuple[int, int]]:
        return [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]

    def init(self) -> MultiLayerNetwork:
        lb = (NeuralNetConfiguration.builder()
              .seed(self.seed)
              .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
              .activation("relu").weight_init("relu")
              .list())
        for n_convs, ch in self._blocks():
            for _ in range(n_convs):
                lb = lb.layer(L.ConvolutionLayer(n_out=ch, kernel_size=(3, 3),
                                                 padding=(1, 1)))
            lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        conf = (lb.layer(L.DenseLayer(n_out=4096, dropout=0.5))
                .layer(L.DenseLayer(n_out=4096, dropout=0.5))
                .layer(L.OutputLayer(n_out=self.num_classes))
                .set_input_type(InputType.convolutional(224, 224, 3))
                .build())
        return MultiLayerNetwork(conf).init()


class VGG19(VGG16):
    """reference zoo.model.VGG19."""

    def _blocks(self):
        return [(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)]


class ResNet50(ZooModel):
    """reference zoo.model.ResNet50 — the north-star ComputationGraph config:
    conv/identity bottleneck blocks with ElementWiseVertex(Add) residuals."""

    def __init__(self, num_classes: int = 1000, seed: int = 123,
                 image_size: int = 224):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed)
                             .updater(Nesterovs(learning_rate=0.1, momentum=0.9))
                             .activation("relu").weight_init("relu").l2(1e-4))
              .add_inputs("input"))
        # stem
        gb.add_layer("stem_conv", L.ConvolutionLayer(
            n_out=64, kernel_size=(7, 7), stride=(2, 2), padding=(3, 3),
            has_bias=False, activation="identity"), "input")
        gb.add_layer("stem_bn", L.BatchNormalization(activation="relu"), "stem_conv")
        gb.add_layer("stem_pool", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), "stem_bn")

        prev = "stem_pool"
        stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
                  (3, 512, 2048, 2)]
        for s, (blocks, mid, out_ch, first_stride) in enumerate(stages):
            for b in range(blocks):
                stride = first_stride if b == 0 else 1
                name = f"s{s}b{b}"
                # main path: 1x1 -> 3x3 -> 1x1 (bottleneck)
                gb.add_layer(f"{name}_c1", L.ConvolutionLayer(
                    n_out=mid, kernel_size=(1, 1), stride=(stride, stride),
                    has_bias=False, activation="identity"), prev)
                gb.add_layer(f"{name}_bn1", L.BatchNormalization(activation="relu"),
                             f"{name}_c1")
                gb.add_layer(f"{name}_c2", L.ConvolutionLayer(
                    n_out=mid, kernel_size=(3, 3), padding=(1, 1),
                    has_bias=False, activation="identity"), f"{name}_bn1")
                gb.add_layer(f"{name}_bn2", L.BatchNormalization(activation="relu"),
                             f"{name}_c2")
                gb.add_layer(f"{name}_c3", L.ConvolutionLayer(
                    n_out=out_ch, kernel_size=(1, 1), has_bias=False,
                    activation="identity"), f"{name}_bn2")
                gb.add_layer(f"{name}_bn3", L.BatchNormalization(activation="identity"),
                             f"{name}_c3")
                # shortcut
                if b == 0:
                    gb.add_layer(f"{name}_sc", L.ConvolutionLayer(
                        n_out=out_ch, kernel_size=(1, 1), stride=(stride, stride),
                        has_bias=False, activation="identity"), prev)
                    gb.add_layer(f"{name}_scbn", L.BatchNormalization(
                        activation="identity"), f"{name}_sc")
                    shortcut = f"{name}_scbn"
                else:
                    shortcut = prev
                gb.add_vertex(f"{name}_add", ElementWiseVertex(op="add"),
                              f"{name}_bn3", shortcut)
                gb.add_layer(f"{name}_relu", L.ActivationLayer(activation="relu"),
                             f"{name}_add")
                prev = f"{name}_relu"

        gb.add_layer("avgpool", L.GlobalPoolingLayer(pooling_type="avg"), prev)
        gb.add_layer("output", L.OutputLayer(n_out=self.num_classes, loss="mcxent",
                                             activation="softmax"), "avgpool")
        conf = (gb.set_outputs("output")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class SqueezeNet(ZooModel):
    """reference zoo.model.SqueezeNet (fire modules via MergeVertex)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123):
        self.num_classes = num_classes
        self.seed = seed

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))
        gb.add_layer("conv1", L.ConvolutionLayer(n_out=64, kernel_size=(3, 3),
                                                 stride=(2, 2)), "input")
        gb.add_layer("pool1", L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)),
                     "conv1")
        prev = "pool1"

        def fire(name, squeeze, expand, inp):
            gb.add_layer(f"{name}_sq", L.ConvolutionLayer(
                n_out=squeeze, kernel_size=(1, 1)), inp)
            gb.add_layer(f"{name}_e1", L.ConvolutionLayer(
                n_out=expand, kernel_size=(1, 1)), f"{name}_sq")
            gb.add_layer(f"{name}_e3", L.ConvolutionLayer(
                n_out=expand, kernel_size=(3, 3), padding=(1, 1)), f"{name}_sq")
            gb.add_vertex(f"{name}_cat", MergeVertex(), f"{name}_e1", f"{name}_e3")
            return f"{name}_cat"

        prev = fire("fire2", 16, 64, prev)
        prev = fire("fire3", 16, 64, prev)
        gb.add_layer("pool3", L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), prev)
        prev = fire("fire4", 32, 128, "pool3")
        prev = fire("fire5", 32, 128, prev)
        gb.add_layer("pool5", L.SubsamplingLayer(kernel_size=(3, 3), stride=(2, 2)), prev)
        prev = fire("fire6", 48, 192, "pool5")
        prev = fire("fire7", 48, 192, prev)
        prev = fire("fire8", 64, 256, prev)
        prev = fire("fire9", 64, 256, prev)
        gb.add_layer("drop", L.DropoutLayer(rate=0.5), prev)
        gb.add_layer("conv10", L.ConvolutionLayer(n_out=self.num_classes,
                                                  kernel_size=(1, 1)), "drop")
        gb.add_layer("gap", L.GlobalPoolingLayer(pooling_type="avg"), "conv10")
        gb.add_layer("output", L.LossLayer(loss="mcxent", activation="softmax"), "gap")
        conf = (gb.set_outputs("output")
                .set_input_types(InputType.convolutional(224, 224, 3)).build())
        return ComputationGraph(conf).init()


class Darknet19(ZooModel):
    """reference zoo.model.Darknet19."""

    def __init__(self, num_classes: int = 1000, seed: int = 123, image_size: int = 224):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> MultiLayerNetwork:
        def conv_bn(lb, ch, k):
            pad = (k // 2, k // 2) if k > 1 else (0, 0)
            return (lb.layer(L.ConvolutionLayer(n_out=ch, kernel_size=(k, k),
                                                padding=pad, has_bias=False,
                                                activation="identity"))
                    .layer(L.BatchNormalization(activation="leakyrelu")))

        lb = (NeuralNetConfiguration.builder()
              .seed(self.seed).updater(Nesterovs(1e-3, 0.9))
              .weight_init("relu").list())
        lb = conv_bn(lb, 32, 3)
        lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        lb = conv_bn(lb, 64, 3)
        lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for chs in ([128, 64, 128], [256, 128, 256]):
            for i, ch in enumerate(chs):
                lb = conv_bn(lb, ch, 3 if i % 2 == 0 else 1)
            lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        for chs in ([512, 256, 512, 256, 512], [1024, 512, 1024, 512, 1024]):
            for i, ch in enumerate(chs):
                lb = conv_bn(lb, ch, 3 if i % 2 == 0 else 1)
            if chs[0] == 512:
                lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        lb = lb.layer(L.ConvolutionLayer(n_out=self.num_classes, kernel_size=(1, 1)))
        lb = lb.layer(L.GlobalPoolingLayer(pooling_type="avg"))
        conf = (lb.layer(L.LossLayer(loss="mcxent", activation="softmax"))
                .set_input_type(InputType.convolutional(self.image_size,
                                                        self.image_size, 3))
                .build())
        return MultiLayerNetwork(conf).init()


class UNet(ZooModel):
    """reference zoo.model.UNet (segmentation; encoder-decoder with skip
    merges)."""

    def __init__(self, n_channels: int = 1, n_classes: int = 1, seed: int = 123,
                 image_size: int = 128, base: int = 32):
        self.n_channels = n_channels
        self.n_classes = n_classes
        self.seed = seed
        self.image_size = image_size
        self.base = base

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-4))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))

        def double_conv(name, ch, inp):
            gb.add_layer(f"{name}_c1", L.ConvolutionLayer(
                n_out=ch, kernel_size=(3, 3), padding=(1, 1)), inp)
            gb.add_layer(f"{name}_c2", L.ConvolutionLayer(
                n_out=ch, kernel_size=(3, 3), padding=(1, 1)), f"{name}_c1")
            return f"{name}_c2"

        b = self.base
        d1 = double_conv("down1", b, "input")
        gb.add_layer("pool1", L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)), d1)
        d2 = double_conv("down2", b * 2, "pool1")
        gb.add_layer("pool2", L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)), d2)
        d3 = double_conv("down3", b * 4, "pool2")
        gb.add_layer("pool3", L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)), d3)
        mid = double_conv("mid", b * 8, "pool3")

        gb.add_layer("up3", L.Deconvolution2D(n_out=b * 4, kernel_size=(2, 2),
                                              stride=(2, 2)), mid)
        gb.add_vertex("cat3", MergeVertex(), "up3", d3)
        u3 = double_conv("upc3", b * 4, "cat3")
        gb.add_layer("up2", L.Deconvolution2D(n_out=b * 2, kernel_size=(2, 2),
                                              stride=(2, 2)), u3)
        gb.add_vertex("cat2", MergeVertex(), "up2", d2)
        u2 = double_conv("upc2", b * 2, "cat2")
        gb.add_layer("up1", L.Deconvolution2D(n_out=b, kernel_size=(2, 2),
                                              stride=(2, 2)), u2)
        gb.add_vertex("cat1", MergeVertex(), "up1", d1)
        u1 = double_conv("upc1", b, "cat1")
        gb.add_layer("head", L.ConvolutionLayer(n_out=self.n_classes,
                                                kernel_size=(1, 1),
                                                activation="identity"), u1)
        gb.add_layer("output", L.LossLayer(loss="binary_xent", activation="sigmoid"),
                     "head")
        conf = (gb.set_outputs("output")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, self.n_channels))
                .build())
        return ComputationGraph(conf).init()


class TextGenerationLSTM(ZooModel):
    """reference zoo.model.TextGenerationLSTM (char-level LM)."""

    def __init__(self, vocab_size: int, hidden: int = 256, seed: int = 123):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.seed = seed

    def init(self) -> MultiLayerNetwork:
        conf = (NeuralNetConfiguration.builder()
                .seed(self.seed).updater(Adam(2e-3))
                .list()
                .layer(L.LSTM(n_out=self.hidden))
                .layer(L.LSTM(n_out=self.hidden))
                .layer(L.RnnOutputLayer(n_out=self.vocab_size, loss="mcxent",
                                        activation="softmax"))
                .set_input_type(InputType.recurrent(self.vocab_size))
                .build())
        return MultiLayerNetwork(conf).init()


class TinyYOLO(ZooModel):
    """reference zoo.model.TinyYOLO: darknet-tiny conv/bn/leaky backbone +
    YOLOv2 detection head (reference anchors, VOC-style defaults)."""

    ANCHORS = ((1.08, 1.19), (3.42, 4.41), (6.63, 11.38), (9.42, 5.11),
               (16.62, 10.52))

    def __init__(self, num_classes: int = 20, seed: int = 123,
                 image_size: int = 416):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> MultiLayerNetwork:
        def conv_bn(lb, ch):
            return (lb.layer(L.ConvolutionLayer(
                        n_out=ch, kernel_size=(3, 3), padding=(1, 1),
                        has_bias=False, activation="identity"))
                    .layer(L.BatchNormalization(activation="leakyrelu")))

        lb = (NeuralNetConfiguration.builder()
              .seed(self.seed).updater(Adam(1e-3)).weight_init("relu")
              .list())
        for i, ch in enumerate((16, 32, 64, 128, 256, 512)):
            lb = conv_bn(lb, ch)
            stride = (2, 2) if i < 5 else (1, 1)
            lb = lb.layer(L.SubsamplingLayer(kernel_size=(2, 2),
                                             stride=stride,
                                             padding=(0, 0) if i < 5
                                             else (1, 1)))
        lb = conv_bn(lb, 1024)
        lb = conv_bn(lb, 1024)
        lb = lb.layer(L.ConvolutionLayer(
            n_out=len(self.ANCHORS) * (5 + self.num_classes),
            kernel_size=(1, 1), activation="identity"))
        conf = (lb.layer(L.Yolo2OutputLayer(anchors=self.ANCHORS))
                .set_input_type(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return MultiLayerNetwork(conf).init()


class YOLO2(ZooModel):
    """reference zoo.model.YOLO2: Darknet-19 backbone + the passthrough
    (reorg) route — SpaceToDepth on the high-res feature map concatenated
    with the deep path (MergeVertex) — + YOLOv2 head."""

    ANCHORS = ((0.57273, 0.677385), (1.87446, 2.06253), (3.33843, 5.47434),
               (7.88282, 3.52778), (9.77052, 9.16828))

    def __init__(self, num_classes: int = 80, seed: int = 123,
                 image_size: int = 416):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .weight_init("relu"))
              .add_inputs("input"))
        idx = [0]

        def conv_bn(name_in, ch, k):
            i = idx[0]
            idx[0] += 1
            pad = (k // 2, k // 2) if k > 1 else (0, 0)
            gb.add_layer(f"conv{i}", L.ConvolutionLayer(
                n_out=ch, kernel_size=(k, k), padding=pad, has_bias=False,
                activation="identity"), name_in)
            gb.add_layer(f"bn{i}", L.BatchNormalization(
                activation="leakyrelu"), f"conv{i}")
            return f"bn{i}"

        def pool(name_in):
            i = idx[0]
            idx[0] += 1
            gb.add_layer(f"pool{i}", L.SubsamplingLayer(
                kernel_size=(2, 2), stride=(2, 2)), name_in)
            return f"pool{i}"

        prev = conv_bn("input", 32, 3)
        prev = pool(prev)
        prev = conv_bn(prev, 64, 3)
        prev = pool(prev)
        for chs in ([128, 64, 128], [256, 128, 256]):
            for j, ch in enumerate(chs):
                prev = conv_bn(prev, ch, 3 if j % 2 == 0 else 1)
            prev = pool(prev)
        for j, ch in enumerate([512, 256, 512, 256, 512]):
            prev = conv_bn(prev, ch, 3 if j % 2 == 0 else 1)
        route = prev                       # 26x26x512 passthrough source
        prev = pool(prev)
        for j, ch in enumerate([1024, 512, 1024, 512, 1024]):
            prev = conv_bn(prev, ch, 3 if j % 2 == 0 else 1)
        prev = conv_bn(prev, 1024, 3)
        prev = conv_bn(prev, 1024, 3)
        # passthrough: reorg the 26x26 map to 13x13 and concat
        gb.add_layer("reorg", L.SpaceToDepthLayer(block_size=2), route)
        gb.add_vertex("route_cat", MergeVertex(), "reorg", prev)
        prev = conv_bn("route_cat", 1024, 3)
        gb.add_layer("head", L.ConvolutionLayer(
            n_out=len(self.ANCHORS) * (5 + self.num_classes),
            kernel_size=(1, 1), activation="identity"), prev)
        gb.add_layer("yolo", L.Yolo2OutputLayer(anchors=self.ANCHORS),
                     "head")
        conf = (gb.set_outputs("yolo")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class Xception(ZooModel):
    """reference zoo.model.Xception: entry/middle/exit flows of separable
    convolutions with conv-projection residuals (ElementWiseVertex add)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123,
                 image_size: int = 299):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))
        n = [0]

        def sep_bn(name_in, ch, act="relu"):
            i = n[0]
            n[0] += 1
            gb.add_layer(f"sep{i}", L.SeparableConvolution2D(
                n_out=ch, kernel_size=(3, 3), convolution_mode="same",
                has_bias=False, activation="identity"), name_in)
            gb.add_layer(f"sbn{i}", L.BatchNormalization(activation=act),
                         f"sep{i}")
            return f"sbn{i}"

        def conv_bn(name_in, ch, k, stride, act="relu"):
            i = n[0]
            n[0] += 1
            gb.add_layer(f"cv{i}", L.ConvolutionLayer(
                n_out=ch, kernel_size=(k, k), stride=(stride, stride),
                convolution_mode="same", has_bias=False,
                activation="identity"), name_in)
            gb.add_layer(f"cbn{i}", L.BatchNormalization(activation=act),
                         f"cv{i}")
            return f"cbn{i}"

        def maxpool(name_in):
            i = n[0]
            n[0] += 1
            gb.add_layer(f"mp{i}", L.SubsamplingLayer(
                kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), name_in)
            return f"mp{i}"

        # entry flow
        prev = conv_bn("input", 32, 3, 2)
        prev = conv_bn(prev, 64, 3, 1)
        for ch in (128, 256, 728):
            res = conv_bn(prev, ch, 1, 2, act="identity")
            x = sep_bn(prev, ch)
            x = sep_bn(x, ch, act="identity")
            x = maxpool(x)
            i = n[0]
            n[0] += 1
            gb.add_vertex(f"add{i}", ElementWiseVertex("add"), x, res)
            prev = f"add{i}"
        # middle flow: 8 blocks of 3 separable convs + identity residual
        for _ in range(8):
            x = prev
            for _ in range(3):
                x = sep_bn(x, 728)
            i = n[0]
            n[0] += 1
            gb.add_vertex(f"add{i}", ElementWiseVertex("add"), x, prev)
            prev = f"add{i}"
        # exit flow
        res = conv_bn(prev, 1024, 1, 2, act="identity")
        x = sep_bn(prev, 728)
        x = sep_bn(x, 1024, act="identity")
        x = maxpool(x)
        i = n[0]
        n[0] += 1
        gb.add_vertex(f"add{i}", ElementWiseVertex("add"), x, res)
        prev = sep_bn(f"add{i}", 1536)
        prev = sep_bn(prev, 2048)
        gb.add_layer("gap", L.GlobalPoolingLayer(pooling_type="avg"), prev)
        gb.add_layer("out", L.OutputLayer(n_out=self.num_classes,
                                          loss="mcxent",
                                          activation="softmax"), "gap")
        conf = (gb.set_outputs("out")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class InceptionResNetV1(ZooModel):
    """reference zoo.model.InceptionResNetV1 (FaceNetNN4-era): stem +
    5x inception-resnet-A + reduction-A + 10x block-B + reduction-B +
    5x block-C, residual branches merged by concat then 1x1-projected and
    added back (ElementWiseVertex)."""

    def __init__(self, num_classes: int = 128, seed: int = 123,
                 image_size: int = 160):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))
        n = [0]

        def conv(name_in, ch, k, stride=1, same=True, act="relu"):
            i = n[0]
            n[0] += 1
            gb.add_layer(f"c{i}", L.ConvolutionLayer(
                n_out=ch, kernel_size=(k, k), stride=(stride, stride),
                convolution_mode="same" if same else "truncate",
                has_bias=False, activation="identity"), name_in)
            gb.add_layer(f"b{i}", L.BatchNormalization(activation=act),
                         f"c{i}")
            return f"b{i}"

        def resnet_block(prev, branches, proj_ch):
            """concat(branches) → 1x1 proj → add residual → relu."""
            i = n[0]
            n[0] += 1
            gb.add_vertex(f"cat{i}", MergeVertex(), *branches)
            gb.add_layer(f"proj{i}", L.ConvolutionLayer(
                n_out=proj_ch, kernel_size=(1, 1),
                activation="identity"), f"cat{i}")
            gb.add_vertex(f"radd{i}", ElementWiseVertex("add"),
                          f"proj{i}", prev)
            gb.add_layer(f"ract{i}", L.ActivationLayer(activation="relu"),
                         f"radd{i}")
            return f"ract{i}"

        # stem (simplified faithful widths)
        prev = conv("input", 32, 3, stride=2)
        prev = conv(prev, 32, 3)
        prev = conv(prev, 64, 3)
        gb.add_layer("stem_pool", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        prev = conv("stem_pool", 80, 1)
        prev = conv(prev, 192, 3)
        prev = conv(prev, 256, 3, stride=2)

        # 5x inception-resnet-A (channels 256)
        for _ in range(5):
            b1 = conv(prev, 32, 1)
            b2 = conv(conv(prev, 32, 1), 32, 3)
            b3 = conv(conv(conv(prev, 32, 1), 32, 3), 32, 3)
            prev = resnet_block(prev, (b1, b2, b3), 256)
        # reduction-A → 896 channels
        ra1 = conv(prev, 384, 3, stride=2)
        ra2 = conv(conv(conv(prev, 192, 1), 192, 3), 256, 3, stride=2)
        gb.add_layer("redA_pool", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        gb.add_vertex("redA", MergeVertex(), ra1, ra2, "redA_pool")
        prev = "redA"
        # 10x inception-resnet-B (channels 896)
        for _ in range(10):
            b1 = conv(prev, 128, 1)
            b2 = conv(conv(prev, 128, 1), 128, 7)
            prev = resnet_block(prev, (b1, b2), 896)
        # reduction-B → 1792 channels
        rb1 = conv(conv(prev, 256, 1), 384, 3, stride=2)
        rb2 = conv(conv(prev, 256, 1), 256, 3, stride=2)
        rb3 = conv(conv(conv(prev, 256, 1), 256, 3), 256, 3, stride=2)
        gb.add_layer("redB_pool", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        gb.add_vertex("redB", MergeVertex(), rb1, rb2, rb3, "redB_pool")
        prev = "redB"
        # 5x inception-resnet-C (channels 1792)
        for _ in range(5):
            b1 = conv(prev, 192, 1)
            b2 = conv(conv(prev, 192, 1), 192, 3)
            prev = resnet_block(prev, (b1, b2), 1792)

        gb.add_layer("gap", L.GlobalPoolingLayer(pooling_type="avg"), prev)
        gb.add_layer("bottleneck", L.DenseLayer(
            n_out=self.num_classes, activation="identity"), "gap")
        gb.add_layer("out", L.LossLayer(loss="mcxent",
                                        activation="softmax"), "bottleneck")
        conf = (gb.set_outputs("out")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class FaceNetNN4Small2(ZooModel):
    """reference zoo.model.FaceNetNN4Small2: the OpenFace nn4.small2
    inception variant — stem convs + inception modules (1x1/3x3/5x5 +
    pooling-projection branches merged channel-wise), a 128-d embedding
    bottleneck, L2 normalization, and a center-loss softmax head
    (reference: FaceNetHelper.appendGraph + CenterLossOutputLayer)."""

    def __init__(self, num_classes: int = 100, embedding_size: int = 128,
                 seed: int = 123, image_size: int = 96):
        self.num_classes = num_classes
        self.embedding_size = embedding_size
        self.seed = seed
        self.image_size = image_size

    def init(self) -> ComputationGraph:
        from ..nn.conf.layers_ext import CenterLossOutputLayer
        from ..nn.graph import L2NormalizeVertex

        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))
        n = [0]

        def conv_bn(inp, ch, k, stride=1, pad=None):
            i = n[0]
            n[0] += 1
            pad = pad if pad is not None else k // 2
            gb.add_layer(f"c{i}", L.ConvolutionLayer(
                n_out=ch, kernel_size=(k, k), stride=(stride, stride),
                padding=(pad, pad), has_bias=False,
                activation="identity"), inp)
            gb.add_layer(f"b{i}", L.BatchNormalization(activation="relu"),
                         f"c{i}")
            return f"b{i}"

        def inception(name, inp, b1x1, b3r, b3, b5r, b5, pool_proj):
            """Four branches: 1x1 | 1x1→3x3 | 1x1→5x5 | pool→1x1;
            a zero channel count drops that branch (nn4.small2 trims
            branches in the later modules)."""
            outs = []
            if b1x1:
                outs.append(conv_bn(inp, b1x1, 1))
            if b3:
                r = conv_bn(inp, b3r, 1)
                outs.append(conv_bn(r, b3, 3))
            if b5:
                r = conv_bn(inp, b5r, 1)
                outs.append(conv_bn(r, b5, 5))
            if pool_proj:
                gb.add_layer(f"{name}_pool", L.SubsamplingLayer(
                    kernel_size=(3, 3), stride=(1, 1), padding=(1, 1)), inp)
                outs.append(conv_bn(f"{name}_pool", pool_proj, 1))
            gb.add_vertex(f"{name}_cat", MergeVertex(), *outs)
            return f"{name}_cat"

        # stem (96 -> 24 -> 12)
        prev = conv_bn("input", 64, 7, 2, 3)
        gb.add_layer("stem_pool", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        prev = conv_bn("stem_pool", 64, 1)
        prev = conv_bn(prev, 192, 3)
        gb.add_layer("stem_pool2", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        prev = "stem_pool2"
        # inception stack (nn4.small2 module shapes)
        prev = inception("i3a", prev, 64, 96, 128, 16, 32, 32)
        prev = inception("i3b", prev, 64, 96, 128, 32, 64, 64)
        gb.add_layer("pool3", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        prev = inception("i4a", "pool3", 256, 96, 192, 32, 64, 128)
        prev = inception("i4e", prev, 0, 160, 256, 64, 128, 0)
        gb.add_layer("pool4", L.SubsamplingLayer(
            kernel_size=(3, 3), stride=(2, 2), padding=(1, 1)), prev)
        prev = inception("i5a", "pool4", 256, 96, 384, 0, 0, 96)
        prev = inception("i5b", prev, 256, 96, 384, 0, 0, 96)
        gb.add_layer("gap", L.GlobalPoolingLayer(pooling_type="avg"), prev)
        gb.add_layer("bottleneck", L.DenseLayer(
            n_out=self.embedding_size, activation="identity"), "gap")
        gb.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        gb.add_layer("lossLayer", CenterLossOutputLayer(
            n_out=self.num_classes, loss="mcxent", activation="softmax",
            alpha=0.1, lambda_=3e-4), "embeddings")
        conf = (gb.set_outputs("lossLayer")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class NASNet(ZooModel):
    """reference zoo.model.NASNet (NASNet-A mobile): stem conv + stacks of
    NASNet-A normal cells with reduction cells between stacks. Cells follow
    the published NASNet-A block structure — five branch pairs of
    {separable 3x3/5x5, avg/max pool, identity} combined by adds and
    concatenated — with 1x1 "adjust" projections aligning the previous
    cell's channels (the reference's adjustBlock)."""

    def __init__(self, num_classes: int = 1000, seed: int = 123,
                 image_size: int = 96, penultimate_filters: int = 192,
                 cells_per_stack: int = 2):
        self.num_classes = num_classes
        self.seed = seed
        self.image_size = image_size
        self.filters = penultimate_filters // 24 * 4   # base cell width
        self.cells_per_stack = cells_per_stack

    def init(self) -> ComputationGraph:
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(Adam(1e-3))
                             .activation("relu").weight_init("relu"))
              .add_inputs("input"))
        n = [0]

        def uid(tag):
            n[0] += 1
            return f"{tag}{n[0]}"

        def adjust(inp, ch, stride=1):
            """1x1 projection + BN to ch channels (reference adjustBlock)."""
            c = uid("adj")
            gb.add_layer(c, L.ConvolutionLayer(
                n_out=ch, kernel_size=(1, 1), stride=(stride, stride),
                has_bias=False, activation="identity"), inp)
            b = uid("adjbn")
            gb.add_layer(b, L.BatchNormalization(activation="identity"), c)
            return b

        def sep(inp, ch, k, stride=1):
            s = uid("sep")
            gb.add_layer(s, L.SeparableConvolution2D(
                n_out=ch, kernel_size=(k, k), stride=(stride, stride),
                convolution_mode="same", has_bias=False,
                activation="identity"), inp)
            b = uid("sepbn")
            gb.add_layer(b, L.BatchNormalization(activation="relu"), s)
            return b

        def avgp(inp, stride=1):
            p = uid("avg")
            gb.add_layer(p, L.SubsamplingLayer(
                kernel_size=(3, 3), stride=(stride, stride), padding=(1, 1),
                pooling_type="avg"), inp)
            return p

        def maxp(inp, stride=1):
            p = uid("max")
            gb.add_layer(p, L.SubsamplingLayer(
                kernel_size=(3, 3), stride=(stride, stride),
                padding=(1, 1)), inp)
            return p

        def add(a, b):
            v = uid("addv")
            gb.add_vertex(v, ElementWiseVertex("add"), a, b)
            return v

        def normal_cell(prev, cur, ch, prev_stride=1):
            """NASNet-A normal cell over (h_{i-1}, h_i); ``prev_stride=2``
            is the adjustBlock's spatial alignment right after a
            reduction cell."""
            p = adjust(prev, ch, prev_stride)
            h = adjust(cur, ch)
            b1 = add(sep(h, ch, 5), sep(p, ch, 3))
            b2 = add(sep(p, ch, 5), sep(p, ch, 3))
            b3 = add(avgp(h), p)
            b4 = add(avgp(p), avgp(p))
            b5 = add(sep(h, ch, 3), h)
            cat = uid("ncat")
            gb.add_vertex(cat, MergeVertex(), b1, b2, b3, b4, b5)
            return cat

        def reduction_cell(prev, cur, ch):
            """NASNet-A reduction cell (stride-2 branches)."""
            p = adjust(prev, ch)
            h = adjust(cur, ch)
            b1 = add(sep(h, ch, 5, 2), sep(p, ch, 7, 2))
            b2 = add(maxp(h, 2), sep(p, ch, 7, 2))
            b3 = add(avgp(h, 2), sep(p, ch, 5, 2))
            b4 = add(maxp(h, 2), sep(b1, ch, 3))
            b5 = add(avgp(b1), b2)
            cat = uid("rcat")
            gb.add_vertex(cat, MergeVertex(), b2, b3, b4, b5)
            return cat

        ch = self.filters
        stem = uid("stem")
        gb.add_layer(stem, L.ConvolutionLayer(
            n_out=ch, kernel_size=(3, 3), stride=(2, 2), padding=(1, 1),
            has_bias=False, activation="identity"), "input")
        stem_bn = uid("stembn")
        gb.add_layer(stem_bn, L.BatchNormalization(activation="identity"),
                     stem)
        prev_cell, cur = stem_bn, stem_bn
        after_reduction = False
        for stack in range(3):
            for _ in range(self.cells_per_stack):
                nxt = normal_cell(prev_cell, cur, ch,
                                  prev_stride=2 if after_reduction else 1)
                after_reduction = False
                prev_cell, cur = cur, nxt
            if stack < 2:
                nxt = reduction_cell(prev_cell, cur, ch * 2)
                prev_cell, cur = cur, nxt
                ch *= 2
                after_reduction = True
        act = uid("relu")
        gb.add_layer(act, L.ActivationLayer(activation="relu"), cur)
        gb.add_layer("gap", L.GlobalPoolingLayer(pooling_type="avg"), act)
        gb.add_layer("out", L.OutputLayer(n_out=self.num_classes,
                                          loss="mcxent",
                                          activation="softmax"), "gap")
        conf = (gb.set_outputs("out")
                .set_input_types(InputType.convolutional(
                    self.image_size, self.image_size, 3))
                .build())
        return ComputationGraph(conf).init()


class Phi4MiniFlash(ZooModel):
    """Phi-4-mini-flash-reasoning ("SambaY": Ren et al., arXiv:2507.06607;
    huggingface.co/microsoft/Phi-4-mini-flash-reasoning, ``config.json``): a
    decoder whose first half alternates Mamba-1 mixers with window-512
    differential attention, whose middle pair is a Mamba layer that exposes
    its scan output (the memory) and a full-attention layer that exposes its
    keys and values, and whose second half alternates gated memory units
    reading that memory with cross-attention over those keys and values.
    Every block is pre-norm (LayerNorm) with a gated MLP; no positional
    encoding; the head is the embedding table itself.

    ``layers``: the published layer indices to build, in order (all
    ``num_hidden_layers`` when None) — a pipeline stage, or one period of
    each half. ``vocab_rows``: rows of the tied embedding/head held here (a
    vocabulary-parallel shard; ids and the loss range over the rows).
    Defaults are the published sizes; sizes the published config does not
    carry (``d_state``, ``d_conv``, ``expand``, ``dt_rank = ceil(d/16)``)
    are the ``Phi4FlashConfig`` defaults. Trained through
    ``ComputationGraph.fit`` on ``[B, T]`` integer ids with ``[B, T]``
    integer next-token labels."""

    def __init__(self, layers: Optional[Sequence[int]] = None,
                 vocab_rows: int = 200064, hidden_size: int = 2560,
                 intermediate_size: int = 10240,
                 num_attention_heads: int = 40, num_key_value_heads: int = 20,
                 sliding_window: int = 512, mb_per_layer: int = 2,
                 num_hidden_layers: int = 32, layer_norm_eps: float = 1e-5,
                 d_state: int = 16, d_conv: int = 4, expand: int = 2,
                 dt_rank: Optional[int] = None, seq_len: Optional[int] = None,
                 compute_dtype: Optional[str] = "bfloat16",
                 state_dtype: Optional[str] = "bfloat16",
                 remat_policy="full", learning_rate: float = 1e-4,
                 weight_decay: float = 0.1, seed: int = 123):
        self.layers = list(range(num_hidden_layers) if layers is None
                           else layers)
        self.vocab_rows = vocab_rows
        self.d, self.ff = hidden_size, intermediate_size
        self.heads, self.kv_heads = num_attention_heads, num_key_value_heads
        self.window, self.period = sliding_window, mb_per_layer
        self.boundary = num_hidden_layers // 2   # the layer that emits memory
        self.eps = layer_norm_eps
        self.d_state, self.d_conv = d_state, d_conv
        self.d_inner = expand * hidden_size
        self.dt_rank = dt_rank or 0     # 0: the layer's own ceil(d / 16)
        self.seq_len = seq_len
        self.compute_dtype, self.state_dtype = compute_dtype, state_dtype
        self.remat_policy = remat_policy
        self.learning_rate, self.weight_decay = learning_rate, weight_decay
        self.seed = seed

    def _mixer(self, l: int):
        """(layer, further inputs) of published layer ``l``."""
        if l % self.period == 0:
            if l > self.boundary:
                return L.GatedMemoryUnit(), [f"l{self.boundary}_mix.memory"]
            return L.MambaLayer(
                d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank,
                emit_memory=l == self.boundary), []
        full, cross = l == self.boundary + 1, l > self.boundary + 1
        kv = f"l{self.boundary + 1}_mix"
        return L.DifferentialAttentionLayer(
            n_heads=self.heads, n_kv_heads=self.kv_heads,
            head_dim=self.d // self.heads,
            window=None if full or cross else self.window,
            cross=cross, emit_kv=full, eps=self.eps,
            lambda_init=0.8 - 0.6 * math.exp(-0.3 * l)), (
                [kv + ".k", kv + ".v"] if cross else [])

    def init(self) -> ComputationGraph:
        updater = AdamW(learning_rate=self.learning_rate, beta1=0.9,
                        beta2=0.95, epsilon=1e-8,
                        weight_decay=self.weight_decay)
        updater.state_dtype = self.state_dtype
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(updater))
              .add_inputs("ids"))
        gb.add_layer("embed", L.EmbeddingSequenceLayer(
            n_out=self.d, weight_init="normal"), "ids")
        norm = lambda: L.LayerNormalization(eps=self.eps)   # noqa: E731
        prev = "embed"
        for l in self.layers:
            mixer, more = self._mixer(l)
            gb.add_layer(f"l{l}_ln1", norm(), prev)
            gb.add_layer(f"l{l}_mix", mixer, f"l{l}_ln1", *more)
            gb.add_vertex(f"l{l}_add1", ElementWiseVertex(op="add"),
                          prev, f"l{l}_mix")
            gb.add_layer(f"l{l}_ln2", norm(), f"l{l}_add1")
            gb.add_layer(f"l{l}_mlp", L.GatedMLPLayer(n_ff=self.ff),
                         f"l{l}_ln2")
            gb.add_vertex(f"l{l}_add2", ElementWiseVertex(op="add"),
                          f"l{l}_add1", f"l{l}_mlp")
            prev = f"l{l}_add2"
        gb.add_layer("final_ln", norm(), prev)
        gb.add_layer("head", L.TiedOutputLayer(tied_to="embed"), "final_ln")
        conf = (gb.set_outputs("head")
                .set_input_types(InputType.recurrent(self.vocab_rows,
                                                     self.seq_len))
                .build())
        gc = conf.global_conf
        gc.compute_dtype = self.compute_dtype
        gc.remat_policy = self.remat_policy
        return ComputationGraph(conf).init()


class Lfm2Moe(ZooModel):
    """LFM2-24B-A2B (``model_type`` ``lfm2_moe``;
    huggingface.co/LiquidAI/LFM2-24B-A2B, ``config.json``; the family's
    modelling code is ``models/lfm2_moe`` of Hugging Face ``transformers``):
    a decoder of pre-norm blocks ``x + Operator(RMSNorm(x))``, ``x +
    FFN(RMSNorm(x))`` whose operator is a gated short convolution or, every
    fourth layer, grouped-query attention with per-head RMSNorm on queries
    and keys and rotary positions, and whose feed-forward is a dense gated
    MLP in the first ``num_dense_layers`` layers and ``num_experts`` routed
    experts, ``num_experts_per_tok`` a token, after them (sigmoid scores, a
    selection bias, weights normalised over the selected). A final RMSNorm;
    the head is the embedding table itself.

    ``layers``: the published layer indices to build, in order (all when
    None) — a pipeline stage, or one period. ``vocab_rows``: rows of the
    tied embedding/head held here. ``experts_held``: ``(first, count)`` of
    the experts of every routed layer that live here (all when None): the
    router keeps its ``num_experts`` outputs and the layer computes its own
    experts' part (``RoutedExpertsLayer``). ``expert_bias``: the
    ``num_experts`` selection biases, the same in every routed layer (zeros
    when None); they are layer state, not parameters. Defaults are the
    published sizes. Trained through ``ComputationGraph.fit`` on ``[B, T]``
    integer ids with ``[B, T]`` integer next-token labels; the tokens that
    selected each expert are ``ComputationGraph.expert_load()``."""

    def __init__(self, layers: Optional[Sequence[int]] = None,
                 vocab_rows: int = 65536,
                 experts_held: Optional[Tuple[int, int]] = None,
                 hidden_size: int = 2048, intermediate_size: int = 11776,
                 moe_intermediate_size: int = 1536,
                 num_attention_heads: int = 32, num_key_value_heads: int = 8,
                 num_experts: int = 64, num_experts_per_tok: int = 4,
                 routed_scaling_factor: float = 1.0,
                 num_dense_layers: int = 2, num_hidden_layers: int = 40,
                 full_attention_every: int = 4, conv_L_cache: int = 3,
                 norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 expert_bias: Optional[Sequence[float]] = None,
                 seq_len: Optional[int] = None,
                 compute_dtype: Optional[str] = "bfloat16",
                 state_dtype: Optional[str] = "bfloat16",
                 remat_policy="full", learning_rate: float = 1e-4,
                 weight_decay: float = 0.1, seed: int = 123):
        self.layers = list(range(num_hidden_layers) if layers is None
                           else layers)
        self.vocab_rows = vocab_rows
        self.experts_held = experts_held or (0, num_experts)
        self.d, self.ff, self.moe_ff = (hidden_size, intermediate_size,
                                        moe_intermediate_size)
        self.heads, self.kv_heads = num_attention_heads, num_key_value_heads
        self.experts, self.top_k = num_experts, num_experts_per_tok
        self.scale = routed_scaling_factor
        self.dense_layers = num_dense_layers
        self.period = full_attention_every
        self.taps, self.eps, self.theta = conv_L_cache, norm_eps, rope_theta
        self.expert_bias = (None if expert_bias is None
                            else [float(b) for b in expert_bias])
        self.seq_len = seq_len
        self.compute_dtype, self.state_dtype = compute_dtype, state_dtype
        self.remat_policy = remat_policy
        self.learning_rate, self.weight_decay = learning_rate, weight_decay
        self.seed = seed

    def is_attention(self, l: int) -> bool:
        """``layer_types[l] == "full_attention"``: layers 2, 6, 10, ..."""
        return l % self.period == self.period - 2

    def init(self) -> ComputationGraph:
        updater = AdamW(learning_rate=self.learning_rate, beta1=0.9,
                        beta2=0.95, epsilon=1e-8,
                        weight_decay=self.weight_decay)
        updater.state_dtype = self.state_dtype
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(updater))
              .add_inputs("ids"))
        gb.add_layer("embed", L.EmbeddingSequenceLayer(
            n_out=self.d, weight_init="normal"), "ids")
        norm = lambda: L.RMSNormLayer(eps=self.eps)         # noqa: E731
        first, held = self.experts_held
        prev = "embed"
        for l in self.layers:
            if self.is_attention(l):
                op = L.RotaryAttentionLayer(
                    n_heads=self.heads, n_kv_heads=self.kv_heads,
                    head_dim=self.d // self.heads, rope_theta=self.theta,
                    eps=self.eps)
            else:
                op = L.ShortConvLayer(taps=self.taps)
            if l < self.dense_layers:
                ffn = L.GatedMLPLayer(n_ff=self.ff)
            else:
                ffn = L.RoutedExpertsLayer(
                    n_routed=self.experts, n_experts=held, first_expert=first,
                    n_ff=self.moe_ff, top_k=self.top_k, scale=self.scale,
                    selection_bias=self.expert_bias)
            gb.add_layer(f"l{l}_ln1", norm(), prev)
            gb.add_layer(f"l{l}_op", op, f"l{l}_ln1")
            gb.add_vertex(f"l{l}_add1", ElementWiseVertex(op="add"),
                          prev, f"l{l}_op")
            gb.add_layer(f"l{l}_ln2", norm(), f"l{l}_add1")
            gb.add_layer(f"l{l}_ffn", ffn, f"l{l}_ln2")
            gb.add_vertex(f"l{l}_add2", ElementWiseVertex(op="add"),
                          f"l{l}_add1", f"l{l}_ffn")
            prev = f"l{l}_add2"
        gb.add_layer("final_ln", norm(), prev)
        gb.add_layer("head", L.TiedOutputLayer(tied_to="embed"), "final_ln")
        conf = (gb.set_outputs("head")
                .set_input_types(InputType.recurrent(self.vocab_rows,
                                                     self.seq_len))
                .build())
        gc = conf.global_conf
        gc.compute_dtype = self.compute_dtype
        gc.remat_policy = self.remat_policy
        return ComputationGraph(conf).init()


class JoyAILLMFlash(ZooModel):
    """JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, 48B-A2.7B;
    huggingface.co/jdopensource/JoyAI-LLM-Flash, ``config.json``, which
    carries the DeepSeek-V3 key set: arXiv:2412.19437 sections 2.1-2.2 and
    the ``deepseek_v3`` modelling code of Hugging Face ``transformers``): a
    decoder of pre-norm blocks ``x + MLA(RMSNorm(x))``, ``x + FFN(RMSNorm(x))``
    whose attention is multi-head latent attention (``LatentAttentionLayer``:
    a rotated slice of each head in interleaved pairs, ``rope_interleave``)
    and whose feed-forward is a dense gated MLP in the first
    ``first_k_dense_replace`` layers and, after them, ``n_routed_experts``
    routed experts, ``num_experts_per_tok`` a token (sigmoid scores, a
    selection bias — ``topk_method`` ``noaux_tc`` —, weights normalised over
    the selected with the family's 1e-20, times ``routed_scaling_factor``)
    beside ``n_shared_experts`` shared experts that every token meets. A
    final RMSNorm and an untied head (``LMHeadLayer``). ``mtp``: one
    multi-token-prediction module after the trunk (``num_nextn_predict_layers``
    1): ``MTPMergeLayer`` over the trunk's normed output and the next token's
    embedding, one routed block of its own, its own final RMSNorm and a head
    that borrows the trunk's matrix; the network's loss is ``L_main +
    mtp_loss_weight * L_mtp``.

    ``layers``: the published layer indices to build, in order (all when
    None). ``vocab_rows``: rows of the embedding and of the head held here.
    ``experts_held``: ``(first, count)`` of the experts of every routed layer
    that live here (all when None), ``expert_bias``: the ``n_routed_experts``
    selection biases (zeros when None), both as ``Lfm2Moe``'s. Defaults are
    the published sizes. Trained through ``ComputationGraph.fit`` on a
    ``MultiDataSet``: inputs ``ids`` ``[B, T]`` and, with ``mtp``,
    ``next_ids`` (each position's next token, the main head's labels);
    labels for ``head`` (next token) and ``mtp_head`` (the token after it,
    its last position masked)."""

    def __init__(self, layers: Optional[Sequence[int]] = None,
                 vocab_rows: int = 129280,
                 experts_held: Optional[Tuple[int, int]] = None,
                 expert_bias: Optional[Sequence[float]] = None,
                 mtp: bool = True, hidden_size: int = 2048,
                 intermediate_size: int = 7168,
                 moe_intermediate_size: int = 768,
                 num_attention_heads: int = 32, q_lora_rank: int = 1536,
                 kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                 qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                 n_routed_experts: int = 256, n_shared_experts: int = 1,
                 num_experts_per_tok: int = 8,
                 routed_scaling_factor: float = 2.5,
                 first_k_dense_replace: int = 1, num_hidden_layers: int = 40,
                 n_group: int = 1, topk_group: int = 1,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 3.2e7,
                 mtp_loss_weight: float = 0.3,
                 seq_len: Optional[int] = None,
                 compute_dtype: Optional[str] = "bfloat16",
                 state_dtype: Optional[str] = "bfloat16",
                 remat_policy="full", learning_rate: float = 1e-4,
                 weight_decay: float = 0.1, seed: int = 123):
        if (n_group, topk_group) != (1, 1):
            # group-limited routing picks groups before experts; with one
            # group that step is the identity, and no other is written here
            raise ValueError("JoyAILLMFlash routes over one group "
                             f"(n_group={n_group}, topk_group={topk_group})")
        self.layers = list(range(num_hidden_layers) if layers is None
                           else layers)
        self.vocab_rows = vocab_rows
        self.experts_held = experts_held or (0, n_routed_experts)
        self.expert_bias = (None if expert_bias is None
                            else [float(b) for b in expert_bias])
        self.mtp, self.mtp_loss_weight = mtp, mtp_loss_weight
        self.d, self.ff, self.moe_ff = (hidden_size, intermediate_size,
                                        moe_intermediate_size)
        self.attention = dict(
            n_heads=num_attention_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, eps=rms_norm_eps)
        self.experts, self.shared = n_routed_experts, n_shared_experts
        self.top_k, self.scale = num_experts_per_tok, routed_scaling_factor
        self.dense_layers, self.eps = first_k_dense_replace, rms_norm_eps
        self.seq_len = seq_len
        self.compute_dtype, self.state_dtype = compute_dtype, state_dtype
        self.remat_policy = remat_policy
        self.learning_rate, self.weight_decay = learning_rate, weight_decay
        self.seed = seed

    def _block(self, gb, name: str, prev: str, routed: bool) -> str:
        """One pre-norm block under the nodes ``<name>_*``; returns its
        output node."""
        norm = lambda: L.RMSNormLayer(eps=self.eps)         # noqa: E731
        gb.add_layer(f"{name}_ln1", norm(), prev)
        gb.add_layer(f"{name}_attn", L.LatentAttentionLayer(**self.attention),
                     f"{name}_ln1")
        gb.add_vertex(f"{name}_add1", ElementWiseVertex(op="add"),
                      prev, f"{name}_attn")
        gb.add_layer(f"{name}_ln2", norm(), f"{name}_add1")
        parts = [f"{name}_ffn"]
        if not routed:
            gb.add_layer(f"{name}_ffn", L.GatedMLPLayer(n_ff=self.ff),
                         f"{name}_ln2")
        else:
            first, held = self.experts_held
            gb.add_layer(f"{name}_ffn", L.RoutedExpertsLayer(
                n_routed=self.experts, n_experts=held, first_expert=first,
                n_ff=self.moe_ff, top_k=self.top_k, scale=self.scale,
                norm_eps=1e-20, selection_bias=self.expert_bias),
                f"{name}_ln2")
            # the shared experts: whole on every chip that shares the layer
            gb.add_layer(f"{name}_shared", L.GatedMLPLayer(
                n_ff=self.shared * self.moe_ff, scope="shared_expert"),
                f"{name}_ln2")
            parts.append(f"{name}_shared")
        gb.add_vertex(f"{name}_add2", ElementWiseVertex(op="add"),
                      f"{name}_add1", *parts)
        return f"{name}_add2"

    def init(self) -> ComputationGraph:
        updater = AdamW(learning_rate=self.learning_rate, beta1=0.9,
                        beta2=0.95, epsilon=1e-8,
                        weight_decay=self.weight_decay)
        updater.state_dtype = self.state_dtype
        inputs = ["ids", "next_ids"] if self.mtp else ["ids"]
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(updater))
              .add_inputs(*inputs))
        gb.add_layer("embed", L.EmbeddingSequenceLayer(
            n_out=self.d, weight_init="normal"), "ids")
        prev = "embed"
        for l in self.layers:
            prev = self._block(gb, f"l{l}", prev, l >= self.dense_layers)
        gb.add_layer("final_ln", L.RMSNormLayer(eps=self.eps), prev)
        gb.add_layer("head", L.LMHeadLayer(n_out=self.vocab_rows), "final_ln")
        outputs = ["head"]
        if self.mtp:
            gb.add_layer("mtp_merge", L.MTPMergeLayer(
                embed="embed", eps=self.eps), "final_ln", "next_ids")
            prev = self._block(gb, "mtp", "mtp_merge", True)
            gb.add_layer("mtp_final_ln", L.RMSNormLayer(eps=self.eps), prev)
            gb.add_layer("mtp_head", L.TiedOutputLayer(
                tied_to="head", loss_weight=self.mtp_loss_weight),
                "mtp_final_ln")
            outputs.append("mtp_head")
        tokens = InputType.recurrent(self.vocab_rows, self.seq_len)
        conf = (gb.set_outputs(*outputs)
                .set_input_types(*[tokens] * len(inputs)).build())
        gc = conf.global_conf
        gc.compute_dtype = self.compute_dtype
        gc.remat_policy = self.remat_policy
        return ComputationGraph(conf).init()


class TrinityMini(ZooModel):
    """Trinity-Mini (``model_type`` ``afmoe``, Arcee's 26B-A3B;
    huggingface.co/arcee-ai/Trinity-Mini, ``config.json``; the family's
    modelling code is ``models/afmoe`` of Hugging Face ``transformers``): a
    decoder of sandwich-norm blocks ``x + RMSNorm(Attn(RMSNorm(x)))``, ``x +
    RMSNorm(FFN(RMSNorm(x)))``. Attention is grouped-query with per-head
    RMSNorm on queries and keys and a sigmoid gate on its output
    (``RotaryAttentionLayer(output_gate=True)``); ``layer_types`` makes a
    layer ``sliding_attention`` (a window of ``sliding_window``, rotary
    positions) or ``full_attention`` (every earlier position, no rotation:
    NoPE). The feed-forward is a dense gated MLP in the first
    ``num_dense_layers`` layers and, after them, ``num_experts`` routed
    experts, ``num_experts_per_tok`` a token (sigmoid scores, a selection
    bias moved by the balance rule at rate ``load_balance_coeff``, weights
    normalised over the selected with the family's 1e-20, times
    ``route_scale``) plus ``num_shared_experts`` shared experts, summed
    before the post-MLP norm. ``mup_enabled``: the embedding is scaled by
    ``sqrt(hidden_size)`` after the lookup (``ScaleVertex``). A final
    RMSNorm and an untied head (``LMHeadLayer``).

    ``layers``: the published layer indices to build, in order (all when
    None). ``vocab_rows``: rows of the embedding and of the head held here.
    ``experts_held``: ``(first, count)`` of the experts of every routed layer
    that live here (all when None), as ``Lfm2Moe``'s. Defaults are the
    published sizes. Trained through ``ComputationGraph.fit`` on ``[B, T]``
    integer ids with ``[B, T]`` integer next-token labels; each routed
    layer's state holds its ``bias`` and ``expert_load``."""

    def __init__(self, layers: Optional[Sequence[int]] = None,
                 vocab_rows: int = 200192,
                 experts_held: Optional[Tuple[int, int]] = None,
                 hidden_size: int = 2048, intermediate_size: int = 6144,
                 moe_intermediate_size: int = 1024,
                 num_attention_heads: int = 32, num_key_value_heads: int = 4,
                 head_dim: int = 128, num_experts: int = 128,
                 num_experts_per_tok: int = 8, num_shared_experts: int = 1,
                 route_scale: float = 2.826, num_dense_layers: int = 2,
                 num_hidden_layers: int = 32,
                 layer_types: Optional[Sequence[str]] = None,
                 global_attn_every_n_layers: int = 4,
                 sliding_window: int = 2048, rms_norm_eps: float = 1e-5,
                 rope_theta: float = 10000.0, mup_enabled: bool = True,
                 load_balance_coeff: float = 0.001,
                 seq_len: Optional[int] = None,
                 compute_dtype: Optional[str] = "bfloat16",
                 state_dtype: Optional[str] = "bfloat16",
                 remat_policy="full", learning_rate: float = 1e-4,
                 weight_decay: float = 0.1, seed: int = 123):
        self.layers = list(range(num_hidden_layers) if layers is None
                           else layers)
        self.layer_types = list(layer_types or [
            "full_attention" if (l + 1) % global_attn_every_n_layers == 0
            else "sliding_attention" for l in range(num_hidden_layers)])
        self.vocab_rows = vocab_rows
        self.experts_held = experts_held or (0, num_experts)
        self.d, self.ff, self.moe_ff = (hidden_size, intermediate_size,
                                        moe_intermediate_size)
        self.heads, self.kv_heads, self.head_dim = (
            num_attention_heads, num_key_value_heads, head_dim)
        self.experts, self.shared = num_experts, num_shared_experts
        self.top_k, self.scale = num_experts_per_tok, route_scale
        self.dense_layers, self.window = num_dense_layers, sliding_window
        self.eps, self.theta = rms_norm_eps, rope_theta
        self.mup, self.balance_rate = mup_enabled, load_balance_coeff
        self.seq_len = seq_len
        self.compute_dtype, self.state_dtype = compute_dtype, state_dtype
        self.remat_policy = remat_policy
        self.learning_rate, self.weight_decay = learning_rate, weight_decay
        self.seed = seed

    def is_sliding(self, l: int) -> bool:
        return self.layer_types[l] == "sliding_attention"

    def _block(self, gb, l: int, prev: str) -> str:
        """One sandwich-norm block under the nodes ``l<l>_*``; returns its
        output node."""
        norm = lambda: L.RMSNormLayer(eps=self.eps)         # noqa: E731
        name, sliding = f"l{l}", self.is_sliding(l)
        gb.add_layer(f"{name}_ln1", norm(), prev)
        gb.add_layer(f"{name}_attn", L.RotaryAttentionLayer(
            n_heads=self.heads, n_kv_heads=self.kv_heads,
            head_dim=self.head_dim, rope_theta=self.theta, eps=self.eps,
            window=self.window if sliding else None, rope=sliding,
            output_gate=True), f"{name}_ln1")
        gb.add_layer(f"{name}_post_ln1", norm(), f"{name}_attn")
        gb.add_vertex(f"{name}_add1", ElementWiseVertex(op="add"),
                      prev, f"{name}_post_ln1")
        gb.add_layer(f"{name}_ln2", norm(), f"{name}_add1")
        if l < self.dense_layers:
            gb.add_layer(f"{name}_ffn", L.GatedMLPLayer(n_ff=self.ff),
                         f"{name}_ln2")
            ffn = f"{name}_ffn"
        else:
            first, held = self.experts_held
            gb.add_layer(f"{name}_ffn", L.RoutedExpertsLayer(
                n_routed=self.experts, n_experts=held, first_expert=first,
                n_ff=self.moe_ff, top_k=self.top_k, scale=self.scale,
                norm_eps=1e-20, bias_update_rate=self.balance_rate),
                f"{name}_ln2")
            # the shared experts: whole on every chip that shares the layer
            gb.add_layer(f"{name}_shared", L.GatedMLPLayer(
                n_ff=self.shared * self.moe_ff, scope="shared_expert"),
                f"{name}_ln2")
            gb.add_vertex(f"{name}_moe", ElementWiseVertex(op="add"),
                          f"{name}_ffn", f"{name}_shared")
            ffn = f"{name}_moe"
        gb.add_layer(f"{name}_post_ln2", norm(), ffn)
        gb.add_vertex(f"{name}_add2", ElementWiseVertex(op="add"),
                      f"{name}_add1", f"{name}_post_ln2")
        return f"{name}_add2"

    def init(self) -> ComputationGraph:
        updater = AdamW(learning_rate=self.learning_rate, beta1=0.9,
                        beta2=0.95, epsilon=1e-8,
                        weight_decay=self.weight_decay)
        updater.state_dtype = self.state_dtype
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(updater))
              .add_inputs("ids"))
        gb.add_layer("embed", L.EmbeddingSequenceLayer(
            n_out=self.d, weight_init="normal"), "ids")
        prev = "embed"
        if self.mup:
            gb.add_vertex("embed_scale", ScaleVertex(scale=self.d ** 0.5),
                          prev)
            prev = "embed_scale"
        for l in self.layers:
            prev = self._block(gb, l, prev)
        gb.add_layer("final_ln", L.RMSNormLayer(eps=self.eps), prev)
        gb.add_layer("head", L.LMHeadLayer(n_out=self.vocab_rows), "final_ln")
        conf = (gb.set_outputs("head")
                .set_input_types(InputType.recurrent(self.vocab_rows,
                                                     self.seq_len))
                .build())
        gc = conf.global_conf
        gc.compute_dtype = self.compute_dtype
        gc.remat_policy = self.remat_policy
        return ComputationGraph(conf).init()


class GraniteHybrid(ZooModel):
    """Granite-4.0-H (``model_type`` ``granitemoehybrid``; IBM's
    granite-4.0-h-micro, huggingface.co/ibm-granite/granite-4.0-h-micro,
    ``config.json``; the family's modelling code is ``models/granitemoehybrid``
    of Hugging Face ``transformers``): a decoder of pre-norm blocks ``x +
    residual_multiplier * Mixer(RMSNorm(x))``, ``x + residual_multiplier *
    MLP(RMSNorm(x))`` whose mixer ``layer_types`` makes a Mamba-2 layer
    (``Mamba2Layer``: ``mamba_n_heads`` heads of ``mamba_d_head``, state
    ``mamba_d_state``, ``mamba_n_groups`` groups of B and C, a causal
    convolution of ``mamba_d_conv`` taps with bias, a gated norm; the scan is
    ``ops.ssm.ssd_scan`` in chunks of ``mamba_chunk_size``) or grouped-query
    attention without position, bias or per-head norm whose softmax scale is
    ``attention_multiplier`` (``RotaryAttentionLayer(rope=False,
    qk_norm=False)``). The MLP is the family's shared MLP with no experts
    (``GatedMLPLayer``, ``shared_intermediate_size`` wide). The embedding is
    scaled by ``embedding_multiplier`` after the lookup and the final norm's
    output by ``1 / logits_scaling`` before the tied head (``ScaleVertex``:
    ``logits = (x E^T) / logits_scaling``); ``residual_multiplier`` rides in
    the residual adds (``ElementWiseVertex(branch_scale=...)``), so it costs
    no vertex of its own.

    ``layers``: the published layer indices to build, in order (all when
    None). ``vocab_rows``: rows of the tied embedding/head held here.
    Defaults are the published sizes. Trained through
    ``ComputationGraph.fit`` on ``[B, T]`` integer ids with ``[B, T]``
    integer next-token labels."""

    def __init__(self, layers: Optional[Sequence[int]] = None,
                 vocab_rows: int = 100352, hidden_size: int = 2048,
                 shared_intermediate_size: int = 8192,
                 num_attention_heads: int = 32, num_key_value_heads: int = 8,
                 attention_multiplier: float = 0.015625,
                 embedding_multiplier: float = 12.0,
                 residual_multiplier: float = 0.22,
                 logits_scaling: float = 8.0, mamba_n_heads: int = 64,
                 mamba_d_head: int = 64, mamba_d_state: int = 128,
                 mamba_n_groups: int = 1, mamba_d_conv: int = 4,
                 mamba_chunk_size: int = 256, num_hidden_layers: int = 40,
                 layer_types: Optional[Sequence[str]] = None,
                 rms_norm_eps: float = 1e-5,
                 seq_len: Optional[int] = None,
                 compute_dtype: Optional[str] = "bfloat16",
                 state_dtype: Optional[str] = "bfloat16",
                 remat_policy="full", learning_rate: float = 1e-4,
                 weight_decay: float = 0.1, seed: int = 123):
        self.layers = list(range(num_hidden_layers) if layers is None
                           else layers)
        # the published pattern: attention at layer 5 of every ten
        self.layer_types = list(layer_types or [
            "attention" if l % 10 == 5 else "mamba"
            for l in range(num_hidden_layers)])
        self.vocab_rows = vocab_rows
        self.d, self.ff = hidden_size, shared_intermediate_size
        self.heads, self.kv_heads = num_attention_heads, num_key_value_heads
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.mamba = dict(d_inner=mamba_n_heads * mamba_d_head,
                          n_heads=mamba_n_heads, d_state=mamba_d_state,
                          n_groups=mamba_n_groups, d_conv=mamba_d_conv,
                          chunk=mamba_chunk_size, eps=rms_norm_eps)
        self.eps = rms_norm_eps
        self.seq_len = seq_len
        self.compute_dtype, self.state_dtype = compute_dtype, state_dtype
        self.remat_policy = remat_policy
        self.learning_rate, self.weight_decay = learning_rate, weight_decay
        self.seed = seed

    def is_attention(self, l: int) -> bool:
        return self.layer_types[l] == "attention"

    def init(self) -> ComputationGraph:
        updater = AdamW(learning_rate=self.learning_rate, beta1=0.9,
                        beta2=0.95, epsilon=1e-8,
                        weight_decay=self.weight_decay)
        updater.state_dtype = self.state_dtype
        gb = (ComputationGraphConfiguration
              .graph_builder(NeuralNetConfiguration.builder()
                             .seed(self.seed).updater(updater))
              .add_inputs("ids"))
        gb.add_layer("embed", L.EmbeddingSequenceLayer(
            n_out=self.d, weight_init="normal"), "ids")
        gb.add_vertex("embed_scale", ScaleVertex(
            scale=self.embedding_multiplier), "embed")
        norm = lambda: L.RMSNormLayer(eps=self.eps)         # noqa: E731
        add = lambda: ElementWiseVertex(                    # noqa: E731
            op="add", branch_scale=self.residual_multiplier)
        prev = "embed_scale"
        for l in self.layers:
            if self.is_attention(l):
                mixer, mixer_layer = f"l{l}_attn", L.RotaryAttentionLayer(
                    n_heads=self.heads, n_kv_heads=self.kv_heads,
                    head_dim=self.d // self.heads, eps=self.eps, rope=False,
                    qk_norm=False, sm_scale=self.attention_multiplier)
            else:
                mixer, mixer_layer = f"l{l}_mamba", L.Mamba2Layer(
                    **self.mamba)
            gb.add_layer(f"l{l}_ln1", norm(), prev)
            gb.add_layer(mixer, mixer_layer, f"l{l}_ln1")
            gb.add_vertex(f"l{l}_add1", add(), prev, mixer)
            gb.add_layer(f"l{l}_ln2", norm(), f"l{l}_add1")
            gb.add_layer(f"l{l}_mlp", L.GatedMLPLayer(n_ff=self.ff),
                         f"l{l}_ln2")
            gb.add_vertex(f"l{l}_add2", add(), f"l{l}_add1", f"l{l}_mlp")
            prev = f"l{l}_add2"
        gb.add_layer("final_ln", norm(), prev)
        gb.add_vertex("head_scale", ScaleVertex(
            scale=1.0 / self.logits_scaling), "final_ln")
        gb.add_layer("head", L.TiedOutputLayer(tied_to="embed"), "head_scale")
        conf = (gb.set_outputs("head")
                .set_input_types(InputType.recurrent(self.vocab_rows,
                                                     self.seq_len))
                .build())
        gc = conf.global_conf
        gc.compute_dtype = self.compute_dtype
        gc.remat_policy = self.remat_policy
        return ComputationGraph(conf).init()
