"""Model zoo (reference `deeplearning4j-zoo/.../zoo/model/*.java`).

Each ZooModel builds an untrained MultiLayerNetwork / ComputationGraph with
the canonical architecture; pretrained weight loading hooks exist but ship
no weights (the reference fetches them from an external blob store — no
egress here; `set_params`/`load` accept externally converted checkpoints).
"""
from deeplearning4j_tpu.zoo.base import ZooModel, ZOO_REGISTRY, zoo_model  # noqa: F401
from deeplearning4j_tpu.zoo.models import (  # noqa: F401
    AlexNet, Darknet19, LeNet, SimpleCNN, TextGenLSTM, VGG16, VGG19)
from deeplearning4j_tpu.zoo.graphs import (  # noqa: F401
    ResNet50, SqueezeNet, UNet)
from deeplearning4j_tpu.zoo.bert import BertConfig, BertModel  # noqa: F401
from deeplearning4j_tpu.zoo.decoder import (  # noqa: F401
    DecoderConfig, DecoderModel)
from deeplearning4j_tpu.zoo.vision import (  # noqa: F401
    InceptionResNetV1, TinyYOLO, Xception, YOLO2)
from deeplearning4j_tpu.zoo.nasnet import NASNet  # noqa: F401
