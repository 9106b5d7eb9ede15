"""Model zoo.

Rebuild of upstream ``org.deeplearning4j.zoo``: ``ZooModel`` SPI with LeNet,
SimpleCNN, AlexNet, VGG16, ResNet-50, Darknet19, TinyYOLO-style backbone,
UNet, TextGenerationLSTM — plus BERT (the reference reaches BERT only through
TF import; here it is first-class, built on the transformer layers).

Each zoo model is a config factory: ``init()`` returns a ready
``MultiLayerNetwork``/``ComputationGraph`` built from the same builder DSL a
user would write, so the zoo doubles as an API test surface (reference
``TestInstantiation`` pattern). ``init_pretrained()`` loads weights from a
local archive path (offline environment; the reference downloads from Azure).
"""

from deeplearning4j_tpu.zoo.base import ZooModel
from deeplearning4j_tpu.zoo.lenet import LeNet
from deeplearning4j_tpu.zoo.simple_cnn import SimpleCNN
from deeplearning4j_tpu.zoo.alexnet import AlexNet
from deeplearning4j_tpu.zoo.vgg16 import VGG16
from deeplearning4j_tpu.zoo.resnet50 import ResNet50
from deeplearning4j_tpu.zoo.unet import UNet
from deeplearning4j_tpu.zoo.darknet19 import Darknet19
from deeplearning4j_tpu.zoo.textgen_lstm import TextGenerationLSTM
from deeplearning4j_tpu.zoo.bert import Bert
from deeplearning4j_tpu.zoo.glm_moe_lite import GlmMoeLite
from deeplearning4j_tpu.zoo.kimi_linear import KimiLinear
from deeplearning4j_tpu.zoo.lfm2_moe import Lfm2Moe
from deeplearning4j_tpu.zoo.sdar_moe import SdarMoe
from deeplearning4j_tpu.zoo.vgg19 import VGG19
from deeplearning4j_tpu.zoo.squeezenet import SqueezeNet
from deeplearning4j_tpu.zoo.xception import Xception
from deeplearning4j_tpu.zoo.inception_resnet import InceptionResNetV1
from deeplearning4j_tpu.zoo.yolo2 import TinyYOLO, YOLO2

__all__ = ["ZooModel", "LeNet", "SimpleCNN", "AlexNet", "VGG16", "VGG19",
           "ResNet50", "UNet", "Darknet19", "TextGenerationLSTM", "Bert", "KimiLinear", "GlmMoeLite", "SdarMoe", "Lfm2Moe",
           "SqueezeNet", "Xception", "InceptionResNetV1", "TinyYOLO", "YOLO2"]
