"""Model zoo (reference: example/ — mnist MLP/LeNet, cifar10 Inception-BN,
imagenet AlexNet/Inception-BN, rnn unrolled LSTM), plus the modern TPU
flagships (ResNet-50 for the north-star benchmark, a transformer LM for
tensor/sequence-parallel training)."""

from .mlp import mlp
from .lenet import lenet
from .alexnet import alexnet
from .inception import inception_bn_cifar, inception_bn
from .resnet import resnet, resnet50
from .lstm import lstm_unroll, LSTMState, LSTMParam
from .lstm_scan import LSTMLM
from .transformer import TransformerLM, transformer_lm_config
from .moe_transformer import MoEPipelineLM, moe_pipeline_config
from .laguna import laguna
from .sdar import sdar
from .kanana2 import kanana2
from .nemotron_h import nemotron_h

__all__ = ["mlp", "lenet", "alexnet", "inception_bn_cifar", "inception_bn",
           "resnet", "resnet50", "lstm_unroll", "LSTMState", "LSTMParam",
           "LSTMLM", "TransformerLM", "transformer_lm_config",
           "MoEPipelineLM", "moe_pipeline_config", "laguna", "sdar",
           "kanana2", "nemotron_h"]
