"""Model zoo: the BASELINE.md benchmark configs built on the framework."""

from .lenet import lenet_conf
from .char_rnn import char_rnn_conf, CharacterIterator
from .resnet import resnet_conf, resnet50_conf, resnet_tiny_conf
from .vgg16 import (vgg16_conf, VGG16ImagePreProcessor, ImageNetLabels,
                    TrainedModels)
from .transformer import (transformer_lm_conf, latent_moe_lm_conf,
                          shortcut_moe_lm_conf, hybrid_ssm_lm_conf,
                          lm_batch, lm_batch_sparse, generate)
from .generation import (TransformerDecoder, SlotGenerationEngine,
                         GenerationRequest)
from .paging import PageAllocator, prefix_route_key

__all__ = ["lenet_conf", "char_rnn_conf", "CharacterIterator",
           "transformer_lm_conf", "latent_moe_lm_conf",
           "shortcut_moe_lm_conf", "hybrid_ssm_lm_conf", "lm_batch",
           "lm_batch_sparse", "generate",
           "TransformerDecoder", "SlotGenerationEngine", "GenerationRequest",
           "PageAllocator", "prefix_route_key",
           "resnet_conf", "resnet50_conf", "resnet_tiny_conf",
           "vgg16_conf", "VGG16ImagePreProcessor", "ImageNetLabels",
           "TrainedModels"]
