"""Model zoo: TPU-first flax models used by the examples and benchmarks."""

from horovod_tpu.models.inception import InceptionV3, VGG16   # noqa: F401
from horovod_tpu.models.mlp import MLP, ConvNet          # noqa: F401
from horovod_tpu.models.resnet import (                   # noqa: F401
    ResNet, ResNet50, ResNet101, ResNet152,
)
from horovod_tpu.models.transformer import (               # noqa: F401
    BlockStack, CompressedConvAttention, GraniteHybridLM,
    GroupedQueryAttention, JoyAIFlashLM, KeyeLM, KimiLinearLM, LagunaLM,
    LatentAttention,
    MultiTokenPrediction, Nemotron3SuperLM,
    NemotronHLM, OLMoELM, OlmoHybridLM, ResidualMerge, SDARLM, SwiGLU,
    TransformerLM, Zaya1LM, apply_rotary, index_losses)
