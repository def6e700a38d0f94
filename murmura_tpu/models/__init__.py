"""Model zoo (reference families: murmura/examples/leaf/, murmura/examples/wearables/)."""

from murmura_tpu.models.core import Model
from murmura_tpu.models.mlp import make_mlp, make_wearable_mlp
from murmura_tpu.models.cnn import make_femnist_cnn, make_celeba_cnn, FEMNIST_VARIANTS
from murmura_tpu.models.lstm import make_char_lstm
from murmura_tpu.models.decoder import make_deepseek_v3
from murmura_tpu.models.zaya import make_zaya1
from murmura_tpu.models.registry import build_model

__all__ = [
    "Model",
    "make_mlp",
    "make_wearable_mlp",
    "make_femnist_cnn",
    "make_celeba_cnn",
    "make_char_lstm",
    "make_deepseek_v3",
    "make_zaya1",
    "build_model",
    "FEMNIST_VARIANTS",
]
