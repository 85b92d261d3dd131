"""Flax model zoo for examples and benchmarks.

The reference ships its models inside example scripts (example/pytorch/
benchmark_byteps.py uses torchvision ResNet-50, SURVEY.md §2.6); we ship
TPU-first flax implementations of the benchmark families named in
BASELINE.md: ResNet-50 (ImageNet), BERT-Large, GPT-2 345M, plus a small
MLP used by the test suite.
"""

from byteps_tpu.models.mlp import MLP  # noqa: F401
from byteps_tpu.models.resnet import ResNet, ResNet18, ResNet50  # noqa: F401
from byteps_tpu.models.vgg import VGG, VGG16, VGG19  # noqa: F401
from byteps_tpu.models.llama import (  # noqa: F401
    Llama1B,
    Llama7B,
    LlamaModel,
    LlamaTiny,
)
from byteps_tpu.models.keye import Keye30BA3B, KeyeModel, KeyeTiny, keye_loss  # noqa: F401,E501
from byteps_tpu.models.kimi_linear import KimiLinear48BA3B, KimiLinearModel, KimiLinearTiny, kimi_linear_loss  # noqa: F401,E501
from byteps_tpu.models.joyai import JoyAIFlash48BA3B, JoyAIFlashModel, JoyAIFlashTiny, joyai_loss, publish_mtp_stats  # noqa: F401,E501
from byteps_tpu.models.laguna import LagunaModel, LagunaTiny, LagunaXS2, laguna_loss  # noqa: F401,E501
from byteps_tpu.models.qwen3_next import Qwen3Next80BA3B, Qwen3NextModel, Qwen3NextTiny, qwen3_next_loss  # noqa: F401,E501
from byteps_tpu.models.zaya import Zaya1_8B, ZayaModel, ZayaTiny, zaya_loss  # noqa: F401,E501
from byteps_tpu.models.mellum import Mellum2_12B, MellumModel, MellumTiny, mellum_loss  # noqa: F401,E501
from byteps_tpu.models.nemotron_h import Nemotron3Nano30BA3B, NemotronHModel, NemotronHTiny, nemotron_h_loss  # noqa: F401,E501
from byteps_tpu.models.phi4_flash import Phi4FlashModel, Phi4FlashTiny, Phi4MiniFlash, phi4_flash_loss  # noqa: F401,E501
from byteps_tpu.models.ouro import Ouro2_6B, OuroModel, OuroTiny, ouro_loss, publish_loop_stats  # noqa: F401,E501
from byteps_tpu.models.olmoe import Olmoe1B7B, OlmoeModel, OlmoeTiny, olmoe_loss  # noqa: F401,E501
from byteps_tpu.models.transformer import (  # noqa: F401
    BertBase,
    BertLarge,
    GPT2Medium,
    GPT2Small,
    TransformerEncoder,
    TransformerLM,
    lm_log_likelihood,
    lm_loss,
    masked_lm_loss,
    sp_lm_loss,
)
