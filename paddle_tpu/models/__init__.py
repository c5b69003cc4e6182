"""paddle_tpu.models — flagship model families (BASELINE configs 3-5).

Vision models (LeNet/ResNet/VGG/MobileNet — configs 1-2) live in
paddle_tpu.vision.models."""

from .llama import LlamaConfig, LlamaForCausalLM, llama_loss_fn, LLAMA_PRESETS  # noqa: F401
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM, GRANITE_PRESETS)
from .mimo_v2 import (  # noqa: F401
    MimoV2Config, MimoV2ForCausalLM, MIMO_V2_PRESETS)
from .glm_moe_dsa import (  # noqa: F401
    GlmMoeDsaConfig, GlmMoeDsaForCausalLM, GLM_MOE_DSA_PRESETS)
from .gpt import GPTConfig, GPTForCausalLM, GPT_PRESETS  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForMaskedLM, BertForSequenceClassification,
    BERT_PRESETS,
)

__all__ = ["LlamaConfig", "LlamaForCausalLM", "llama_loss_fn",
           "LLAMA_PRESETS", "GraniteHybridConfig",
           "GraniteHybridForCausalLM", "GRANITE_PRESETS", "MimoV2Config",
           "MimoV2ForCausalLM", "MIMO_V2_PRESETS", "GlmMoeDsaConfig",
           "GlmMoeDsaForCausalLM", "GLM_MOE_DSA_PRESETS", "GPTConfig", "GPTForCausalLM", "GPT_PRESETS", "BertConfig", "BertModel",
           "BertForMaskedLM", "BertForSequenceClassification",
           "BERT_PRESETS"]
