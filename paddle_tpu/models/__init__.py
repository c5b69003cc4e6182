"""paddle_tpu.models — flagship model families (BASELINE configs 3-5).

Served through the paged ``DecodeEngine``, which meets a family through
``paged_stack.PagedPrograms`` (``models/paged_stack.py``: the seam, and
the block walk, run scan and greedy chunk the families' programs share):
``llama`` (every option), and four families with paged programs of their
own, each of which refuses at construction (``unsupported``, option ->
why) the prefix cache, chunked prefill, speculative decoding, int8 KV, a
mesh and the contiguous engine: ``granite_hybrid`` (per-slot recurrent
state), ``mimo_v2`` (window rings, a held share of experts),
``glm_moe_dsa`` (a latent page and an indexer page, learned sparse
attention) and ``deepseek_v3`` (ONE kind of page, the latent's, read
densely; routing inside groups of experts; YaRN).

Vision models (LeNet/ResNet/VGG/MobileNet — configs 1-2) live in
paddle_tpu.vision.models."""

from .llama import LlamaConfig, LlamaForCausalLM, llama_loss_fn, LLAMA_PRESETS  # noqa: F401
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM, GRANITE_PRESETS)
from .mimo_v2 import (  # noqa: F401
    MimoV2Config, MimoV2ForCausalLM, MIMO_V2_PRESETS)
from .glm_moe_dsa import (  # noqa: F401
    GlmMoeDsaConfig, GlmMoeDsaForCausalLM, GLM_MOE_DSA_PRESETS)
from .deepseek_v3 import (  # noqa: F401
    DeepseekV3Config, DeepseekV3ForCausalLM, DEEPSEEK_V3_PRESETS)
from .gpt import GPTConfig, GPTForCausalLM, GPT_PRESETS  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForMaskedLM, BertForSequenceClassification,
    BERT_PRESETS,
)

__all__ = ["LlamaConfig", "LlamaForCausalLM", "llama_loss_fn",
           "LLAMA_PRESETS", "GraniteHybridConfig",
           "GraniteHybridForCausalLM", "GRANITE_PRESETS", "MimoV2Config",
           "MimoV2ForCausalLM", "MIMO_V2_PRESETS", "GlmMoeDsaConfig",
           "GlmMoeDsaForCausalLM", "GLM_MOE_DSA_PRESETS", "DeepseekV3Config",
           "DeepseekV3ForCausalLM", "DEEPSEEK_V3_PRESETS", "GPTConfig", "GPTForCausalLM", "GPT_PRESETS", "BertConfig", "BertModel",
           "BertForMaskedLM", "BertForSequenceClassification",
           "BERT_PRESETS"]
