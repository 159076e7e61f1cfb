from neuronx_distributed_tpu.models.afmoe import (
    AfmoeConfig,
    AfmoeForCausalLM,
    AfmoeModel,
    tiny_afmoe,
    trinity_large,
)
from neuronx_distributed_tpu.models.bert import (
    BertConfig,
    BertForMaskedLM,
    BertModel,
    bert_large,
    tiny_bert,
)
from neuronx_distributed_tpu.models.codegen import (
    CodeGenConfig,
    CodeGenForCausalLM,
    codegen25_7b,
    tiny_codegen,
)
from neuronx_distributed_tpu.models.dbrx import (
    DbrxConfig,
    DbrxForCausalLM,
    dbrx_base,
    tiny_dbrx,
)
from neuronx_distributed_tpu.models.gpt_neox import (
    GPTNeoXConfig,
    GPTNeoXForCausalLM,
    gpt_neox_20b,
    tiny_gpt_neox,
)
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama2_7b,
    llama2_70b,
    llama3_8b,
    tiny_llama,
)
from neuronx_distributed_tpu.models.mixtral import (
    MixtralConfig,
    MixtralForCausalLM,
    MixtralModel,
    mixtral_8x7b,
    tiny_mixtral,
)
from neuronx_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2Config,
    DeepseekV2ForCausalLM,
    DeepseekV2Model,
    deepseek_v2_lite,
    tiny_deepseek_v2,
)
from neuronx_distributed_tpu.models.glm_moe_dsa import (
    GlmMoeDsaConfig,
    GlmMoeDsaForCausalLM,
    GlmMoeDsaModel,
    glm5,
    tiny_glm_moe_dsa,
)
from neuronx_distributed_tpu.models.keye_vl2 import (
    KeyeVL2Config,
    KeyeVL2ForCausalLM,
    KeyeVL2Model,
    keye_vl2_30b_a3b,
    tiny_keye_vl2,
)
from neuronx_distributed_tpu.models.zaya import (
    ZayaConfig,
    ZayaForCausalLM,
    ZayaModel,
    tiny_zaya,
    zaya1_8b,
)
from neuronx_distributed_tpu.models.vit import (
    ViTConfig,
    ViTForImageClassification,
    tiny_vit,
    vit_base_patch16,
)

__all__ = [
    "LlamaConfig", "LlamaForCausalLM", "LlamaModel",
    "llama2_7b", "llama2_70b", "llama3_8b", "tiny_llama",
    "MixtralConfig", "MixtralForCausalLM", "MixtralModel",
    "mixtral_8x7b", "tiny_mixtral",
    "BertConfig", "BertForMaskedLM", "BertModel", "bert_large", "tiny_bert",
    "GPTNeoXConfig", "GPTNeoXForCausalLM", "gpt_neox_20b", "tiny_gpt_neox",
    "DbrxConfig", "DbrxForCausalLM", "dbrx_base", "tiny_dbrx",
    "ViTConfig", "ViTForImageClassification", "vit_base_patch16", "tiny_vit",
    "CodeGenConfig", "CodeGenForCausalLM", "codegen25_7b", "tiny_codegen",
    "DeepseekV2Config", "DeepseekV2ForCausalLM", "DeepseekV2Model",
    "deepseek_v2_lite", "tiny_deepseek_v2",
    "GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "GlmMoeDsaModel",
    "glm5", "tiny_glm_moe_dsa",
    "KeyeVL2Config", "KeyeVL2ForCausalLM", "KeyeVL2Model",
    "keye_vl2_30b_a3b", "tiny_keye_vl2",
    "AfmoeConfig", "AfmoeForCausalLM", "AfmoeModel", "trinity_large", "tiny_afmoe",
    "ZayaConfig", "ZayaForCausalLM", "ZayaModel", "zaya1_8b", "tiny_zaya",
]
