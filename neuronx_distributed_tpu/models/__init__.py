"""The model families.

What a causal LM returns in each ``mode`` (every ``*ForCausalLM`` here and
``MedusaForCausalLM``, one contract):

- ``mode="train"``: logits at EVERY position, ``(B, S, V)``.
- ``mode="prefill"``: the whole prompt goes through the layers and into the
  cache, and the head is applied to the LAST position alone: logits
  ``(B, 1, V)`` (``if self.mode == "prefill": x = x[:, -1:]`` between the final
  norm and the head). Every caller of a prefill reads ``[:, -1]`` of a
  LEFT-padded prompt and no other row (``inference/generate.py``, the
  speculative pair, Medusa, ``ServingEngine``, the disaggregated prefill
  worker), and XLA does not push that slice through the head's matmul: at
  every position the logits of a 20,992-token DeepSeek-V2-Lite prompt are
  4.3 GiB in bf16 and a ninth of the prefill's time.
- ``mode="decode"``: logits at every position of the step's window,
  ``(B, W, V)`` (W = 1, or a speculative round's window).

Logits at every position of a context: ``mode="train"``, or, where the family
has one, the headless ``*Model`` in ``prefill`` mode and the head's kernel
(``params["params"]["lm_head"]["kernel"]``), as ``chip_smoke.py``'s reference
comparisons do.

``solar_open2`` (``SolarOpen2ForCausalLM``) keeps the same contract with a
decode window of ONE token: its linear-attention layers take a slot's token
through a recurrent state, in order (``models/solar_open2.py``).

``ouro`` (``OuroForCausalLM``) is a LOOPED stack: its layers run
``total_ut_steps`` times over one set of weights, each pass on a K/V cache
node of its own (``layers_<i>/attn/pass_<t>``), and the final norm closes
every pass; ``train`` also returns the passes' exit distribution, the served
modes return the last pass's logits; its prefill is one scan over the passes
and, under the paged engine, gives out a row of the prompt bucket's columns
(``OuroConfig.bucket_prefill_rows``). Same contract, with a decode window of
ONE token on the paged engine's fused path: the walking kernel reads one query
row a slot (``models/ouro.py``)."""

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
from neuronx_distributed_tpu.models.solar_open2 import (
    SolarOpen2Config,
    SolarOpen2ForCausalLM,
    SolarOpen2Model,
    solar_open2_250b,
    tiny_solar_open2,
)
from neuronx_distributed_tpu.models.ouro import (
    OuroConfig,
    OuroForCausalLM,
    OuroModel,
    ouro_2_6b,
    tiny_ouro,
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
    "SolarOpen2Config", "SolarOpen2ForCausalLM", "SolarOpen2Model",
    "solar_open2_250b", "tiny_solar_open2",
    "OuroConfig", "OuroForCausalLM", "OuroModel", "ouro_2_6b", "tiny_ouro",
]
