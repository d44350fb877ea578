"""Model zoo: flagship architectures built on paddle_tpu.nn.

The reference ships its model zoo in python/paddle/vision/models (CNNs) and,
for the Fleet GPT benchmark path, GPT implementations in the PaddleNLP/
fleet examples built from fleet/layers/mpu/mp_layers.py. Here the language
flagship (GPT) lives in-tree because it is the hybrid-parallel benchmark
target (BASELINE.md: "Fleet hybrid-parallel GPT ... tokens/sec").
"""
from .axk1 import (  # noqa: F401
    AXK1Config,
    AXK1ForCausalLM,
    AXK1Model,
    AXK1Stack,
    axk1_tiny,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    bert_base,
    bert_large,
    bert_tiny,
    ernie_base,
)
from .brumby import (  # noqa: F401
    BrumbyConfig,
    BrumbyForCausalLM,
    BrumbyLayers,
    BrumbyModel,
    brumby_tiny,
)
from .cohere2_moe import (  # noqa: F401
    Cohere2MoEBlock,
    Cohere2MoEConfig,
    Cohere2MoEForCausalLM,
    Cohere2MoEModel,
    cohere2_moe_tiny,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    GPTPretrainingCriterion,
    gpt_tiny,
    gpt2_small,
    gpt2_medium,
    gpt_1p3b,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaModel,
    LlamaPretrainingCriterion,
    llama2_7b,
    llama3_8b,
    llama_tiny,
)
