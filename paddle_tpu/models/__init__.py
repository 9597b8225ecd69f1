"""Language-model zoo: the workload families from BASELINE.md.

ref: test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py
(Llama), python/paddle/nn/layer/transformer.py (BERT building blocks),
incubate/distributed/models/moe/moe_layer.py (ERNIE-MoE). TPU-native:
every model is a plain nn.Layer whose parameters can carry NamedShardings
(tp/fsdp/sp placements), so one jit of the train step compiles the full
hybrid-parallel program.
"""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, LlamaPretrainingCriterion,
    shard_llama,
)
from .gpt import GPTConfig, GPTForCausalLM, shard_gpt  # noqa: F401
from .bert import BertConfig, BertForMaskedLM, BertModel  # noqa: F401
from .ernie_moe import ErnieMoEConfig, ErnieMoEForCausalLM  # noqa: F401
from .llama_pipe import LlamaForCausalLMPipe  # noqa: F401
from .cohere2_moe import (  # noqa: F401
    Cohere2MoeConfig, Cohere2MoeForCausalLM,
)
from .glm_moe_dsa import (  # noqa: F401
    GlmMoeDsaConfig, GlmMoeDsaForCausalLM,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, SolarOpen2ForCausalLM,
)
from .brumby import (  # noqa: F401
    BrumbyConfig, BrumbyForCausalLM,
)
