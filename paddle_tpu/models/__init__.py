"""Model zoo: flagship LLMs (Llama-2 family, GPT-3 family) and vision/SSM
models, all with mesh-sharding annotations built in.

Role parity: the reference ships model zoos in ``python/paddle/vision/models``
and ergonomics for large NLP models via PaddleNLP recipes (BASELINE.json
configs: Llama-2 7B/70B, GPT-3 6.7B, ERNIE, ViT-L, Mamba-2).
"""

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.mamba import MambaConfig, MambaForCausalLM
from paddle_tpu.models.mlp import MLP, MNISTClassifier
from paddle_tpu.models.moe import MoEConfig, MoEForCausalLM
from paddle_tpu.models.deepseek_v3 import (
    DeepseekV3Config, DeepseekV3ForCausalLM,
)
from paddle_tpu.models.smallthinker import (
    SmallThinkerConfig, SmallThinkerForCausalLM,
)
from paddle_tpu.models.kimi_linear import (
    KimiLinearConfig, KimiLinearForCausalLM,
)
from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models.ernie import ErnieConfig, ErnieForPretraining, ErnieModel
