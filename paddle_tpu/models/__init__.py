"""Flagship model zoo (BASELINE configs): GPT / BERT / ERNIE, and the
Xing4.0 decoder (MLA + dropless experts + hyper-connections) and the
Cohere2-MoE decoder (grouped-query window and full layers, a parallel
attention + expert block) and the SDAR-MoE decoder (block-causal attention,
softmax-routed experts, generation by diffusion over blocks) and the
Qwen3-Next decoder (gated DeltaNet layers with a recurrent state beside
gated full-attention layers, softmax-routed experts with a gated shared
expert) of the served path."""
from . import (bert, cohere2_moe, ernie, gpt, qwen3_next,  # noqa: F401
               sdar_moe, xing4)
from .bert import (BertConfig, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, BertModel,
                   BertPretrainingCriterion, bert_base, bert_tiny)
from .ernie import (ErnieConfig, ErnieForSequenceClassification,  # noqa: F401
                    ErnieModel, build_static_inference_program,
                    ernie_3p0_medium, ernie_tiny)
from .gpt import (GPTConfig, GPTForPretraining, GPTModel,  # noqa: F401
                  GPTPretrainingCriterion, gpt2_small, gpt3_1p3b, gpt3_6p7b,
                  gpt_tiny, gpt_tiny_moe)
from .cohere2_moe import Cohere2MoeConfig, Cohere2MoeModel  # noqa: F401
from .qwen3_next import Qwen3NextConfig, Qwen3NextModel  # noqa: F401
from .sdar_moe import SdarMoeConfig, SdarMoeModel  # noqa: F401
from .xing4 import Xing4Config, Xing4Model  # noqa: F401
