"""Flagship model zoo (NLP): GPT / BERT pretraining models.

Role parity: the reference's headline workloads are PaddleNLP ERNIE/GPT
pretraining (BASELINE.json configs 2-3); PaddleNLP is a separate repo, so
this package provides the equivalent in-framework model family, built
TPU-first (fused SDPA, TP/PP-ready blocks, one-jit train step).
"""

from .gpt import (  # noqa: F401
    GPTConfig, GPTForPretraining, GPTForPretrainingPipe, GPTModel,
    GPTPretrainingCriterion, build_functional_train_step,
    gpt_tiny, gpt_small, gpt_medium, gpt_1p3b, gpt_13b,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, BertPretrainingCriterion,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieModel, ErnieForPretraining, ErniePretrainingCriterion,
    ErnieForSequenceClassification, ErnieForTokenClassification,
    ernie_3_0_base, ernie_3_0_medium, ernie_3_0_micro,
)
from .generation import (  # noqa: F401
    build_beam_search_fn, build_generate_fn, generate,
)
from .cohere2_moe import (  # noqa: F401
    Cohere2MoeConfig, Cohere2MoeForCausalLM,
)
from .falcon_h1 import (  # noqa: F401
    FalconH1Config, FalconH1ForCausalLM,
)
from .rec import (  # noqa: F401
    RecConfig, DeepFM, WideDeep, FusedSparseEmbedding, synthetic_click_batch,
)
