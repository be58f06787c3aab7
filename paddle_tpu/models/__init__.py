from .gpt import (GPTConfig, GPTModel, GPTForPretraining,  # noqa: F401
                  GPTPretrainingCriterion, build_train_step,
                  init_gpt_params)
from . import bert  # noqa: F401
from . import llama  # noqa: F401
from .bert import BERT_CONFIGS, BertConfig  # noqa: F401
from .llama import LLAMA_CONFIGS, LlamaConfig  # noqa: F401
from . import mla_moe  # noqa: F401
from .mla_moe import MlaMoeConfig  # noqa: F401
