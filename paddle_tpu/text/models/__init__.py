"""paddle_tpu.text.models — language model zoo (reference capability:
PaddleNLP-style GPT/BERT/ERNIE driven through fleet; here built-in
since the benchmark ladder needs them: BASELINE configs 3-5)."""
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt2_small, gpt2_345m
from .bert import BertConfig, BertModel, BertForPretraining, bert_base
from .ernie import ErnieConfig, ErnieModel, ErnieForPretraining
from .glm4_moe_lite import (Glm4MoeLiteConfig, Glm4MoeLiteModel,
                            Glm4MoeLiteForCausalLM)
from .longcat_flash import (LongcatFlashConfig, LongcatFlashModel,
                            LongcatFlashForCausalLM)
from .lfm2_moe import (Lfm2MoeConfig, Lfm2MoeModel,
                       Lfm2MoeForCausalLM)
