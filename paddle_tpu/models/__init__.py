"""Model zoo (the PaddleNLP/ppdiffusers-analog families, in-repo since the
TPU build is self-contained): transformer LMs (ERNIE/LLaMA-style), BERT,
and the diffusion UNet."""

from . import gpt
from . import bert
from . import unet
from . import llama
from . import deepseek_v2
from . import xing4
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, ERNIE_7B, LLAMA2_13B
from .bert import BertConfig, BertModel, BertForMaskedLM
from .unet import UNetConfig, UNet2DConditionModel
from .llama import (
    LlamaConfig, LlamaModel, LlamaForCausalLM,
    LLAMA2_7B, LLAMA3_8B,
)
from .deepseek_v2 import DeepSeekV2Config, DeepSeekV2Model, DeepSeekV2ForCausalLM
from .xing4 import Xing4Config, Xing4Model, Xing4ForCausalLM
