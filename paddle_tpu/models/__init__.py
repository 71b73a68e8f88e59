"""Model zoo."""
from .llama import LlamaConfig, LlamaForCausalLM  # noqa: F401
from .bert import BertConfig, BertForPretraining, BertForSequenceClassification, BertModel  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .unet import UNetConfig, UNetModel  # noqa: F401
