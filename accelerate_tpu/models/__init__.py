from .bert import BertConfig, BertForSequenceClassification
from .glm5 import Glm5, Glm5Config
from .gpt2 import GPT2, GPT2Config
from .gptx import GPTX, GPTXConfig
from .laguna import Laguna, LagunaConfig
from .llama import Llama, LlamaConfig
from .minicpm_sala import MiniCPMSALA, MiniCPMSALAConfig
from .moe import MoELlama, MoELlamaConfig
from .t5 import T5Config, T5ForConditionalGeneration
from .vision import ConvNetConfig, ConvNetForImageClassification
from .vit import ViTConfig, ViTForImageClassification
from .whisper import WhisperConfig, WhisperForConditionalGeneration


def __getattr__(name):
    # Lazy: convert.py pulls in numpy/jax paths only needed for HF interop.
    if name in ("from_hf", "from_hf_checkpoint", "llama_config_from_hf",
                "llama_params_from_hf", "gpt2_config_from_hf", "gpt2_params_from_hf",
                "bert_config_from_hf", "bert_params_from_hf",
                "t5_config_from_hf", "t5_params_from_hf",
                "mixtral_config_from_hf", "mixtral_params_from_hf",
                "qwen2_config_from_hf", "qwen2_params_from_hf",
                "qwen3_config_from_hf", "qwen3_params_from_hf",
                "phi3_config_from_hf", "phi3_params_from_hf",
                "gemma_config_from_hf", "gemma_params_from_hf",
                "gpt_neox_config_from_hf", "gpt_neox_params_from_hf",
                "gptj_config_from_hf", "gptj_params_from_hf",
                "opt_config_from_hf", "opt_params_from_hf",
                "whisper_config_from_hf", "whisper_params_from_hf",
                "vit_config_from_hf", "vit_params_from_hf"):
        from . import convert

        return getattr(convert, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
