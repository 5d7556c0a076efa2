from .bert import bert_config, bert_model
from .evabyte import evabyte_config, evabyte_model
from .families import (bloom_config, bloom_model, falcon_config,
                       falcon_model, gpt_neox_config, gpt_neox_model,
                       mistral_config,
                       mistral_model, opt_config, opt_model, phi_config,
                       phi_model, qwen_config, qwen_model)
from .gpt2 import gpt2_config, gpt2_model
from .laguna import laguna_config, laguna_model
from .lfm2_moe import lfm2_moe_config, lfm2_moe_model
from .llama import llama_config, llama_model
from .mimo_v2 import mimo_v2_config, mimo_v2_model
from .mistral4 import mistral4_config, mistral4_model
from .mixtral import mixtral_config, mixtral_model
from .phi4_flash import phi4_flash_config, phi4_flash_model
from .sdar_moe import sdar_moe_config, sdar_moe_model
from .solar_open2 import solar_open2_config, solar_open2_model
from .transformer import TransformerConfig
from .xing4 import xing4_config, xing4_model

__all__ = ["bert_config", "bert_model", "gpt2_config", "gpt2_model",
           "llama_config", "llama_model", "mixtral_config", "mixtral_model",
           "mistral_config", "mistral_model", "qwen_config", "qwen_model",
           "phi_config", "phi_model", "opt_config", "opt_model",
           "falcon_config", "falcon_model", "bloom_config", "bloom_model",
           "gpt_neox_config", "gpt_neox_model", "solar_open2_config",
           "solar_open2_model", "lfm2_moe_config", "lfm2_moe_model",
           "phi4_flash_config", "phi4_flash_model", "mistral4_config",
           "mistral4_model", "mimo_v2_config", "mimo_v2_model",
           "sdar_moe_config", "sdar_moe_model", "laguna_config",
           "laguna_model", "xing4_config", "xing4_model",
           "evabyte_config", "evabyte_model",
           "TransformerConfig"]
