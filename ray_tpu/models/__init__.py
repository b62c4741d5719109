from ray_tpu.models.bert import (Bert, BertConfig, bert_base,
                                 bert_sharding_rules, bert_tiny,
                                 mask_tokens, mlm_loss)
from ray_tpu.models.t5 import (T5, T5Config, greedy_decode as
                               t5_greedy_decode, seq2seq_loss,
                               t5_sharding_rules, t5_small, t5_tiny)
from ray_tpu.models.gpt2 import (GPT2, GPT2Config, gpt2_sharding_rules,
                                 gpt2_124m)
from ray_tpu.models.llama import (Llama, LlamaConfig, generate,
                                  llama2_7b, llama_sharding_rules,
                                  llama_tiny)
from ray_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                    mixtral_8x7b, mixtral_sharding_rules,
                                    mixtral_tiny, moe_aux_loss,
                                    olmoe_1b_7b, olmoe_tiny)
from ray_tpu.models.granite_hybrid import (GraniteHybrid,
                                           GraniteHybridConfig,
                                           granite_hybrid_tiny)
from ray_tpu.models.resnet import ResNet, ResNetConfig, resnet50, resnet18
from ray_tpu.models.vit import (ViT, ViTConfig, classification_loss,
                                vit_base_16, vit_sharding_rules,
                                vit_tiny)

__all__ = [
    "T5", "T5Config", "t5_small", "t5_tiny", "t5_sharding_rules",
    "t5_greedy_decode", "seq2seq_loss",
    "Bert", "BertConfig", "bert_base", "bert_tiny",
    "bert_sharding_rules", "mask_tokens", "mlm_loss",
    "GPT2", "GPT2Config", "gpt2_sharding_rules", "gpt2_124m",
    "ResNet", "ResNetConfig", "resnet50", "resnet18",
    "ViT", "ViTConfig", "vit_base_16", "vit_tiny",
    "vit_sharding_rules", "classification_loss",
    "Llama", "LlamaConfig", "llama2_7b", "llama_tiny",
    "llama_sharding_rules", "generate",
    "Mixtral", "MixtralConfig", "mixtral_8x7b", "mixtral_tiny",
    "mixtral_sharding_rules", "moe_aux_loss", "olmoe_1b_7b",
    "olmoe_tiny",
    "GraniteHybrid", "GraniteHybridConfig", "granite_hybrid_tiny",
]
