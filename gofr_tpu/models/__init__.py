"""Model families (functional JAX modules — see gofr_tpu.models.base).

The reference has no model layer (SURVEY.md §2.9); this package is the new
capability the TPU build adds: decoder LMs for /generate, encoders for
embedding and classification endpoints, all shardable via logical axes.
"""

from gofr_tpu.models import bert, gpt2, llama, mixtral, vit
from gofr_tpu.models.base import (
    ModelSpec,
    cast_floats,
    family_of,
    get_family,
    param_bytes,
    param_count,
    register_family,
)
from gofr_tpu.models.gpt2 import GPT2Config
from gofr_tpu.models.llama import LlamaConfig
from gofr_tpu.models.mixtral import MixtralConfig
from gofr_tpu.models.bert import BertConfig
from gofr_tpu.models.vit import ViTConfig

register_family("gpt2", gpt2)
register_family("llama", llama)
register_family("mixtral", mixtral)
register_family("bert", bert)
register_family("vit", vit)
register_family("cohere2_moe", "gofr_tpu.models.cohere2_moe")  # imported when first asked for

__all__ = [
    "ModelSpec",
    "LlamaConfig",
    "MixtralConfig",
    "BertConfig",
    "ViTConfig",
    "gpt2",
    "GPT2Config",
    "llama",
    "mixtral",
    "bert",
    "vit",
    "cast_floats",
    "family_of",
    "get_family",
    "param_bytes",
    "param_count",
    "register_family",
]
