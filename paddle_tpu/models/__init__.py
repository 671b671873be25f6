"""Model zoo.

Reference scope: the reference frameworks' flagship model families live in
PaddleNLP/PaddleClas etc., but the in-repo anchor is the auto-parallel Llama
decoder used by its hybrid-strategy tests
(test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py).
Here the zoo is first-class: Llama is the flagship for benchmarks.
"""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, LlamaPretrainingCriterion,
    PagedKVManager, build_paged_generate, build_quant_generate,
    hash_prefix_blocks, init_quant_serving_params, llama_sharding_rules,
    quantize_kv_pages, resolve_kv_cache_dtype,
    serving_block_size_candidates, shard_llama,
)
from .checkpoint import load_quant_serving_params  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel, shard_gpt  # noqa: F401
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    ErnieConfig, ErnieForMaskedLM, ErnieForSequenceClassification,
    ErnieModel,
)
from .mellum import MellumConfig  # noqa: F401
from .hy_v4 import HyV4Config  # noqa: F401
from .falcon_h1 import FalconH1Config  # noqa: F401
from .glm4_moe_lite import (  # noqa: F401
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
    Glm4MoeLitePretrainingCriterion,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig, AfmoeForCausalLM, AfmoePretrainingCriterion,
)
