"""Dense Qwen3 (hf:Qwen/Qwen3-8B): the Qwen3-MoE module's hooks, which
build the dense block where a configuration has no experts."""
from arch.qwen3_moe import (attention_flops, leaves,  # noqa: F401
                            paged_decode_cost, program_config,
                            served_logits, token_flops)
