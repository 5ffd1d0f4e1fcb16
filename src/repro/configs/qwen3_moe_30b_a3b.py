"""qwen3-moe-30b-a3b [moe] — 48L d=2048 32H (kv=4) d_ff=768 vocab=151936, 128e top-8.

[hf:Qwen/Qwen3-30B-A3B; hf]. This is the model the Gimbal paper itself serves
(Qwen3-30B-A3B on 4xH100): the reference architecture for all paper-claim
benchmarks. d_ff=768 is the per-expert FFN dim; every layer is MoE. qk_norm is
a Qwen3-family trait and is kept.
"""
from repro.configs.base import ModelConfig, MoEConfig, reduced


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-moe-30b-a3b",
        family="moe",
        n_layers=48,
        d_model=2048,
        n_heads=32,
        n_kv_heads=4,
        d_ff=768,                      # also the expert dim (all layers MoE)
        vocab_size=151936,
        head_dim=128,                  # Qwen3 uses explicit head_dim=128
        moe=MoEConfig(n_experts=128, top_k=8, d_expert=768, moe_every=1),
        qk_norm=True,
        rope_theta=1000000.0,
        supports_long_context=False,
    )


def one_chip_config() -> ModelConfig:
    """Published widths, 4 of the 48 layers, for one 16 GB TPU v5e chip.

    Every width is Qwen3-30B-A3B's own (d_model 2048, 32 query / 4 KV heads
    of 128, 128 experts of d_expert 768, top-8, vocab 151,936, qk_norm,
    rope theta 1e6, bf16); weights are random from a seed. Only depth is
    cut: 4 layers are 6.2 GB of weights (each layer's 128 experts are
    1.2 GB, the embedding and the untied head 0.6 GB each). An expert
    migration (``models/transformer.migrate_params_for_placement``)
    briefly holds a second copy of the stacked expert weights, about
    4.8 GB, and each paged step writes a fresh copy of the page pool, so
    4 layers plus a pool of ~0.5 GB still fit 16 GB; 6 layers would not
    survive a migration.
    """
    return reduced(config(), n_layers=4)


def smoke_config() -> ModelConfig:
    return reduced(
        config(),
        n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=48, vocab_size=256,
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=48, moe_every=1),
    )
