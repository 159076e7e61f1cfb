"""DeepSeek-V2 family: the published ``config.json`` keys -> the program's model."""

from __future__ import annotations

reference = "deepseek_v2"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.deepseek_v2 import (
        DeepseekV2Config,
        DeepseekV2ForCausalLM,
        YarnScaling,
    )

    if cfg.get("q_lora_rank") is not None:
        raise ValueError("query compression (q_lora_rank) is not modelled: V2-Lite has none")
    if sequence_parallel:
        raise ValueError("the DeepSeek-V2 model has no sequence-parallel form")
    rs = cfg.get("rope_scaling")
    config = DeepseekV2Config(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        first_k_dense=int(cfg["first_k_dense_replace"]),
        num_heads=int(cfg["num_attention_heads"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        num_experts=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=None if rs is None else YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        ),
        rms_eps=float(cfg["rms_norm_eps"]),
        # dropless grouped matmuls (``ragged_dot``) at every batch size: with
        # 8 slots "auto" would gather 48 experts' weights a step (selective)
        expert_strategy="blockwise",
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return DeepseekV2ForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    return {
        "num_layers": int(cfg["num_hidden_layers"]),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        # the cache has ONE row a token for all heads (latent + rotated key)
        "num_kv_heads": 1,
        "head_dim": int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        "v_head_dim": int(cfg["v_head_dim"]),
        "latent_dim": int(cfg["kv_lora_rank"]),
        "rope_dim": int(cfg["qk_rope_head_dim"]),
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
