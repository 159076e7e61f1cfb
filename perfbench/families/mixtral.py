"""Mixtral family: the published ``config.json`` keys -> the program's model."""

from __future__ import annotations

reference = "mixtral"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

    config = MixtralConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        num_experts=int(cfg["num_local_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        capacity_factor=None,                      # dropless, as published
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        sequence_parallel=sequence_parallel,
        remat=remat,
        # the engine's fused paged path pairs pool leaves with layers by
        # name, which needs unrolled layers
        scan_layers=False,
    )
    return MixtralForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    heads = int(cfg["num_attention_heads"])
    return {
        "num_layers": int(cfg["num_hidden_layers"]),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": heads,
        "num_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["hidden_size"]) // heads,
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
