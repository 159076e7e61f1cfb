"""Keye-VL-2.0 family (language model): the published ``config.json`` keys -> the program's model."""

from __future__ import annotations

reference = "keye_vl2"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM

    if sequence_parallel:
        raise ValueError("the Keye-VL-2.0 model has no sequence-parallel form")
    if int(cfg["decoder_sparse_step"]) != 1 or cfg["mlp_only_layers"]:
        raise ValueError("dense layers between the sparse ones are not modelled: the config has none")
    if cfg.get("use_sliding_window"):
        raise ValueError("sliding-window layers are not modelled: the config has none")
    sa = cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexed cache holds ONE index key a token")
    config = KeyeVL2Config(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        num_experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(int(n) for n in cfg["rope_scaling"]["mrope_section"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        indexer_num_heads=int(sa["indexer_num_heads"]),
        indexer_head_dim=int(sa["indexer_head_dim"]),
        index_topk=int(sa["topk"]),
        # dropless grouped matmuls (``ragged_dot``) at every batch size: with
        # 8 slots "auto" would gather 64 experts' weights a step (selective)
        expert_strategy="blockwise",
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return KeyeVL2ForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return {
        "num_layers": int(cfg["num_hidden_layers"]),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        "num_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "index_topk": int(sa["topk"]),
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
