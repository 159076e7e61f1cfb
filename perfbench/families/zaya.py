"""ZAYA1 family (``zaya``): the published ``config.json`` keys -> the program's model.

A cut in depth is ``num_hidden_layers`` (listed in ``reduced``; every layer is
the same block, so the first ``num_hidden_layers`` are run). Keys of the
file's own beside the published ones say what the random weights START the
learned pieces at, which a published checkpoint carries trained:
``temperature_init`` (the keys' temperature ``tau``), ``router_bias_std`` (the
normal whose quantiles the selection bias is drawn from:
``routing.stratified_normal``), ``moe_branch_scale_init`` (the learned scale a
channel on the expert sublayer's branch at its residual merge),
``embed_init_std`` (the tied table)."""

from __future__ import annotations

reference = "zaya"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    from perfbench.references.zaya import rope_theta

    if sequence_parallel:
        raise ValueError("the ZAYA1 model has no sequence-parallel form")
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("the head is the embedding table (tied)")
    if cfg.get("sliding_window") is not None or set(cfg["layer_types"]) != {"hybrid"}:
        raise ValueError("every layer is a full-attention 'hybrid' block: no window is modelled")
    if cfg.get("attention_bias") or cfg.get("lm_head_bias"):
        raise ValueError("no bias on the attention projections or the head")
    if cfg["hidden_act"] != "silu":
        raise ValueError("the experts are SwiGLU")
    config = ZayaConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        cca_time0=int(cfg["cca_time0"]),
        cca_time1=int(cfg["cca_time1"]),
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        num_experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        router_hidden_size=int(cfg["router_hidden_size"]),
        max_seq_len=int(max_seq_len),
        rope_theta=rope_theta(cfg),
        rms_eps=float(cfg["rms_norm_eps"]),
        embed_init_std=float(cfg.get("embed_init_std", 0.02)),
        temperature_init=float(cfg.get("temperature_init", 1.0)),
        router_bias_init_std=float(cfg.get("router_bias_std", 0.0)),
        moe_branch_scale_init=float(cfg.get("moe_branch_scale_init", 1.0)),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return ZayaForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    heads, kv_heads, d = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    return {
        "num_layers": int(cfg["num_hidden_layers"]),
        "expert_layers": int(cfg["num_hidden_layers"]),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": heads,
        "num_kv_heads": kv_heads,
        "head_dim": d,
        # the channels the two convolutions run over: every query and kv head
        "cca_conv_channels": (heads + kv_heads) * d,
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
