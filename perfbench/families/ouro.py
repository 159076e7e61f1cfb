"""Ouro family (``ouro``): the published ``config.json`` keys -> the program's model.

A cut in depth is ``num_hidden_layers`` (listed in ``reduced``) with
``layer_types`` cut to as many entries and ``num_hidden_layers_published``
beside them; ``total_ut_steps`` is never cut: the stack that is held runs
every pass. The cache holds one node a layer a PASS, and ``geometry`` counts
those as ``full_layers``: what a decode step attends."""

from __future__ import annotations

reference = "ouro"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.ouro import OuroConfig, OuroForCausalLM

    if sequence_parallel or remat:
        raise ValueError("the Ouro model has no sequence-parallel and no rematerialised form: it is served")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("a scaled rotary is not modelled here: Ouro has plain rope")
    if cfg.get("use_sliding_window", False) or cfg.get("sliding_window") is not None:
        raise ValueError("a sliding window is not modelled here: every Ouro layer is full attention")
    if any(kind != "full_attention" for kind in cfg["layer_types"]) or \
            len(cfg["layer_types"]) != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types must name num_hidden_layers full_attention layers")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("the head is untied")
    if cfg["hidden_act"] != "silu":
        raise ValueError("the MLP is SwiGLU (hidden_act silu)")
    config = OuroConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        total_ut_steps=int(cfg["total_ut_steps"]),
        early_exit_threshold=float(cfg["early_exit_threshold"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
    )
    return OuroForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    layers, passes = int(cfg["num_hidden_layers"]), int(cfg["total_ut_steps"])
    return {
        "num_layers": layers,
        "passes": passes,
        # the cache NODES a step attends, one a layer a pass, all of them full
        # attention (swa_costs.layers_cost sums both kinds)
        "full_layers": layers * passes,
        "window_layers": 0,
        "window": None,
        "hidden": int(cfg["hidden_size"]),
        "intermediate": int(cfg["intermediate_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        "num_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
