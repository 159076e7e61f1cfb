"""Trinity family (``afmoe``): the published ``config.json`` keys -> the program's model.

A cut is named by keys of the file's own beside the published ones.
``layers_run``: the published layers that are run, in order (their kinds are
read from the published ``layer_types``, kept whole), ``num_hidden_layers``
their count (listed in ``reduced``), ``dense_layers_run`` how many of them are
the leading dense layers. A share of an expert-parallel deployment:
``num_experts`` is how many experts are HELD (in ``reduced``),
``num_experts_published`` the router's outputs, ``first_held_expert`` the
first one held; ``router_bias_std`` the normal whose quantiles the selection
bias is drawn from with the other random weights (the same values in every
share of held experts under every seed: ``routing.stratified_normal``)."""

from __future__ import annotations

reference = "afmoe"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    from perfbench.references.afmoe import held_experts, layers_run

    if sequence_parallel:
        raise ValueError("the Trinity model has no sequence-parallel form")
    if any(int(cfg[k]) != 1 for k in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")):
        raise ValueError("group-limited routing is not modelled: Trinity has one group")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("a scaled rotary is not modelled here: Trinity has plain rope")
    if cfg["score_func"] != "sigmoid":
        raise ValueError("the router is sigmoid scoring under a selection bias")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("the head is untied")
    kinds, dense = layers_run(cfg)
    published, first, held = held_experts(cfg)
    config = AfmoeConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_dense_layers=dense,
        layer_types=tuple(kinds),
        global_attn_every_n_layers=int(cfg["global_attn_every_n_layers"]),
        sliding_window=int(cfg["sliding_window"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        num_experts=published,
        top_k=int(cfg["num_experts_per_tok"]),
        num_shared_experts=int(cfg["num_shared_experts"]),
        route_scale=float(cfg["route_scale"]),
        route_norm=bool(cfg["route_norm"]),
        mup_enabled=bool(cfg["mup_enabled"]),
        held_experts=None if held == published else (first, held),
        router_bias_init_std=float(cfg.get("router_bias_std", 0.0)),
        qk_norm_init=float(cfg.get("qk_norm_gain_init", 1.0)),
        post_attn_norm_init=float(cfg.get("post_attention_norm_gain_init", 1.0)),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        router_aux_loss_coef=float(cfg.get("load_balance_coeff", 0.0)),
        # all experts held: dropless grouped matmuls at every batch size (as
        # the other MoE families); a share takes the held path whatever this says
        expert_strategy="blockwise",
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return AfmoeForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    from perfbench.references.afmoe import SLIDING, layers_run

    kinds, dense = layers_run(cfg)
    return {
        "num_layers": len(kinds),
        "expert_layers": len(kinds) - dense,
        "window_layers": sum(k == SLIDING for k in kinds),
        "full_layers": sum(k != SLIDING for k in kinds),
        "window": int(cfg["sliding_window"]),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        "num_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        # the slice this chip holds: the tape draws its ids from it
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
