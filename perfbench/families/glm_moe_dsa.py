"""GLM-5 family (``glm_moe_dsa``): the published ``config.json`` keys -> the program's model.

A share of an expert-parallel deployment is named by keys of the file's own
beside the published ones: ``n_routed_experts`` is how many experts are HELD
(listed in ``reduced``), ``n_routed_experts_published`` the router's outputs,
``first_held_expert`` the first one held; ``dense_layers_run`` how many of the
published ``first_k_dense_replace`` leading dense layers the depth cut keeps
(they count once); ``e_score_correction_bias_std`` the normal whose quantiles
the selection bias is drawn from with the other random weights (the same values
in every share of held experts under every seed: ``routing.stratified_normal``)."""

from __future__ import annotations

reference = "glm_moe_dsa"


def _dense_layers(cfg: dict) -> int:
    return int(cfg.get("dense_layers_run", cfg["first_k_dense_replace"]))


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.glm_moe_dsa import GlmMoeDsaConfig, GlmMoeDsaForCausalLM
    from perfbench.references.glm_moe_dsa import held_experts

    if sequence_parallel:
        raise ValueError("the GLM-5 model has no sequence-parallel form")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1:
        raise ValueError("group-limited routing is not modelled: GLM-5 has one group")
    if cfg["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("a scaled rotary (YaRN) is not modelled here: GLM-5 has plain rope")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc":
        raise ValueError("the router is sigmoid scoring under a selection bias (noaux_tc)")
    if int(cfg.get("moe_layer_freq", 1)) != 1:
        raise ValueError("dense layers between the sparse ones are not modelled: the config has none")
    if int(cfg["qk_head_dim"]) != int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    published, first, held = held_experts(cfg)
    config = GlmMoeDsaConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        first_k_dense=_dense_layers(cfg),
        num_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        num_experts=published,
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        held_experts=None if held == published else (first, held),
        router_bias_init_std=float(cfg.get("e_score_correction_bias_std", 0.0)),
        index_n_heads=int(cfg["index_n_heads"]),
        index_head_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        max_seq_len=int(max_seq_len),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        # all experts held: dropless grouped matmuls at every batch size (as
        # the other MoE families); a share takes the held path whatever this says
        expert_strategy="blockwise",
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return GlmMoeDsaForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    return {
        "num_layers": int(cfg["num_hidden_layers"]),
        "expert_layers": int(cfg["num_hidden_layers"]) - _dense_layers(cfg),
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        # the cache has ONE row a token for all heads (latent + rotated key)
        "num_kv_heads": 1,
        "head_dim": int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        "v_head_dim": int(cfg["v_head_dim"]),
        "latent_dim": int(cfg["kv_lora_rank"]),
        "rope_dim": int(cfg["qk_rope_head_dim"]),
        "index_heads": int(cfg["index_n_heads"]),
        "index_dim": int(cfg["index_head_dim"]),
        "index_topk": int(cfg["index_topk"]),
        # the slice this chip holds: the tape draws its ids from it
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
