"""Solar Open 2 family (``solar_open2``): the published ``config.json`` keys -> the program's model.

A cut in depth is ``num_hidden_layers`` (listed in ``reduced``): the first
``num_hidden_layers`` layers of the published pattern are run, ``gqa_layers``
(kept whole, as published) saying which of them are softmax GQA layers. A
share of the experts names the experts it HOLDS as ``n_routed_experts``, the
published count as ``n_routed_experts_published`` and the first one held as
``first_held_expert``; a vocabulary slice names its rows as ``vocab_size``.
Keys of the file's own beside the published ones say what the config has no
key for (``kda_low_rank``, the width of the decay's and the output gate's
low-rank pairs) and what the random weights START the learned pieces at, which
a published checkpoint carries trained: ``dt_range`` (``softplus(dt_bias)`` a
channel, log-uniform), ``qk_init_gain`` (the GQA layers' q and k projections)
and ``router_zero_sum_group`` (the router's rows of every device's experts
sum to zero: the held share's load is then even under every seed)."""

from __future__ import annotations

reference = "solar_open2"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.solar_open2 import SolarOpen2Config, SolarOpen2ForCausalLM
    from perfbench.references.glm_moe_dsa import held_experts

    if sequence_parallel:
        raise ValueError("the Solar Open 2 model has no sequence-parallel form")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("the head is untied")
    if cfg.get("use_rope", False) or not cfg.get("use_gqa_gate", True):
        raise ValueError("the GQA layers carry no rotary and a sigmoid gate: no other form is modelled")
    if cfg.get("kda_use_full_proj", False) or not cfg.get("kda_allow_neg_eigval", True):
        raise ValueError("the decay is low-rank and beta runs to 2: no other form is modelled")
    if int(cfg.get("first_k_dense_replace", 0)) != 0:
        raise ValueError("every layer is a mixture of experts (first_k_dense_replace 0)")
    lin = cfg["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("a linear layer's keys and values have its query heads' count")
    layers = int(cfg["num_hidden_layers"])
    n_out, first, held = held_experts(cfg)
    config = SolarOpen2Config(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_layers=layers,
        gqa_layers=tuple(int(i) for i in cfg["gqa_layers"] if int(i) < layers),
        gqa_interval=int(cfg["gqa_interval"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        linear_num_heads=int(lin["num_heads"]),
        linear_head_dim=int(lin["head_dim"]),
        conv_kernel=int(lin["short_conv_kernel_size"]),
        low_rank=int(cfg.get("kda_low_rank", 128)),
        num_experts=n_out,
        top_k=int(cfg["num_experts_per_tok"]),
        num_shared_experts=int(cfg["n_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        held_experts=None if held == n_out else (first, held),
        max_seq_len=int(max_seq_len),
        rms_eps=float(cfg["rms_norm_eps"]),
        dt_range=tuple(float(v) for v in cfg.get("dt_range", (1e-3, 1e-1))),
        qk_init_gain=float(cfg.get("qk_init_gain", 1.0)),
        router_zero_sum_group=int(cfg.get("router_zero_sum_group", 0)),
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        remat=remat,
    )
    return SolarOpen2ForCausalLM(config, attention_impl="auto")


def geometry(cfg: dict) -> dict:
    layers = int(cfg["num_hidden_layers"])
    full = sum(1 for i in cfg["gqa_layers"] if int(i) < layers)
    lin = cfg["linear_attn_config"]
    return {
        "num_layers": layers,
        "expert_layers": layers,
        "hidden": int(cfg["hidden_size"]),
        "num_q_heads": int(cfg["num_attention_heads"]),
        "num_kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        # the GQA layers are full-attention layers on a joined K/V leaf: no window layer
        "full_layers": full,
        "window_layers": 0,
        "window": None,
        "recurrent_layers": layers - full,
        "kda_heads": int(lin["num_heads"]),
        "kda_head_dim": int(lin["head_dim"]),
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["hidden_size"])
