"""CodeGen family: the published ``config.json`` keys -> the program's model.

A family file is the only place that knows the program's class for a family
and how its config keys are spelled there. ``reference`` names the plain
reference under ``perfbench/references/``.
"""

from __future__ import annotations

reference = "codegen"


def build(cfg: dict, *, runner: str, max_seq_len: int, sequence_parallel: bool = False,
          remat: bool = False):
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.codegen import CodeGenConfig, CodeGenForCausalLM

    n_inner = cfg.get("n_inner") or 4 * int(cfg["n_embd"])
    config = CodeGenConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["n_embd"]),
        intermediate_size=int(n_inner),
        num_layers=int(cfg["n_layer"]),
        num_heads=int(cfg["n_head"]),
        max_seq_len=int(max_seq_len),
        rotary_dim=int(cfg["rotary_dim"]),
        layer_norm_eps=float(cfg["layer_norm_epsilon"]),
        dtype=jnp.bfloat16,
        # served in bf16; trained from fp32 master weights
        param_dtype=jnp.bfloat16 if runner == "serve" else jnp.float32,
        sequence_parallel=sequence_parallel,
        remat=remat,
    )
    return CodeGenForCausalLM(config)


def geometry(cfg: dict) -> dict:
    """Head geometry for the roofline functions."""
    heads = int(cfg["n_head"])
    return {
        "num_layers": int(cfg["n_layer"]),
        "hidden": int(cfg["n_embd"]),
        "num_q_heads": heads,
        "num_kv_heads": heads,
        "head_dim": int(cfg["n_embd"]) // heads,
        "vocab_size": int(cfg["vocab_size"]),
    }


def embed_table_params(cfg: dict) -> int:
    return int(cfg["vocab_size"]) * int(cfg["n_embd"])
