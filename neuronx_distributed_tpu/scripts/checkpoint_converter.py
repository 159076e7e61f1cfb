"""HF ↔ native Llama checkpoint conversion.

Reference analogue: ``scripts/checkpoint_converter.py`` (``CheckpointConverterBase``,
fused/split-QKV transforms :21-252, merge/split entry points :269,:445). The
reference converts between a HF state dict and per-rank TP/PP/EP-sharded
NxD checkpoints; here a "native" checkpoint is a *global* (unsharded-logical)
flax param tree — sharding is a property of how it is loaded (``NamedSharding``
targets in ``trainer.checkpoint.load_checkpoint``), so the per-TP-degree
split/merge machinery of the reference is unnecessary by construction. What
remains is pure name/layout mapping:

* HF linear weights are ``(out, in)``; native kernels are ``(in, out)`` — transpose.
* HF stores rotary q/k in the half-split layout (same convention as
  ``models/llama.apply_rope``), so no permutation is needed.
* ``scan_layers=True`` models hold one stacked subtree ``model/layers/layer/...``
  with a leading layer axis; conversion stacks/unstacks it.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, Mapping

import numpy as np

_LAYER_MAP = {
    # HF suffix (under model.layers.{i}.) → native path (under layers_{i}/), transpose?
    "self_attn.q_proj.weight": ("attn/qkv/q_proj/kernel", True),
    "self_attn.k_proj.weight": ("attn/qkv/k_proj/kernel", True),
    "self_attn.v_proj.weight": ("attn/qkv/v_proj/kernel", True),
    "self_attn.o_proj.weight": ("attn/o_proj/kernel", True),
    "mlp.gate_proj.weight": ("mlp/gate_proj/kernel", True),
    "mlp.up_proj.weight": ("mlp/up_proj/kernel", True),
    "mlp.down_proj.weight": ("mlp/down_proj/kernel", True),
    "input_layernorm.weight": ("input_norm/weight", False),
    "post_attention_layernorm.weight": ("post_attn_norm/weight", False),
}

_TOP_MAP = {
    "model.embed_tokens.weight": ("model/embed/embedding", False),
    "model.norm.weight": ("model/final_norm/weight", False),
    "lm_head.weight": ("lm_head/kernel", True),
}


def _set(tree: Dict[str, Any], path: str, value: np.ndarray) -> None:
    parts = path.split("/")
    node = tree
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def _get(tree: Mapping[str, Any], path: str) -> np.ndarray:
    node: Any = tree
    for p in path.split("/"):
        node = node[p]
    return node


def hf_to_native(
    hf_state: Mapping[str, np.ndarray], scan_layers: bool = False
) -> Dict[str, Any]:
    """Map a HF Llama state dict to the native param tree ``{"params": ...}``."""
    params: Dict[str, Any] = {}
    num_layers = 0
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _TOP_MAP:
            path, transpose = _TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            idx_str, suffix = rest.split(".", 1)
            idx = int(idx_str)
            num_layers = max(num_layers, idx + 1)
            if suffix not in _LAYER_MAP:
                raise KeyError(f"unmapped HF layer tensor: {name}")
            path, transpose = _LAYER_MAP[suffix]
            _set(
                params,
                f"model/layers_{idx}/{path}",
                tensor.T if transpose else tensor,
            )
            continue
        if name == "model.rotary_emb.inv_freq" or name.endswith("rotary_emb.inv_freq"):
            continue  # recomputed from config
        raise KeyError(f"unmapped HF tensor: {name}")

    # Tied-embedding models (e.g. some Llama-3.2 exports) omit lm_head.
    if "lm_head" not in params:
        _set(params, "lm_head/kernel", _get(params, "model/embed/embedding").T)

    if scan_layers:
        params["model"] = _stack_layers(params["model"], num_layers)
    return {"params": params}


def native_to_hf(
    params: Mapping[str, Any], tie_word_embeddings: bool = False
) -> Dict[str, np.ndarray]:
    """Inverse of :func:`hf_to_native`. Accepts scan or unstacked layouts.
    ``tie_word_embeddings=True`` omits ``lm_head.weight`` (HF tied exports
    carry no separate head; the native side synthesized it on import)."""
    tree = dict(params.get("params", params))
    model = dict(tree["model"])
    if "layers" in model:
        model = _unstack_layers(model)
    tree = dict(tree)
    tree["model"] = model

    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _TOP_MAP.items():
        if tie_word_embeddings and hf_name == "lm_head.weight":
            continue
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    idx = 0
    while f"layers_{idx}" in model:
        for hf_suffix, (path, transpose) in _LAYER_MAP.items():
            t = np.asarray(_get(model, f"layers_{idx}/{path}"))
            out[f"model.layers.{idx}.{hf_suffix}"] = t.T if transpose else t
        idx += 1
    return out


def _stack_layers(model: Dict[str, Any], num_layers: int) -> Dict[str, Any]:
    """layers_{i}/... → layers/layer/... with leading layer axis (the
    ``nn.scan`` parameter layout)."""
    import jax

    per_layer = [model.pop(f"layers_{i}") for i in range(num_layers)]
    stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *per_layer)
    model["layers"] = {"layer": stacked}
    return model


def _unstack_layers(model: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    stacked = model.pop("layers")["layer"]
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(num_layers):
        model[f"layers_{i}"] = jax.tree.map(lambda x: np.asarray(x[i]), stacked)
    return model


# --- Mixtral family (reference checkpoint_converter.py multi-family support;
# experts stack across HF per-expert tensors into the 3D (E, in, out) native
# layout) ----------------------------------------------------------------------

_MIXTRAL_ATTN_MAP = {
    "self_attn.q_proj.weight": ("attn/qkv/q_proj/kernel", True),
    "self_attn.k_proj.weight": ("attn/qkv/k_proj/kernel", True),
    "self_attn.v_proj.weight": ("attn/qkv/v_proj/kernel", True),
    "self_attn.o_proj.weight": ("attn/o_proj/kernel", True),
    "input_layernorm.weight": ("input_norm/weight", False),
    "post_attention_layernorm.weight": ("post_attn_norm/weight", False),
    "block_sparse_moe.gate.weight": ("moe/router/weight", True),
}
# HF per-expert names → native 3D stacks (w1=gate, w3=up, w2=down)
_MIXTRAL_EXPERT_MAP = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}


def hf_to_native_mixtral(
    hf_state: Mapping[str, np.ndarray], scan_layers: bool = False
) -> Dict[str, Any]:
    params: Dict[str, Any] = {}
    experts: Dict[tuple, Dict[int, np.ndarray]] = {}
    num_layers = 0
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _TOP_MAP:
            path, transpose = _TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            idx_str, suffix = rest.split(".", 1)
            idx = int(idx_str)
            num_layers = max(num_layers, idx + 1)
            if suffix in _MIXTRAL_ATTN_MAP:
                path, transpose = _MIXTRAL_ATTN_MAP[suffix]
                _set(params, f"model/layers_{idx}/{path}",
                     tensor.T if transpose else tensor)
                continue
            if suffix.startswith("block_sparse_moe.experts."):
                erest = suffix[len("block_sparse_moe.experts.") :]
                e_str, wname = erest.split(".", 1)
                wname = wname.removesuffix(".weight")
                if wname not in _MIXTRAL_EXPERT_MAP:
                    raise KeyError(f"unmapped Mixtral expert tensor: {name}")
                # HF expert linears are (out, in); native 3D is (E, in, out)
                experts.setdefault((idx, _MIXTRAL_EXPERT_MAP[wname]), {})[
                    int(e_str)
                ] = tensor.T
                continue
            raise KeyError(f"unmapped HF layer tensor: {name}")
        if name.endswith("rotary_emb.inv_freq"):
            continue
        raise KeyError(f"unmapped HF tensor: {name}")
    for (idx, native_name), by_e in experts.items():
        stacked = np.stack([by_e[e] for e in range(len(by_e))], axis=0)
        _set(params, f"model/layers_{idx}/moe/experts/{native_name}", stacked)
    if "lm_head" not in params:
        _set(params, "lm_head/kernel", _get(params, "model/embed/embedding").T)
    if scan_layers:
        params["model"] = _stack_layers(params["model"], num_layers)
    return {"params": params}


def native_to_hf_mixtral(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    model = dict(tree["model"])
    if "layers" in model:
        model = _unstack_layers(model)
    tree = dict(tree)
    tree["model"] = model
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    idx = 0
    while f"layers_{idx}" in model:
        layer = model[f"layers_{idx}"]
        for hf_suffix, (path, transpose) in _MIXTRAL_ATTN_MAP.items():
            t = np.asarray(_get(layer, path))
            out[f"model.layers.{idx}.{hf_suffix}"] = t.T if transpose else t
        for wname, native_name in _MIXTRAL_EXPERT_MAP.items():
            stacked = np.asarray(_get(layer, f"moe/experts/{native_name}"))
            for e in range(stacked.shape[0]):
                out[
                    f"model.layers.{idx}.block_sparse_moe.experts.{e}.{wname}.weight"
                ] = stacked[e].T
        idx += 1
    return out


# --- GPT-NeoX family: fused query_key_value with PER-HEAD interleaving — the
# reference's fused/split-QKV transform with the kv-multiplier inverse
# (checkpoint_converter.py:21-252); NeoX's multiplier is 1 but the per-head
# [q_i; k_i; v_i] interleave is the same split/fuse machinery ------------------

_NEOX_TOP_MAP = {
    "gpt_neox.embed_in.weight": ("embed/embedding", False),
    "gpt_neox.final_layer_norm.weight": ("final_norm/ln/scale", False),
    "gpt_neox.final_layer_norm.bias": ("final_norm/ln/bias", False),
    "embed_out.weight": ("lm_head/kernel", True),
}

_NEOX_LAYER_MAP = {
    "attention.dense.weight": ("attn/o_proj/kernel", True),
    "attention.dense.bias": ("attn/o_proj/bias", False),
    "mlp.dense_h_to_4h.weight": ("mlp/up/kernel", True),
    "mlp.dense_h_to_4h.bias": ("mlp/up/bias", False),
    "mlp.dense_4h_to_h.weight": ("mlp/down/kernel", True),
    "mlp.dense_4h_to_h.bias": ("mlp/down/bias", False),
    "input_layernorm.weight": ("input_norm/ln/scale", False),
    "input_layernorm.bias": ("input_norm/ln/bias", False),
    "post_attention_layernorm.weight": ("post_attn_norm/ln/scale", False),
    "post_attention_layernorm.bias": ("post_attn_norm/ln/bias", False),
}

_NEOX_SKIP = (
    "attention.bias",
    "attention.masked_bias",
    "attention.rotary_emb.inv_freq",
)


def _split_neox_qkv(fused_w: np.ndarray, fused_b: np.ndarray, num_heads: int):
    """HF NeoX fuses per head: rows are [q_0 k_0 v_0 q_1 k_1 v_1 ...]."""
    hidden = fused_w.shape[1]
    d = fused_w.shape[0] // (3 * num_heads)
    w = fused_w.reshape(num_heads, 3, d, hidden)
    b = fused_b.reshape(num_heads, 3, d)
    out = {}
    for j, proj in enumerate(("q_proj", "k_proj", "v_proj")):
        out[f"{proj}/kernel"] = w[:, j].reshape(num_heads * d, hidden).T
        out[f"{proj}/bias"] = b[:, j].reshape(num_heads * d)
    return out


def _fuse_neox_qkv(layer: Mapping[str, Any], num_heads: int):
    ws, bs = [], []
    for proj in ("q_proj", "k_proj", "v_proj"):
        ws.append(np.asarray(_get(layer, f"attn/qkv/{proj}/kernel")).T)
        bs.append(np.asarray(_get(layer, f"attn/qkv/{proj}/bias")))
    hidden = ws[0].shape[1]
    d = ws[0].shape[0] // num_heads
    w = np.stack([wi.reshape(num_heads, d, hidden) for wi in ws], axis=1)
    b = np.stack([bi.reshape(num_heads, d) for bi in bs], axis=1)
    return w.reshape(3 * num_heads * d, hidden), b.reshape(3 * num_heads * d)


def hf_to_native_gpt_neox(
    hf_state: Mapping[str, np.ndarray], num_heads: int, scan_layers: bool = False
) -> Dict[str, Any]:
    if scan_layers:
        raise ValueError("native GPT-NeoX uses the unrolled layer layout")
    params: Dict[str, Any] = {}
    fused: Dict[int, Dict[str, np.ndarray]] = {}
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _NEOX_TOP_MAP:
            path, transpose = _NEOX_TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("gpt_neox.layers."):
            rest = name[len("gpt_neox.layers.") :]
            idx_str, suffix = rest.split(".", 1)
            idx = int(idx_str)
            if suffix in _NEOX_SKIP:
                continue
            if suffix in ("attention.query_key_value.weight",
                          "attention.query_key_value.bias"):
                fused.setdefault(idx, {})[suffix.rsplit(".", 1)[-1]] = tensor
                continue
            if suffix in _NEOX_LAYER_MAP:
                path, transpose = _NEOX_LAYER_MAP[suffix]
                _set(params, f"layers_{idx}/{path}",
                     tensor.T if transpose else tensor)
                continue
            raise KeyError(f"unmapped HF layer tensor: {name}")
        raise KeyError(f"unmapped HF tensor: {name}")
    for idx, wb in fused.items():
        split = _split_neox_qkv(wb["weight"], wb["bias"], num_heads)
        for sub, tensor in split.items():
            _set(params, f"layers_{idx}/attn/qkv/{sub}", tensor)
    return {"params": params}


def native_to_hf_gpt_neox(
    params: Mapping[str, Any], num_heads: int
) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _NEOX_TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    idx = 0
    while f"layers_{idx}" in tree:
        layer = tree[f"layers_{idx}"]
        for hf_suffix, (path, transpose) in _NEOX_LAYER_MAP.items():
            t = np.asarray(_get(layer, path))
            out[f"gpt_neox.layers.{idx}.{hf_suffix}"] = t.T if transpose else t
        w, b = _fuse_neox_qkv(layer, num_heads)
        out[f"gpt_neox.layers.{idx}.attention.query_key_value.weight"] = w
        out[f"gpt_neox.layers.{idx}.attention.query_key_value.bias"] = b
        idx += 1
    return out


# --- DBRX family: fused Wqkv with GQA split widths [H, Hkv·d, Hkv·d] (the
# reference's fused-QKV + kv-multiplier geometry, checkpoint_converter.py:21-252),
# stacked expert tensors w1/v1/w2 (E·ffn, hidden) ↔ native 3D (E, in, out) ------

_DBRX_LAYER_MAP = {
    "norm_attn_norm.attn.out_proj.weight": ("attn/o_proj/kernel", True),
    "norm_attn_norm.norm_1.weight": ("norm_1/ln/scale", False),
    "norm_attn_norm.norm_2.weight": ("norm_2/ln/scale", False),
    "ffn.router.layer.weight": ("moe/router/weight", True),
}

_DBRX_TOP_MAP = {
    "transformer.wte.weight": ("embed/embedding", False),
    "transformer.norm_f.weight": ("final_norm/ln/scale", False),
    "lm_head.weight": ("lm_head/kernel", True),
}


def hf_to_native_dbrx(
    hf_state: Mapping[str, np.ndarray], num_heads: int, num_kv_heads: int
) -> Dict[str, Any]:
    """HF DBRX → native (both sides use bias-free LayerNorms)."""
    params: Dict[str, Any] = {}
    num_layers = 0
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _DBRX_TOP_MAP:
            path, transpose = _DBRX_TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("transformer.blocks."):
            rest = name[len("transformer.blocks.") :]
            idx_str, suffix = rest.split(".", 1)
            idx = int(idx_str)
            num_layers = max(num_layers, idx + 1)
            if suffix in _DBRX_LAYER_MAP:
                path, transpose = _DBRX_LAYER_MAP[suffix]
                _set(params, f"blocks_{idx}/{path}",
                     tensor.T if transpose else tensor)
                continue
            if suffix == "norm_attn_norm.attn.Wqkv.weight":
                h = tensor.shape[1]
                d = h // num_heads
                kvd = num_kv_heads * d
                q, k, v = np.split(tensor, [h, h + kvd], axis=0)
                _set(params, f"blocks_{idx}/attn/qkv/q_proj/kernel", q.T)
                _set(params, f"blocks_{idx}/attn/qkv/k_proj/kernel", k.T)
                _set(params, f"blocks_{idx}/attn/qkv/v_proj/kernel", v.T)
                continue
            if suffix in ("ffn.experts.mlp.w1", "ffn.experts.mlp.v1",
                          "ffn.experts.mlp.w2"):
                # w1/v1 (E·ffn, hidden): per-expert chunk used as x @ chunk.T →
                # native (E, hidden, ffn); w2 used as x1 @ chunk → (E, ffn, hidden)
                h = tensor.shape[1]
                native = {"w1": "gate_proj", "v1": "up_proj", "w2": "down_proj"}[
                    suffix.rsplit(".", 1)[-1]
                ]
                _set(params, f"blocks_{idx}/moe/experts/{native}",
                     tensor)  # reshaped once E is known (below)
                continue
            raise KeyError(f"unmapped HF DBRX tensor: {name}")
        raise KeyError(f"unmapped HF DBRX tensor: {name}")
    # finalize expert reshapes: E = rows / ffn, ffn inferred from router width
    for i in range(num_layers):
        blk = params[f"blocks_{i}"]
        E = blk["moe"]["router"]["weight"].shape[1]
        for nm in ("gate_proj", "up_proj", "down_proj"):
            t = blk["moe"]["experts"][nm]
            ffn = t.shape[0] // E
            t = t.reshape(E, ffn, t.shape[1])
            if nm != "down_proj":
                t = np.transpose(t, (0, 2, 1))
            blk["moe"]["experts"][nm] = t
    return {"params": params}


def native_to_hf_dbrx(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _DBRX_TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    idx = 0
    while f"blocks_{idx}" in tree:
        blk = tree[f"blocks_{idx}"]
        pre = f"transformer.blocks.{idx}"
        for hf_suffix, (path, transpose) in _DBRX_LAYER_MAP.items():
            t = np.asarray(_get(blk, path))
            out[f"{pre}.{hf_suffix}"] = t.T if transpose else t
        q = np.asarray(_get(blk, "attn/qkv/q_proj/kernel")).T
        k = np.asarray(_get(blk, "attn/qkv/k_proj/kernel")).T
        v = np.asarray(_get(blk, "attn/qkv/v_proj/kernel")).T
        out[f"{pre}.norm_attn_norm.attn.Wqkv.weight"] = np.concatenate(
            [q, k, v], axis=0
        )
        for nm, hf_nm in (("gate_proj", "w1"), ("up_proj", "v1"),
                          ("down_proj", "w2")):
            t = np.asarray(_get(blk, f"moe/experts/{nm}"))
            if nm != "down_proj":
                t = np.transpose(t, (0, 2, 1))
            out[f"{pre}.ffn.experts.mlp.{hf_nm}"] = t.reshape(-1, t.shape[2])
        idx += 1
    return out


# --- CodeGen family: the mp_num-blocked fused qkv with [q, v, k] interior
# order AND the GPT-J interleaved rotary → half-split channel permutation
# (the deepest fused-QKV inverse of the set; reference :21-252) ---------------

_CODEGEN_TOP_MAP = {
    "transformer.wte.weight": ("embed/embedding", False),
    "transformer.ln_f.weight": ("final_norm/ln/scale", False),
    "transformer.ln_f.bias": ("final_norm/ln/bias", False),
    "lm_head.weight": ("lm_head/kernel", True),
    "lm_head.bias": ("lm_head/bias", False),
}

_CODEGEN_LAYER_MAP = {
    "attn.out_proj.weight": ("attn/o_proj/kernel", True),
    "mlp.fc_in.weight": ("mlp/up/kernel", True),
    "mlp.fc_in.bias": ("mlp/up/bias", False),
    "mlp.fc_out.weight": ("mlp/down/kernel", True),
    "mlp.fc_out.bias": ("mlp/down/bias", False),
    "ln_1.weight": ("input_norm/ln/scale", False),
    "ln_1.bias": ("input_norm/ln/bias", False),
}

_CODEGEN_SKIP_SUFFIXES = ("attn.causal_mask", "attn.masked_bias", "attn.bias")
_CODEGEN_MP_NUM = 4  # fixed blocking of HF CodeGen's fused qkv_proj


def _rotary_perm(num_heads: int, head_dim: int, rotary_dim: int,
                 inverse: bool = False) -> np.ndarray:
    """Row permutation (on the projection OUTPUT dim, size H·d) mapping each
    head's first ``rotary_dim`` channels from GPT-J interleaved pairs
    (2i, 2i+1) to the half-split layout (i, rot/2+i) our ``apply_rope``
    expects. Non-rotary channels stay put."""
    half = rotary_dim // 2
    per_head = np.arange(head_dim)
    src = per_head.copy()
    # half-split channel j takes interleaved channel: j<half → 2j; else 2(j-half)+1
    src[:half] = 2 * np.arange(half)
    src[half:rotary_dim] = 2 * np.arange(half) + 1
    if inverse:
        inv = np.empty_like(src)
        inv[src] = per_head
        src = inv
    return (np.arange(num_heads)[:, None] * head_dim + src[None]).reshape(-1)


def _split_codegen_qkv(fused_w: np.ndarray, num_heads: int, rotary_dim: int):
    """HF fused qkv_proj (3·hidden, hidden): mp_num row blocks, each
    internally [q, v, k]; heads are ordered across blocks."""
    hidden = fused_w.shape[1]
    mp = _CODEGEN_MP_NUM
    local = hidden // mp
    blocks = fused_w.reshape(mp, 3 * local, hidden)
    q = blocks[:, :local].reshape(hidden, hidden)
    v = blocks[:, local : 2 * local].reshape(hidden, hidden)
    k = blocks[:, 2 * local :].reshape(hidden, hidden)
    perm = _rotary_perm(num_heads, hidden // num_heads, rotary_dim)
    return {"q_proj": q[perm].T, "k_proj": k[perm].T, "v_proj": v.T}


def _fuse_codegen_qkv(layer: Mapping[str, Any], num_heads: int, rotary_dim: int):
    q = np.asarray(_get(layer, "attn/qkv/q_proj/kernel")).T
    k = np.asarray(_get(layer, "attn/qkv/k_proj/kernel")).T
    v = np.asarray(_get(layer, "attn/qkv/v_proj/kernel")).T
    hidden = q.shape[1]
    inv = _rotary_perm(num_heads, hidden // num_heads, rotary_dim, inverse=True)
    q, k = q[inv], k[inv]
    mp = _CODEGEN_MP_NUM
    local = hidden // mp
    blocks = [
        np.concatenate(
            [q[m * local : (m + 1) * local],
             v[m * local : (m + 1) * local],
             k[m * local : (m + 1) * local]], axis=0
        )
        for m in range(mp)
    ]
    return np.concatenate(blocks, axis=0)


def hf_to_native_codegen(
    hf_state: Mapping[str, np.ndarray], num_heads: int, rotary_dim: int
) -> Dict[str, Any]:
    params: Dict[str, Any] = {}
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _CODEGEN_TOP_MAP:
            path, transpose = _CODEGEN_TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("transformer.h."):
            rest = name[len("transformer.h.") :]
            idx_str, suffix = rest.split(".", 1)
            idx = int(idx_str)
            if suffix in _CODEGEN_SKIP_SUFFIXES:
                continue
            if suffix == "attn.qkv_proj.weight":
                for sub, t in _split_codegen_qkv(
                    tensor, num_heads, rotary_dim
                ).items():
                    _set(params, f"blocks_{idx}/attn/qkv/{sub}/kernel", t)
                continue
            if suffix in _CODEGEN_LAYER_MAP:
                path, transpose = _CODEGEN_LAYER_MAP[suffix]
                _set(params, f"blocks_{idx}/{path}",
                     tensor.T if transpose else tensor)
                continue
            raise KeyError(f"unmapped HF CodeGen tensor: {name}")
        raise KeyError(f"unmapped HF CodeGen tensor: {name}")
    return {"params": params}


def native_to_hf_codegen(
    params: Mapping[str, Any], num_heads: int, rotary_dim: int
) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _CODEGEN_TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    idx = 0
    while f"blocks_{idx}" in tree:
        blk = tree[f"blocks_{idx}"]
        for hf_suffix, (path, transpose) in _CODEGEN_LAYER_MAP.items():
            t = np.asarray(_get(blk, path))
            out[f"transformer.h.{idx}.{hf_suffix}"] = t.T if transpose else t
        out[f"transformer.h.{idx}.attn.qkv_proj.weight"] = _fuse_codegen_qkv(
            blk, num_heads, rotary_dim
        )
        idx += 1
    return out


# --- BERT family (reference example: tp_dp_bert_hf_pretrain) ------------------

_BERT_TOP_MAP = {
    "bert.embeddings.word_embeddings.weight": ("bert/tok_embed/embedding", False),
    "bert.embeddings.position_embeddings.weight": ("bert/pos_embed/embedding", False),
    "bert.embeddings.token_type_embeddings.weight": ("bert/type_embed/embedding", False),
    "bert.embeddings.LayerNorm.weight": ("bert/embed_norm/ln/scale", False),
    "bert.embeddings.LayerNorm.bias": ("bert/embed_norm/ln/bias", False),
    "cls.predictions.transform.dense.weight": ("transform/kernel", True),
    "cls.predictions.transform.dense.bias": ("transform/bias", False),
    "cls.predictions.transform.LayerNorm.weight": ("transform_norm/ln/scale", False),
    "cls.predictions.transform.LayerNorm.bias": ("transform_norm/ln/bias", False),
    "cls.predictions.decoder.weight": ("decoder/kernel", True),
    "cls.predictions.decoder.bias": ("decoder/bias", False),
}

_BERT_LAYER_MAP = {
    "attention.self.query.weight": ("attn/qkv/q_proj/kernel", True),
    "attention.self.query.bias": ("attn/qkv/q_proj/bias", False),
    "attention.self.key.weight": ("attn/qkv/k_proj/kernel", True),
    "attention.self.key.bias": ("attn/qkv/k_proj/bias", False),
    "attention.self.value.weight": ("attn/qkv/v_proj/kernel", True),
    "attention.self.value.bias": ("attn/qkv/v_proj/bias", False),
    "attention.output.dense.weight": ("attn/o_proj/kernel", True),
    "attention.output.dense.bias": ("attn/o_proj/bias", False),
    "attention.output.LayerNorm.weight": ("attn_norm/ln/scale", False),
    "attention.output.LayerNorm.bias": ("attn_norm/ln/bias", False),
    "intermediate.dense.weight": ("mlp/up/kernel", True),
    "intermediate.dense.bias": ("mlp/up/bias", False),
    "output.dense.weight": ("mlp/down/kernel", True),
    "output.dense.bias": ("mlp/down/bias", False),
    "output.LayerNorm.weight": ("mlp_norm/ln/scale", False),
    "output.LayerNorm.bias": ("mlp_norm/ln/bias", False),
}

_BERT_SKIP = ("bert.embeddings.position_ids", "cls.predictions.bias")


def hf_to_native_bert(hf_state: Mapping[str, np.ndarray]) -> Dict[str, Any]:
    """HF BertForMaskedLM → native. ``cls.predictions.bias`` duplicates
    ``decoder.bias`` in HF (tied) — the decoder copy wins; tied exports with
    no ``decoder.weight`` fall back to the word embedding."""
    params: Dict[str, Any] = {}
    pred_bias = None
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name == "cls.predictions.bias":
            pred_bias = tensor
            continue
        if name in _BERT_SKIP or name.startswith("bert.pooler."):
            continue
        if name in _BERT_TOP_MAP:
            path, transpose = _BERT_TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name.startswith("bert.encoder.layer."):
            rest = name[len("bert.encoder.layer.") :]
            idx_str, suffix = rest.split(".", 1)
            if suffix not in _BERT_LAYER_MAP:
                raise KeyError(f"unmapped HF BERT tensor: {name}")
            path, transpose = _BERT_LAYER_MAP[suffix]
            _set(params, f"bert/layers_{idx_str}/{path}",
                 tensor.T if transpose else tensor)
            continue
        raise KeyError(f"unmapped HF BERT tensor: {name}")
    if "kernel" not in params.get("decoder", {}):
        # tied export: decoder.weight stripped (bias may still be present)
        _set(params, "decoder/kernel",
             np.asarray(_get(params, "bert/tok_embed/embedding")).T)
    if "bias" not in params.get("decoder", {}):
        vocab = _get(params, "decoder/kernel").shape[1]
        _set(params, "decoder/bias",
             pred_bias if pred_bias is not None else np.zeros(vocab, np.float32))
    return {"params": params}


def native_to_hf_bert(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _BERT_TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    out["cls.predictions.bias"] = out["cls.predictions.decoder.bias"]
    bert = tree["bert"]
    idx = 0
    while f"layers_{idx}" in bert:
        layer = bert[f"layers_{idx}"]
        for hf_suffix, (path, transpose) in _BERT_LAYER_MAP.items():
            t = np.asarray(_get(layer, path))
            out[f"bert.encoder.layer.{idx}.{hf_suffix}"] = t.T if transpose else t
        idx += 1
    return out


# --- ViT family (reference example: examples/training/vit) --------------------

_VIT_TOP_MAP = {
    "vit.embeddings.cls_token": ("cls_token", False),
    "vit.embeddings.position_embeddings": ("pos_embed", False),
    "vit.embeddings.patch_embeddings.projection.bias": ("patch_embed/bias", False),
    "vit.layernorm.weight": ("final_norm/ln/scale", False),
    "vit.layernorm.bias": ("final_norm/ln/bias", False),
    "classifier.weight": ("classifier/kernel", True),
    "classifier.bias": ("classifier/bias", False),
}

_VIT_LAYER_MAP = {
    "attention.attention.query.weight": ("attn/qkv/q_proj/kernel", True),
    "attention.attention.query.bias": ("attn/qkv/q_proj/bias", False),
    "attention.attention.key.weight": ("attn/qkv/k_proj/kernel", True),
    "attention.attention.key.bias": ("attn/qkv/k_proj/bias", False),
    "attention.attention.value.weight": ("attn/qkv/v_proj/kernel", True),
    "attention.attention.value.bias": ("attn/qkv/v_proj/bias", False),
    "attention.output.dense.weight": ("attn/o_proj/kernel", True),
    "attention.output.dense.bias": ("attn/o_proj/bias", False),
    "layernorm_before.weight": ("norm_1/ln/scale", False),
    "layernorm_before.bias": ("norm_1/ln/bias", False),
    "layernorm_after.weight": ("norm_2/ln/scale", False),
    "layernorm_after.bias": ("norm_2/ln/bias", False),
    "intermediate.dense.weight": ("mlp/up/kernel", True),
    "intermediate.dense.bias": ("mlp/up/bias", False),
    "output.dense.weight": ("mlp/down/kernel", True),
    "output.dense.bias": ("mlp/down/bias", False),
}


def hf_to_native_vit(hf_state: Mapping[str, np.ndarray]) -> Dict[str, Any]:
    params: Dict[str, Any] = {}
    for name, tensor in hf_state.items():
        tensor = np.asarray(tensor)
        if name in _VIT_TOP_MAP:
            path, transpose = _VIT_TOP_MAP[name]
            _set(params, path, tensor.T if transpose else tensor)
            continue
        if name == "vit.embeddings.patch_embeddings.projection.weight":
            # HF conv (out, in, kh, kw) → flax conv (kh, kw, in, out)
            _set(params, "patch_embed/kernel", np.transpose(tensor, (2, 3, 1, 0)))
            continue
        if name.startswith("vit.encoder.layer."):
            rest = name[len("vit.encoder.layer.") :]
            idx_str, suffix = rest.split(".", 1)
            if suffix not in _VIT_LAYER_MAP:
                raise KeyError(f"unmapped HF ViT tensor: {name}")
            path, transpose = _VIT_LAYER_MAP[suffix]
            _set(params, f"blocks_{idx_str}/{path}",
                 tensor.T if transpose else tensor)
            continue
        raise KeyError(f"unmapped HF ViT tensor: {name}")
    return {"params": params}


def native_to_hf_vit(params: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    tree = dict(params.get("params", params))
    out: Dict[str, np.ndarray] = {}
    for hf_name, (path, transpose) in _VIT_TOP_MAP.items():
        t = np.asarray(_get(tree, path))
        out[hf_name] = t.T if transpose else t
    out["vit.embeddings.patch_embeddings.projection.weight"] = np.transpose(
        np.asarray(_get(tree, "patch_embed/kernel")), (3, 2, 0, 1)
    )
    idx = 0
    while f"blocks_{idx}" in tree:
        blk = tree[f"blocks_{idx}"]
        for hf_suffix, (path, transpose) in _VIT_LAYER_MAP.items():
            t = np.asarray(_get(blk, path))
            out[f"vit.encoder.layer.{idx}.{hf_suffix}"] = t.T if transpose else t
        idx += 1
    return out


FAMILIES = ("llama", "mixtral", "gpt_neox", "dbrx", "codegen", "bert", "vit")


def _load_hf_dir(hf_dir: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    state: Dict[str, np.ndarray] = {}
    files = sorted(f for f in os.listdir(hf_dir) if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {hf_dir}")
    for fname in files:
        with safe_open(os.path.join(hf_dir, fname), framework="numpy") as f:
            for key in f.keys():
                state[key] = f.get_tensor(key)
    return state


def convert_hf_to_native(
    hf_dir: str,
    output_dir: str,
    tag: str = "hf_import",
    scan_layers: bool = False,
    family: str = "llama",
    num_heads: int = 0,
    num_kv_heads: int = 0,
    rotary_dim: int = 0,
) -> None:
    from neuronx_distributed_tpu.trainer.checkpoint import save_checkpoint

    state = _load_hf_dir(hf_dir)
    if family == "llama":
        params = hf_to_native(state, scan_layers=scan_layers)
    elif family == "mixtral":
        params = hf_to_native_mixtral(state, scan_layers=scan_layers)
    elif family == "gpt_neox":
        if num_heads <= 0:
            raise ValueError("gpt_neox conversion needs --num-heads (fused QKV split)")
        params = hf_to_native_gpt_neox(state, num_heads=num_heads)
    elif family == "dbrx":
        if num_heads <= 0 or num_kv_heads <= 0:
            raise ValueError(
                "dbrx conversion needs --num-heads and --num-kv-heads (Wqkv split)"
            )
        params = hf_to_native_dbrx(
            state, num_heads=num_heads, num_kv_heads=num_kv_heads
        )
    elif family == "codegen":
        if num_heads <= 0 or rotary_dim <= 0:
            raise ValueError(
                "codegen conversion needs --num-heads and --rotary-dim "
                "(fused qkv + rotary channel permutation)"
            )
        params = hf_to_native_codegen(
            state, num_heads=num_heads, rotary_dim=rotary_dim
        )
    elif family == "bert":
        params = hf_to_native_bert(state)
    elif family == "vit":
        params = hf_to_native_vit(state)
    else:
        raise ValueError(f"unknown family {family!r} (choose from {FAMILIES})")
    save_checkpoint(output_dir, tag, items={"model": params})


def convert_native_to_hf(
    checkpoint_dir: str,
    output_dir: str,
    tag: str = None,
    tie_word_embeddings: bool = False,
    family: str = "llama",
    num_heads: int = 0,
    num_kv_heads: int = 0,
    rotary_dim: int = 0,
) -> None:
    from safetensors.numpy import save_file

    from neuronx_distributed_tpu.trainer.checkpoint import load_checkpoint

    items, _, tag = load_checkpoint(checkpoint_dir, tag, items_target={"model": None})
    if family == "llama":
        hf_state = native_to_hf(items["model"], tie_word_embeddings=tie_word_embeddings)
    elif family == "mixtral":
        hf_state = native_to_hf_mixtral(items["model"])
    elif family == "gpt_neox":
        if num_heads <= 0:
            raise ValueError("gpt_neox conversion needs --num-heads (QKV fuse)")
        hf_state = native_to_hf_gpt_neox(items["model"], num_heads=num_heads)
    elif family == "dbrx":
        hf_state = native_to_hf_dbrx(items["model"])
    elif family == "codegen":
        if num_heads <= 0 or rotary_dim <= 0:
            raise ValueError(
                "codegen conversion needs --num-heads and --rotary-dim"
            )
        hf_state = native_to_hf_codegen(
            items["model"], num_heads=num_heads, rotary_dim=rotary_dim
        )
    elif family == "bert":
        hf_state = native_to_hf_bert(items["model"])
    elif family == "vit":
        hf_state = native_to_hf_vit(items["model"])
    else:
        raise ValueError(f"unknown family {family!r} (choose from {FAMILIES})")
    os.makedirs(output_dir, exist_ok=True)
    # safetensors writes the raw buffer IGNORING strides — a transposed view
    # (which every `t.T` mapping above produces) would be silently saved with
    # its pre-transpose content. Contiguity is load-bearing here.
    hf_state = {k: np.ascontiguousarray(v) for k, v in hf_state.items()}
    save_file(hf_state, os.path.join(output_dir, "model.safetensors"))
    with open(os.path.join(output_dir, "conversion_info.json"), "w") as f:
        json.dump({"source": checkpoint_dir, "tag": tag, "family": family}, f)


def main() -> None:
    # conversion is pure host-side IO/layout work — it never takes (or
    # waits on) an accelerator
    import jax

    jax.config.update("jax_platforms", "cpu")
    p = argparse.ArgumentParser(description="HF ↔ native checkpoint converter")
    p.add_argument("--direction", choices=["hf2native", "native2hf"], required=True)
    p.add_argument("--family", choices=list(FAMILIES), default="llama")
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--tag", default=None)
    p.add_argument("--scan-layers", action="store_true")
    p.add_argument("--tie-embeddings", action="store_true")
    p.add_argument("--num-heads", type=int, default=0,
                   help="attention heads (gpt_neox/dbrx/codegen fused-QKV split/fuse)")
    p.add_argument("--num-kv-heads", type=int, default=0,
                   help="KV heads (dbrx GQA Wqkv split)")
    p.add_argument("--rotary-dim", type=int, default=0,
                   help="rotary channels per head (codegen partial rotary permutation)")
    args = p.parse_args()
    if args.direction == "hf2native":
        convert_hf_to_native(
            args.input, args.output, args.tag or "hf_import", args.scan_layers,
            family=args.family, num_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads, rotary_dim=args.rotary_dim,
        )
    else:
        convert_native_to_hf(
            args.input, args.output, args.tag,
            tie_word_embeddings=args.tie_embeddings,
            family=args.family, num_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads, rotary_dim=args.rotary_dim,
        )


if __name__ == "__main__":
    main()
