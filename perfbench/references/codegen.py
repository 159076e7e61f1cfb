"""Plain reference of the CodeGen (GPT-J style) causal LM.

Published description (Nijkamp et al., CodeGen / CodeGen2; the HF
``CodeGenForCausalLM``): token embedding; per block ONE LayerNorm whose
output feeds both a multi-head causal attention (no projection biases,
rotary over the first ``rotary_dim`` channels of each head) and a biased
MLP ``h -> 4h -> h`` with tanh-GELU; the block returns
``x + attn(ln(x)) + mlp(ln(x))``; a final LayerNorm and a biased output head.

Departure, noted: rotary pairs channel ``i`` with ``i + rotary_dim/2`` (the
repo's convention) and not even with odd channels (see ``common``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references import common


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


class Reference:
    """``logits(ids)`` and ``loss(ids, labels)`` of the same weights the
    system holds (``params``: the unboxed flax tree, any float dtype)."""

    def __init__(self, cfg: dict, params):
        self.cfg = cfg
        self.p = params["params"]
        heads, rot = int(cfg["n_head"]), int(cfg["rotary_dim"])
        eps, theta = float(cfg["layer_norm_epsilon"]), float(cfg.get("rope_theta", 10000.0))

        def block(layer, x):
            layer = common.f32(layer)
            b, s, hid = x.shape
            d = hid // heads
            h = _layer_norm(x, layer["input_norm"]["ln"], eps)
            qkv = layer["attn"]["qkv"]
            q = (h @ qkv["q_proj"]["kernel"]).reshape(b, s, heads, d)
            k = (h @ qkv["k_proj"]["kernel"]).reshape(b, s, heads, d)
            v = (h @ qkv["v_proj"]["kernel"]).reshape(b, s, heads, d)
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q = common.rope_half_split(q, pos, rot, theta)
            k = common.rope_half_split(k, pos, rot, theta)
            attn = common.causal_attention(q, k, v).reshape(b, s, hid)
            attn = attn @ layer["attn"]["o_proj"]["kernel"]
            up = h @ layer["mlp"]["up"]["kernel"] + layer["mlp"]["up"]["bias"]
            mlp = jax.nn.gelu(up, approximate=True) @ layer["mlp"]["down"]["kernel"]
            return x + attn + mlp + layer["mlp"]["down"]["bias"]

        def embed(table, ids):
            return jnp.asarray(table, jnp.float32)[ids]

        def head(norm, lm, x):
            norm, lm = common.f32(norm), common.f32(lm)
            x = _layer_norm(x, norm["ln"], eps)
            return x @ lm["kernel"] + lm["bias"]

        self._block = common.highest(block)
        self._embed = common.highest(embed)
        self._head = common.highest(head)
        self._loss = jax.jit(common.cross_entropy)

    def logits(self, ids):
        x = self._embed(self.p["embed"]["embedding"], jnp.asarray(ids))
        for i in range(int(self.cfg["n_layer"])):
            x = self._block(self.p[f"blocks_{i}"], x)
        return self._head(self.p["final_norm"], self.p["lm_head"], x)

    def loss(self, ids, labels) -> float:
        """Mean cross entropy over the batch, one sequence at a time (the
        float32 S x S scores of a whole batch would not fit)."""
        total = 0.0
        for row, lab in zip(ids, labels):
            total += float(self._loss(self.logits(row[None]), jnp.asarray(lab[None])))
        return total / len(ids)
