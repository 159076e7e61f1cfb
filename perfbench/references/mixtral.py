"""Plain reference of the Mixtral sparse-MoE causal LM.

Published description (Jiang et al., "Mixtral of Experts"; the HF
``MixtralForCausalLM``): token embedding; per block RMSNorm -> grouped-query
causal attention (rotary over the whole head, no biases) -> residual;
RMSNorm -> router: softmax over all experts, the top ``k`` kept and their
weights renormalised to sum to one -> each token goes through its ``k``
SwiGLU experts (``down(silu(gate(x)) * up(x))``), dropless -> weighted sum
-> residual; a final RMSNorm and an unbiased output head.

The expert layer is computed densely here (every expert on every token,
then masked by the routing weights): the plainest form of the same sum.

Top-``k`` routing is a step function of the router's logits: where the
``k``-th and the ``k+1``-th logit of a position nearly tie, a system in bf16
may send the token to another expert than this float32 reference does, and its
logits there are then another model's. So the reference also reports, per
position, how close its own choice was (``logits_and_router_margin``): the
smallest gap over the layers between the last logit kept and the first one
dropped. The comparison that decides ``correct`` exempts a position only
where that margin is under the configuration's ``router_near_tie``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.references import common


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


class Reference:
    def __init__(self, cfg: dict, params):
        self.cfg = cfg
        self.p = params["params"]
        heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        top_k, n_exp = int(cfg["num_experts_per_tok"]), int(cfg["num_local_experts"])
        eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])

        def block(layer, x):
            # the experts are upcast one at a time below: a float32 copy of a
            # whole layer's experts (5.8 GB at 8 x 14336) fits beside nothing
            ex = layer["moe"]["experts"]
            layer = common.f32({k: v for k, v in layer.items() if k != "moe"}
                               | {"moe": {"router": layer["moe"]["router"]}})
            b, s, hid = x.shape
            d = hid // heads
            h = _rms_norm(x, layer["input_norm"]["weight"], eps)
            qkv = layer["attn"]["qkv"]
            q = (h @ qkv["q_proj"]["kernel"]).reshape(b, s, heads, d)
            k = (h @ qkv["k_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            v = (h @ qkv["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q = common.rope_half_split(q, pos, d, theta)
            k = common.rope_half_split(k, pos, d, theta)
            attn = common.causal_attention(q, k, v).reshape(b, s, hid)
            x = x + attn @ layer["attn"]["o_proj"]["kernel"]
            h = _rms_norm(x, layer["post_attn_norm"]["weight"], eps)
            router_logits = h @ layer["moe"]["router"]["weight"]
            ranked = jax.lax.top_k(router_logits, top_k + 1)[0]
            margin = ranked[..., top_k - 1] - ranked[..., top_k]                  # (B, S)
            probs = jax.nn.softmax(router_logits, axis=-1)
            top_w, top_e = jax.lax.top_k(probs, top_k)
            top_w = top_w / top_w.sum(-1, keepdims=True)
            weights = (jax.nn.one_hot(top_e, n_exp) * top_w[..., None]).sum(-2)   # (B, S, E)
            out = jnp.zeros_like(x)
            for e in range(n_exp):
                gate, up, down = (jnp.asarray(ex[n][e], jnp.float32)
                                  for n in ("gate_proj", "up_proj", "down_proj"))
                out = out + ((jax.nn.silu(h @ gate) * (h @ up)) @ down) * weights[..., e:e + 1]
            return x + out, margin

        def embed(table, ids):
            return jnp.asarray(table, jnp.float32)[ids]

        def head(norm, lm, x):
            return _rms_norm(x, jnp.asarray(norm["weight"], jnp.float32), eps) @ jnp.asarray(
                lm["kernel"], jnp.float32)

        self._block = common.highest(block)
        self._embed = common.highest(embed)
        self._head = common.highest(head)

    def logits_and_router_margin(self, ids):
        """``(logits (B, S, V), margin (B, S))``: the margin is the smallest,
        over the layers, of the router's last kept logit less its first
        dropped one at that position."""
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for i in range(int(self.cfg["num_hidden_layers"])):
            x, m = self._block(model[f"layers_{i}"], x)
            margin = jnp.minimum(margin, m)
        return self._head(model["final_norm"], self.p["lm_head"], x), margin

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]
