"""Plain reference of the DeepSeek-V2 causal LM (multi-head latent attention,
shared + routed experts), after the published ``modeling_deepseek.py`` and
``deepseek-ai/DeepSeek-V2-Lite``'s ``config.json`` (``q_lora_rank`` null).

Per block, ``h`` a token's hidden vector, ``H`` heads, RMSNorm eps from the
config, no biases:

* ``x += attn(norm1(x))``; ``x += ffn(norm2(x))``; a final RMSNorm and an
  untied output head.
* attention, MATERIALISED form only: ``q = W_q h`` -> ``H x (d_nope +
  d_rope)``; ``W_kv_a h`` -> ``d_c + d_rope``: ``c = RMSNorm(first d_c)``,
  ``k_pe = rope(last d_rope)``, one per token for all heads; ``W_kv_b c`` ->
  ``H x (d_nope + d_v)``: ``k_nope``, ``v``; ``q_pe = rope(q_pe)``; scores
  ``(q_nope . k_nope + q_pe . k_pe) * scale``, causal softmax, ``. v``,
  ``W_o``. ``scale = (d_nope + d_rope)^-0.5 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``. The system decodes in the ABSORBED form
  (``W_kv_b`` folded into the query and the output, a cache of ``c`` and
  ``k_pe``); this file never does, so that form is checked against
  independent mathematics. Attention runs in query blocks (a 24k context's
  scores do not fit at once); each block sees every key.
* YaRN over the ``d_rope`` channels: ``f_extra = base^(-2i/d)``, ``f_inter =
  f_extra / factor``; ``dim(r) = d * ln(orig / (2 pi r)) / (2 ln base)``,
  ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` clamped to
  ``[0, d - 1]``; ``ramp = clip((i - low) / (high - low), 0, 1)``, ``i < d /
  2``; ``inv_freq = f_inter * ramp + f_extra * (1 - ramp)``; cos and sin
  times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``.
* FFN: the first ``first_k_dense_replace`` layers a SwiGLU MLP. The others:
  gate logits, softmax over the routed experts, greedy top-k, weights NOT
  renormalised (``norm_topk_prob`` false), times ``routed_scaling_factor``;
  ``sum_i w_i expert_i(h) + shared(h)``, ``shared`` one SwiGLU MLP of
  ``n_shared_experts * moe_intermediate_size``. Experts densely (every expert
  on every token, masked by the routing weights), upcast one at a time (a
  ``scan`` over the experts: 64 outputs of a 24k context side by side do not
  fit).

Departures: the published code de-interleaves the rope channels (even, odd
-> halves) before ``rotate_half``; here, as in the system, channel ``i``
pairs with ``i + d/2`` directly: a fixed permutation of projection columns
that random weights cannot tell apart (``common.rope_half_split`` notes the
same for CodeGen). ``n_group``/``topk_group`` (one group in V2-Lite) are not
modelled.

Top-k routing is a step function (``references/mixtral.py``): the reference
reports per position its own narrowest margin between the last expert kept
and the first dropped, over the layers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.references import common

QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling):
    """``(inv_freq (dim/2,), cos/sin scale)`` as the docstring has it."""
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    f_extra = 1.0 / (theta ** (i / dim))
    if not scaling:
        return f_extra, 1.0
    factor, orig = float(scaling["factor"]), float(scaling["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv = (f_extra / factor) * ramp + f_extra * (1 - ramp)
    return inv, _mscale(factor, float(scaling["mscale"])) / _mscale(factor, float(scaling["mscale_all_dim"]))


def _rope(x, positions, inv_freq, scale):
    """x (B, S, H, d): channel ``i`` with ``i + d/2`` (module docstring)."""
    ang = positions[..., None].astype(jnp.float32) * inv_freq           # (B, S, d/2)
    cos, sin = scale * jnp.cos(ang)[:, :, None, :], scale * jnp.sin(ang)[:, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocked_causal_attention(q, k, v, scale, block):
    """q, k (B, S, H, dq), v (B, S, H, dv): causal softmax attention, ONE
    block of queries at a time (``lax.map``: the blocks' scores never stand
    side by side) against ALL keys."""
    b, s, h, _ = q.shape
    block = min(block, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))    # rows past S are dropped below
    cols = jnp.arange(s)

    def one(args):
        qb, lo = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        rows = lo + jnp.arange(block)
        scores = jnp.where((rows[:, None] >= cols[None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (jnp.moveaxis(q.reshape(b, n, block, h, -1), 1, 0), jnp.arange(n) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, h, -1)[:, :s]


class Reference:
    """``latent_dtype``: round what a latent cache would hold (the normed
    latent and the rotated key) to that type before they are used: the
    reference "computed in a lower precision", for showing that a comparison
    against the plain one can fail (``chip_smoke.py``)."""

    def __init__(self, cfg: dict, params, latent_dtype=None):
        self.cfg = cfg
        self.p = params["params"]
        heads = int(cfg["num_attention_heads"])
        d_c, d_n = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"])
        d_r, d_v = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
        top_k, n_exp = int(cfg["num_experts_per_tok"]), int(cfg["n_routed_experts"])
        eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
        scaling = cfg.get("rope_scaling")
        routed_scale = float(cfg.get("routed_scaling_factor", 1.0))
        renorm = bool(cfg.get("norm_topk_prob", False))
        softmax_scale = (d_n + d_r) ** -0.5
        if scaling and scaling.get("mscale_all_dim"):
            softmax_scale *= _mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"])) ** 2
        self.first_dense = int(cfg.get("first_k_dense_replace", 0))

        def attention(layer, x):
            b, s, _ = x.shape
            a = layer["attn"]
            inv_freq, rope_scale = yarn_inv_freq(d_r, theta, scaling)
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            h = _rms_norm(x, layer["input_norm"]["weight"], eps)
            q = (h @ a["q_proj"]["kernel"]).reshape(b, s, heads, d_n + d_r)
            kv_a = h @ a["kv_a_proj"]["kernel"]
            c = _rms_norm(kv_a[..., :d_c], a["kv_a_norm"]["weight"], eps)
            k_pe = _rope(kv_a[..., d_c:][:, :, None, :], pos, inv_freq, rope_scale)
            if latent_dtype is not None:
                c, k_pe = (t.astype(latent_dtype).astype(jnp.float32) for t in (c, k_pe))
            kv = (c @ a["kv_b_proj"].reshape(d_c, heads * (d_n + d_v))).reshape(b, s, heads, d_n + d_v)
            q = jnp.concatenate([q[..., :d_n], _rope(q[..., d_n:], pos, inv_freq, rope_scale)], -1)
            k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(k_pe, (b, s, heads, d_r))], -1)
            out = _blocked_causal_attention(q, k, kv[..., d_n:], softmax_scale, QUERY_BLOCK)
            return x + out.reshape(b, s, heads * d_v) @ a["o_proj"]["kernel"]

        def swiglu(w, h):
            return (jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"])) @ w["down"]["kernel"]

        def dense_block(layer, x):
            layer = common.f32(layer)
            x = attention(layer, x)
            h = _rms_norm(x, layer["post_attn_norm"]["weight"], eps)
            return x + swiglu(layer["mlp"], h), jnp.full(x.shape[:2], jnp.inf, jnp.float32)

        def sparse_block(layer, x):
            # the experts are upcast one at a time: 64 x 3 x 2048 x 1408 in
            # float32 is 2.2 GB a layer
            ex = layer["moe"]["experts"]
            layer = common.f32({k: v for k, v in layer.items() if k != "moe"}
                               | {"moe": {k: v for k, v in layer["moe"].items() if k != "experts"}})
            x = attention(layer, x)
            h = _rms_norm(x, layer["post_attn_norm"]["weight"], eps)
            router_logits = h @ layer["moe"]["router"]["weight"]
            ranked = jax.lax.top_k(router_logits, top_k + 1)[0]
            margin = ranked[..., top_k - 1] - ranked[..., top_k]                   # (B, S)
            top_w, top_e = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1), top_k)
            if renorm:
                top_w = top_w / top_w.sum(-1, keepdims=True)
            weights = (jax.nn.one_hot(top_e, n_exp) * (top_w * routed_scale)[..., None]).sum(-2)
            out = swiglu(layer["moe"]["shared"], h) if "shared" in layer["moe"] else jnp.zeros_like(x)

            def one_expert(acc, e):     # a scan: one expert's float32 copy and output at a time
                gate, up, down, w = (jnp.asarray(a, jnp.float32) for a in e)
                return acc + ((jax.nn.silu(h @ gate) * (h @ up)) @ down) * w[..., None], None

            out, _ = jax.lax.scan(one_expert, out, (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return x + out, margin

        def embed(table, ids):
            return jnp.asarray(table, jnp.float32)[ids]

        def head(norm, lm, x):
            return _rms_norm(x, jnp.asarray(norm["weight"], jnp.float32), eps) @ jnp.asarray(
                lm["kernel"], jnp.float32)

        self._dense = common.highest(dense_block)
        self._sparse = common.highest(sparse_block)
        self._embed = common.highest(embed)
        self._head = common.highest(head)

    def _hidden(self, ids):
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for i in range(int(self.cfg["num_hidden_layers"])):
            block = self._dense if i < self.first_dense else self._sparse
            x, m = block(model[f"layers_{i}"], x)
            margin = jnp.minimum(margin, m)
        return x, margin

    def logits_and_router_margin(self, ids, positions=None):
        """``(logits (B, S, V), margin (B, S))``: the margin is the smallest,
        over the sparse layers, of the router's last kept logit less its first
        dropped one at that position. ``positions``: only these (a 1-D index
        array into S) go through the output head: a long context's logits at
        every position would not fit."""
        x, margin = self._hidden(ids)
        if positions is not None:
            x, margin = x[:, positions], margin[:, positions]
        return self._head(self.p["model"]["final_norm"], self.p["lm_head"], x), margin

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]
