"""Plain reference of Zyphra's ZAYA1 language models (``zaya``): attention in
a compressed latent whose queries and keys pass two short causal convolutions
and whose second value head reads the previous token (CCA), a router that is
an MLP over a state of its own passed from layer to layer, top-1 experts, a
learned scale and bias on both sides of every residual merge, a tied head;
after the catalog's ``config`` of ``Zyphra/ZAYA1-8B``. Float32 ``jax.numpy`` at
matmul precision ``highest``; no kernel, no cache, NO STATE: a convolution is a
shift of the whole sequence by one position. Fed the system's weights a layer
at a time, attention in blocks of queries, the experts upcast one at a time,
the head over the vocabulary in blocks of positions.

Per layer ``l``, position ``t``, stream ``x`` (``h``), router state ``r``; ``H``
query and ``Hkv`` = 2 key/value heads of ``D``, ``G = H / Hkv``; RMSNorm eps
``rms_norm_eps`` with a learned scale:

1. ``u = N1(x)``. ``q~ = Wq u``, ``k~ = Wk u``, ``v = [Wv1 u_t ; Wv2 u_{t-1}]``
   (kv head 1 reads the previous position; position 0 reads zeros).
2. ``m_q = (q~ + repeat(k~, G)) / 2``; ``m_k`` its mean over a kv head's ``G``
   query heads.
3. ``c = [q~ ; k~]``. ``y0_t = w0[0] c_{t-1} + w0[1] c_t + b0`` (depthwise);
   ``y1_t = W1[0] y0_{t-1} + W1[1] y0_t + b1`` (a ``D x D`` matrix a head and
   tap); ``c_{-1} = y0_{-1} = 0``.
4. ``q = y1[q part] + m_q``, ``k = y1[k part] + m_k``; each head to length
   ``sqrt(D)``, ``k`` times its kv head's temperature ``tau``; rotary over the
   first ``partial_rotary_factor * D`` channels of each head at ``rope_theta``.
5. ``a = softmax(q k^T / sqrt(D), causal) v``, query head ``n`` reads kv head
   ``n // G``; ``o = Wo a``.
6. ``x <- (x + b_r) * s_r + (o + b_f) * s_f``; the same form after the experts.
7. ``g = N2(x)``; ``s = Wd g + bd``; ``l > 0``: ``s <- s + gamma * r``; ``r <-
   s``; ``p = softmax(W3 gelu(W2 gelu(W1 N(s) + c1) + c2))`` (exact gelu);
   ``e = argmax(p + bias)``; ``y = p_e * Expert_e(g)`` (SwiGLU), not
   renormalised.
8. A final RMSNorm; ``logits = x E^T`` (tied).

The router's margin, per position the narrowest over the layers: the largest
``p + bias`` less the second.

Departures: rotary pairing (channel ``i`` with ``i + rot / 2``, as the system:
``common.rope_half_split``). Controls, for showing that a comparison against
this file can fail: ``conv`` (``"none"``: ``y1 = c``, no convolution),
``value_shift`` False (kv head 1 reads ITS OWN position), ``eda`` False (no
``gamma * r``), ``residual_scaling`` False (a plain ``x + o``),
``bias_in_weights`` (the selection bias wrongly added to the weight too),
``kv_dtype`` (round what a cache would hold), ``dtype`` (the WHOLE reference in
a lower precision: every weight, every cached value and every projection's
input rounded through it, one scale a tensor; sums still in float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import common
from perfbench.references.glm_moe_dsa import _blocks, _rms_norm, _rounded

QUERY_BLOCK = 256
HEAD_BLOCK = 512


def rope_theta(cfg: dict) -> float:
    """The published file keeps the layers' rotary base under their kind."""
    rope = cfg.get("rope_parameters") or {}
    return float(rope.get("hybrid", rope).get("rope_theta", cfg.get("rope_theta", 10000.0)))


def _prev(a):
    """``a`` (B, S, ...) a position later, zeros at position 0."""
    return jnp.pad(a[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (a.ndim - 2))


class Reference:
    def __init__(self, cfg: dict, params, conv=None, value_shift: bool = True, eda: bool = True,
                 residual_scaling: bool = True, bias_in_weights: bool = False, kv_dtype=None, dtype=None):
        self.cfg = cfg
        if dtype is not None:
            kv_dtype = kv_dtype or dtype
        if conv not in (None, "none"):
            raise ValueError(f"conv is None (as published) or 'none', got {conv!r}")

        def f32(tree):
            """The stored weights in float32, through ``dtype`` where it is given."""
            return common.f32(tree) if dtype is None else jax.tree.map(lambda a: _rounded(a, dtype), tree)

        def act(x):
            """A projection's input, through ``dtype`` where it is given."""
            return x if dtype is None else _rounded(x, dtype)

        self.p = params["params"]
        heads, kv_heads, d = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
        group = heads // kv_heads
        eps = float(cfg["rms_norm_eps"])
        rot = int(d * float(cfg["partial_rotary_factor"]))
        theta = rope_theta(cfg)
        if kv_heads != 2 or int(cfg["num_experts_per_tok"]) != 1:
            raise ValueError("written for 2 kv heads (the value shift) and one expert a token")
        if (int(cfg["cca_time0"]), int(cfg["cca_time1"])) != (2, 2):
            raise ValueError("written for convolutions that reach one position back")
        self.layers = int(cfg["num_hidden_layers"])

        def attention(a, u):
            """``Wo softmax(q k^T) v`` of normed inputs ``u`` (B, S, h): steps 1-5."""
            b, s, _ = u.shape
            u = act(u)
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q0 = (u @ a["qkv"]["q_proj"]["kernel"]).reshape(b, s, heads, d)
            k0 = (u @ a["qkv"]["k_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            v = (u @ a["qkv"]["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            if value_shift:
                v = jnp.stack([v[:, :, 0], _prev(v[:, :, 1])], axis=2)
            m_q = (q0 + jnp.repeat(k0, group, axis=2)) / 2.0
            m_k = m_q.reshape(b, s, kv_heads, group, d).mean(axis=3)
            c = jnp.concatenate([q0, k0], axis=2)                                  # (B, S, H + Hkv, D)
            if conv == "none":
                y1 = c
            else:
                w0 = a["conv0_weight"].reshape(2, heads + kv_heads, d)
                y0 = w0[0] * _prev(c) + w0[1] * c + a["conv0_bias"].reshape(heads + kv_heads, d)
                w1 = a["conv1_weight"]                                             # (2, H + Hkv, D, D)
                y1 = (jnp.einsum("bsnc,ncd->bsnd", _prev(y0), w1[0])
                      + jnp.einsum("bsnc,ncd->bsnd", y0, w1[1]) + a["conv1_bias"])
            q = y1[:, :, :heads] + m_q
            k = y1[:, :, heads:] + m_k
            q = q * jnp.sqrt(jnp.float32(d)) / jnp.linalg.norm(q, axis=-1, keepdims=True)
            k = k * jnp.sqrt(jnp.float32(d)) / jnp.linalg.norm(k, axis=-1, keepdims=True)
            k = k * a["temperature"][:, None]
            q = common.rope_half_split(q, pos, rot, theta)
            k = common.rope_half_split(k, pos, rot, theta)
            if kv_dtype is not None:       # what a cache would hold
                k, v = (t.astype(kv_dtype).astype(jnp.float32) for t in (k, v))
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
            cols = jnp.arange(s)

            def rows(qb, lo):
                t = lo + jnp.arange(qb.shape[1])
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(d))
                att = jnp.where((t[:, None] >= cols[None])[None, None], att, -jnp.inf)
                return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)

            out = _blocks(rows, (q,), s, QUERY_BLOCK).reshape(b, s, heads * d)
            return act(out) @ a["o_proj"]["kernel"]

        def merge(m, x, branch):
            if not residual_scaling:
                return x + branch
            return ((x + m["residual_bias"]) * m["residual_scale"]
                    + (branch + m["branch_bias"]) * m["branch_scale"])

        def route(router, g, r):
            """``(p (B, S, E), biased, new state)`` for normed inputs ``g``."""
            s = g @ router["down_weight"] + router["down_bias"]
            if r is not None and eda:
                s = s + router["state_mix"] * r
            y = _rms_norm(s, router["norm_weight"], eps)
            y = jax.nn.gelu(y @ router["fc1_weight"] + router["fc1_bias"], approximate=False)
            y = jax.nn.gelu(y @ router["fc2_weight"] + router["fc2_bias"], approximate=False)
            p = jax.nn.softmax(y @ router["fc3_weight"], axis=-1)
            return p, p + router["e_score_correction_bias"], s

        def experts(ex, g, weights):
            """``sum_e weights[..., e] * Expert_e(g)``, one expert's float32 copy at a time."""

            def one_expert(acc, e):
                gate_w, up, down = f32(e[:3])
                return acc + (act(jax.nn.silu(g @ gate_w) * (g @ up)) @ down) * e[3][..., None], None

            out, _ = jax.lax.scan(one_expert, jnp.zeros_like(g), (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return out

        def block(first, layer, x, r):
            ex = layer["moe"]["experts"]
            layer = f32({k: v for k, v in layer.items() if k != "moe"} | {"moe": {"router": layer["moe"]["router"]}})
            x = merge(layer["attn_merge"], x, attention(layer["attn"], _rms_norm(x, layer["input_norm"]["weight"], eps)))
            g = act(_rms_norm(x, layer["pre_moe_norm"]["weight"], eps))
            p, biased, r = route(layer["moe"]["router"], g, None if first else r)
            ranked, order = jax.lax.top_k(biased, 2)
            top = order[..., 0]
            w = ranked[..., 0] if bias_in_weights else jnp.take_along_axis(p, top[..., None], axis=-1)[..., 0]
            weights = jax.nn.one_hot(top, p.shape[-1]) * w[..., None]
            x = merge(layer["moe_merge"], x, experts(ex, g, weights))
            return x, r, ranked[..., 0] - ranked[..., 1], top

        def attention_part(layer, x):
            layer = f32({k: v for k, v in layer.items() if k != "moe"})
            return attention(layer["attn"], _rms_norm(x, layer["input_norm"]["weight"], eps))

        def table_rows(table):
            """``fn(rows)``: rows of the embedding table in float32, through
            ``dtype`` at the WHOLE table's scale; the table itself is never
            copied (262,272 x 2048 in float32 are 2.1 GB)."""
            if dtype is None:
                return lambda rows: jnp.asarray(rows, jnp.float32)
            scale = jnp.maximum(jnp.abs(table).max().astype(jnp.float32), 1e-30) / float(jnp.finfo(dtype).max)
            return lambda rows: (jnp.asarray(rows, jnp.float32) / scale).astype(dtype).astype(jnp.float32) * scale

        def embed(table, ids):
            return table_rows(table)(table[ids])

        def head(norm, table, x):
            """Over the vocabulary in up to 8 blocks, one block's float32 copy at a time."""
            rows = table_rows(table)
            g = act(_rms_norm(x, f32(norm["weight"]), eps))
            v = table.shape[0]
            n = max(n for n in range(1, 9) if v % n == 0)
            out = jax.lax.map(lambda blk: g @ rows(blk).T, table.reshape(n, v // n, -1))    # (n, B, S, V / n)
            return jnp.moveaxis(out, 0, 2).reshape(g.shape[:2] + (v,))

        self._first = common.highest(lambda layer, x: block(True, layer, x, None))
        self._later = common.highest(lambda layer, x, r: block(False, layer, x, r))
        self._attention_part = common.highest(attention_part)
        self._embed = common.highest(embed)
        self._head = common.highest(head)

    def block(self, layer: int, x, r=None):
        """``(stream, router state, router margin, chosen expert)`` after layer
        ``layer`` alone, for a comparison that the layers after it cannot blur
        (``chip_smoke.py``); ``r=None``: the first layer (no state comes in)."""
        weights = self.p["model"][f"layers_{layer}"]
        x = jnp.asarray(x, jnp.float32)
        return self._first(weights, x) if r is None else self._later(weights, x, jnp.asarray(r, jnp.float32))

    def attention_part(self, layer: int, x):
        """What layer ``layer``'s attention hands its residual merge: ``Wo a``."""
        return self._attention_part(self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def embed(self, ids):
        return self._embed(self.p["model"]["embed"]["embedding"], jnp.asarray(ids))

    def _hidden(self, ids):
        x, r = self.embed(ids), None
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for i in range(self.layers):
            x, r, m, _ = self.block(i, x, r)
            margin = jnp.minimum(margin, m)
        return x, margin

    def _logits(self, x):
        """The head in blocks of positions, into a host array."""
        b, s, _ = x.shape
        out = np.empty((b, s, int(self.cfg["vocab_size"])), np.float32)
        model = self.p["model"]
        for lo in range(0, s, HEAD_BLOCK):
            out[:, lo:lo + HEAD_BLOCK] = np.asarray(self._head(
                model["final_norm"], model["embed"]["embedding"], x[:, lo:lo + HEAD_BLOCK]))
        return out

    def logits_and_router_margin(self, ids):
        """``(logits (B, S, V) on the host, the router's margin (B, S))``."""
        x, margin = self._hidden(ids)
        return self._logits(x), np.asarray(margin)

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]
