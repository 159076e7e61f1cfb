"""Plain reference of Arcee's Trinity language models (``afmoe``): window and
full attention layers in one stack, gated grouped-query attention with rotary
on the window layers only, four norms a block, sigmoid routing under a
selection bias with a shared expert; after the catalog's ``config`` of
``arcee-ai/Trinity-Large-Preview``. Float32 ``jax.numpy`` at matmul precision
``highest``; no kernel, no cache, the mask built from indices, no batching;
fed the system's weights a layer at a time, attention computed in blocks of
queries so that 16,384 tokens fit. It is GIVEN THE SAME SHARE as the system:
the routed experts ``[first_held_expert, first_held_expert + num_experts)`` of
``num_experts_published`` router outputs, the vocabulary's slice, and the
layers ``layers_run`` of the published ``layer_types``.

Per block, ``h`` hidden, ``H`` query and ``Hkv`` key/value heads of ``D``,
RMSNorm eps ``rms_norm_eps`` with a learned scale everywhere, no biases:

* ``x0 = E[ids] * sqrt(h)`` (``mup_enabled``).
* ``a = Attn(N1(x))``; ``x = x + N2(a)``; ``m = F(N3(x))``; ``x = x + N4(m)``.
* ``Attn(u)``: ``q = Wq u`` (H x D), ``k = Wk u``, ``v = Wv u`` (Hkv x D),
  ``g = Wg u`` (H D); ``q, k`` through an RMSNorm over each head's D channels;
  a ``sliding_attention`` layer: rotary over all D channels at
  ``rope_theta`` and key ``j`` visible to query ``i`` iff ``i - sliding_window
  < j <= i``; a ``full_attention`` layer: NO rotary, ``j <= i``. Scores ``/
  sqrt(D)``, softmax, query head ``n`` reads kv head ``n // (H / Hkv)``.
  ``Attn = Wo (o * sigmoid(g))``.
* ``F``: the first ``dense_layers_run`` layers run (the published
  ``num_dense_layers`` where the file names no cut) a SwiGLU MLP. The others:
  ``s = sigmoid(Wr u)``; the ``k`` experts of largest ``s + b``; weights ``s_i
  / (sum of the chosen s + 1e-20) * route_scale``, without ``b``; ``sum over
  the HELD chosen experts of w_i expert_i(u) + shared(u)``. What the absent
  experts would have added is left out, as in the system.
* a final RMSNorm and an untied head.

The router's margin, per position the narrowest over the sparse layers: the
``k``-th largest ``s + b`` less the next, COUNTED ONLY WHERE ONE OF THE TWO IS
AN EXPERT HELD HERE (infinite elsewhere), as ``references/glm_moe_dsa.py``.

Departures: rotary pairing (channel ``i`` with ``i + D/2``, as the system:
``common.rope_half_split``). Controls, for showing that a comparison against
this file can fail: ``window`` (another window on the window layers;
``"none"``: none at all), ``rope_full`` (rotary wrongly applied on the full
layers too), ``gate`` False (the sigmoid gate left out), ``kv_dtype`` (round
what a cache would hold), ``bias_in_weights`` (the selection bias wrongly
added to the weights too), ``dtype`` (the WHOLE reference in a lower
precision: every weight, every cached value and every projection's input
rounded through it, one scale a tensor; sums still in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import common
from perfbench.references.glm_moe_dsa import _blocks, _rms_norm, _rounded

QUERY_BLOCK = 256
HEAD_BLOCK = 1024
SLIDING = "sliding_attention"


def held_experts(cfg: dict):
    """``(router outputs, first held, held)`` of a configuration's ``model``
    group: the published file names all its experts; a share names the
    published count and the first expert held under keys of its own."""
    held = int(cfg["num_experts"])
    return int(cfg.get("num_experts_published", held)), int(cfg.get("first_held_expert", 0)), held


def layers_run(cfg: dict):
    """``(the kind of every layer that is run, how many of them are dense)``:
    the published ``layer_types`` at ``layers_run`` (every layer where the file
    names no cut), the first ``dense_layers_run`` of them dense."""
    kinds = list(cfg["layer_types"])
    run = [int(i) for i in cfg.get("layers_run", range(int(cfg["num_hidden_layers"])))]
    if len(run) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"layers_run names {len(run)} layers, num_hidden_layers {cfg['num_hidden_layers']}")
    return [kinds[i] for i in run], int(cfg.get("dense_layers_run", cfg["num_dense_layers"]))


class Reference:
    def __init__(self, cfg: dict, params, window=None, rope_full: bool = False, gate: bool = True,
                 kv_dtype=None, bias_in_weights: bool = False, dtype=None):
        self.cfg = cfg
        if dtype is not None:
            kv_dtype = kv_dtype or dtype

        def f32(tree):
            """The stored weights in float32, through ``dtype`` where it is given."""
            return common.f32(tree) if dtype is None else jax.tree.map(lambda a: _rounded(a, dtype), tree)

        def act(x):
            """A projection's input, through ``dtype`` where it is given."""
            return x if dtype is None else _rounded(x, dtype)

        self.p = params["params"]
        heads, kv_heads, d = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
        hidden = int(cfg["hidden_size"])
        top_k = int(cfg["num_experts_per_tok"])
        _, first, held = held_experts(cfg)
        eps = float(cfg["rms_norm_eps"])
        theta = float(cfg["rope_theta"])
        route_scale = float(cfg.get("route_scale", 1.0))
        renorm = bool(cfg.get("route_norm", True))
        published = int(cfg["sliding_window"])
        self.kinds, self.dense_layers = layers_run(cfg)
        self.embed_scale = hidden ** 0.5 if cfg.get("mup_enabled", False) else 1.0

        def width(kind):
            """The window of a layer of ``kind``; ``None``: every key before the query."""
            if kind != SLIDING or window == "none":
                return None
            return published if window is None else int(window)

        def cached(a, pos, rotary):
            """What a cache would hold of normed inputs' projections: ``(k, v)``
            (B, S, Hkv, D), rounded to ``kv_dtype``."""
            k, v = a
            if rotary:
                k = common.rope_half_split(k, pos, d, theta)
            if kv_dtype is not None:
                k, v = (t.astype(kv_dtype).astype(jnp.float32) for t in (k, v))
            return k, v

        def attention(kind, layer, x):
            """What the attention block ADDS to the stream ``x``: ``N2(Attn(N1(x)))``."""
            b, s, _ = x.shape
            a = layer["attn"]
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            u = act(_rms_norm(x, layer["input_norm"]["weight"], eps))
            q = _rms_norm((u @ a["qkv"]["q_proj"]["kernel"]).reshape(b, s, heads, d), a["q_norm"]["weight"], eps)
            k = _rms_norm((u @ a["qkv"]["k_proj"]["kernel"]).reshape(b, s, kv_heads, d), a["k_norm"]["weight"], eps)
            v = (u @ a["qkv"]["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            rotary = kind == SLIDING or rope_full
            if rotary:
                q = common.rope_half_split(q, pos, d, theta)
            k, v = cached((k, v), pos, rotary)
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            cols = jnp.arange(s)
            w = width(kind)

            def rows(qb, lo):
                t = lo + jnp.arange(qb.shape[1])
                keep = t[:, None] >= cols[None]                              # (Q, S)
                if w is not None:
                    keep = keep & (cols[None] > t[:, None] - w)
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(d))
                att = jnp.where(keep[None, None], att, -jnp.inf)
                return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)

            out = _blocks(rows, (q,), s, QUERY_BLOCK).reshape(b, s, heads * d)
            if gate:
                out = out * jax.nn.sigmoid(u @ a["gate_proj"]["kernel"])
            return _rms_norm(act(out) @ a["o_proj"]["kernel"], layer["post_attn_norm"]["weight"], eps)

        def swiglu(w, h):
            return act(jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"])) @ w["down"]["kernel"]

        def dense_block(kind, layer, x):
            layer = f32(layer)
            x = x + attention(kind, layer, x)
            h = act(_rms_norm(x, layer["pre_mlp_norm"]["weight"], eps))
            out = _rms_norm(swiglu(layer["mlp"], h), layer["post_mlp_norm"]["weight"], eps)
            return x + out, jnp.full(x.shape[:2], jnp.inf, jnp.float32)

        def routed(moe, ex, h):
            """``(the held experts' part of the routed sum, the router's margin)``
            for normed inputs ``h``: ``moe`` the layer's float32 router (and
            shared expert), ``ex`` its experts as stored, upcast one at a
            time."""
            router = moe["router"]
            s_all = jax.nn.sigmoid(h @ router["weight"])                       # (B, S, n_out)
            biased = s_all + router["e_score_correction_bias"]
            ranked, order = jax.lax.top_k(biased, top_k + 1)
            here = (order >= first) & (order < first + held)
            margin = jnp.where(here[..., top_k - 1] | here[..., top_k],
                               ranked[..., top_k - 1] - ranked[..., top_k], jnp.inf)
            top_e = order[..., :top_k]
            chosen = jnp.take_along_axis(s_all, top_e, axis=-1)
            if bias_in_weights:     # the control: what a program that weighs with b computes
                chosen = ranked[..., :top_k]
            top_w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) if renorm else chosen
            weights = (jax.nn.one_hot(top_e - first, held) * (top_w * route_scale)[..., None]).sum(-2)

            def one_expert(acc, e):     # a scan: one expert's float32 copy and output at a time
                gate_w, up, down = f32(e[:3])
                w = e[3]
                return acc + (act(jax.nn.silu(h @ gate_w) * (h @ up)) @ down) * w[..., None], None

            out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return out, margin

        def split(layer):
            """``(the layer in float32 but for its experts, the experts as stored)``."""
            ex = layer["moe"]["experts"]
            return f32({k: v for k, v in layer.items() if k != "moe"}
                       | {"moe": {k: v for k, v in layer["moe"].items() if k != "experts"}}), ex

        def sparse_block(kind, layer, x):
            layer, ex = split(layer)
            x = x + attention(kind, layer, x)
            h = act(_rms_norm(x, layer["pre_mlp_norm"]["weight"], eps))
            out, margin = routed(layer["moe"], ex, h)
            if "shared" in layer["moe"]:
                out = out + swiglu(layer["moe"]["shared"], h)
            return x + _rms_norm(out, layer["post_mlp_norm"]["weight"], eps), margin

        def attention_part(kind, layer, x):
            layer = f32({k: v for k, v in layer.items() if k not in ("moe", "mlp")})
            return attention(kind, layer, x)

        def cache_part(kind, layer, x):
            layer = f32({k: v for k, v in layer.items() if k not in ("moe", "mlp")})
            b, s, _ = x.shape
            a = layer["attn"]
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            u = _rms_norm(x, layer["input_norm"]["weight"], eps)
            k = _rms_norm((u @ a["qkv"]["k_proj"]["kernel"]).reshape(b, s, kv_heads, d), a["k_norm"]["weight"], eps)
            v = (u @ a["qkv"]["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            return jnp.concatenate(cached((k, v), pos, kind == SLIDING or rope_full), axis=2)

        def routed_part(layer, h):
            layer, ex = split(layer)
            return routed(layer["moe"], ex, jnp.asarray(h, jnp.float32))[0]

        def embed(table, ids):
            return f32(table)[ids] * self.embed_scale

        def head(norm, lm, x):
            return act(_rms_norm(x, f32(norm["weight"]), eps)) @ f32(lm["kernel"])

        kinds = sorted(set(self.kinds))
        self._dense = {k: common.highest(functools.partial(dense_block, k)) for k in kinds}
        self._sparse = {k: common.highest(functools.partial(sparse_block, k)) for k in kinds}
        self._attention_part = {k: common.highest(functools.partial(attention_part, k)) for k in kinds}
        self._cache_part = {k: common.highest(functools.partial(cache_part, k)) for k in kinds}
        self._embed = common.highest(embed)
        self._head = common.highest(head)
        self._routed_part = common.highest(routed_part)

    def _hidden(self, ids):
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for i, kind in enumerate(self.kinds):
            block = (self._dense if i < self.dense_layers else self._sparse)[kind]
            x, m = block(model[f"layers_{i}"], x)
            margin = jnp.minimum(margin, m)
        return x, margin

    def _logits(self, x):
        """The head in blocks of positions, into a host array."""
        b, s, _ = x.shape
        out = np.empty((b, s, int(self.cfg["vocab_size"])), np.float32)
        for lo in range(0, s, HEAD_BLOCK):
            out[:, lo:lo + HEAD_BLOCK] = np.asarray(self._head(
                self.p["model"]["final_norm"], self.p["lm_head"], x[:, lo:lo + HEAD_BLOCK]))
        return out

    def logits_and_router_margin(self, ids):
        """``(logits (B, S, V) on the host, the router's margin (B, S))``, the
        margin as the module docstring defines it."""
        x, margin = self._hidden(ids)
        return self._logits(x), np.asarray(margin)

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]

    def attention_part(self, layer: int, x):
        """What layer ``layer``'s attention block ADDS to the stream ``x`` (B,
        S, hidden), its post-attention norm included: one block alone, for a
        comparison that the layers after it cannot blur (``chip_smoke.py``)."""
        return self._attention_part[self.kinds[layer]](
            self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def cache_part(self, layer: int, x):
        """What layer ``layer``'s cache holds of the stream ``x``: every token's
        K heads then its V heads, (B, S, 2 Hkv, D), rounded as ``kv_dtype``
        says."""
        return self._cache_part[self.kinds[layer]](
            self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def routed_part(self, layer: int, h):
        """The held experts' part of sparse layer ``layer``'s routed sum for
        normed inputs ``h`` (B, S, hidden), without the shared expert."""
        return self._routed_part(self.p["model"][f"layers_{layer}"], h)

    def embed(self, ids):
        return self._embed(self.p["model"]["embed"]["embedding"], jnp.asarray(ids))
