"""Plain reference of GLM-5's language model (``glm_moe_dsa``): multi-head
latent attention with a query latent, DeepSeek-V3.2's lightning indexer fed
from that latent and selecting among the keys, sigmoid routing with a
selection bias, a shared expert; after the catalog's ``config`` of
``zai-org/GLM-5``. Float32 ``jax.numpy`` at matmul precision ``highest``; no
kernel, no cache, no absorbed form, no batching; fed the system's weights a
layer at a time. It is GIVEN THE SAME SHARE as the system: the routed experts
``[first_held_expert, first_held_expert + n_routed_experts)`` of
``n_routed_experts_published`` router outputs, and the vocabulary's slice.

Per block, ``h`` a token's hidden vector, ``H`` heads, position ``t``, causal,
no biases, RMSNorm eps ``rms_norm_eps``:

* ``x += attn(norm1(x))``; ``x += ffn(norm2(x))``; a final RMSNorm and an
  untied output head.
* ``c_q = RMSNorm(W_q_a h)``; ``q = W_q_b c_q`` -> ``H x (d_nope + d_rope)``,
  rotary on the last ``d_rope``. ``W_kv_a h`` -> ``d_c + d_rope``: ``c =
  RMSNorm(first d_c)``, ``k_pe = rope(last d_rope)``, one per token for all
  heads. ``W_kv_b c`` -> ``H x (d_nope + d_v)``: ``k_nope``, ``v``. Plain
  rotary at ``rope_parameters.rope_theta`` (no YaRN, no ``mscale``).
* indexer: ``qI = W_qI c_q`` (H_i x d_i) and ``kI = LayerNorm(W_kI h)`` (d_i,
  eps 1e-6, scale and bias), rotary on the first ``d_rope`` channels of each;
  ``w = W_w h`` (H_i); ``I[t, s] = H_i^-0.5 d_i^-0.5 sum_j w[t, j] relu(qI[t,
  j] . kI[s])``, ``s <= t`` (the published constants kept; the system leaves
  them out).
* selection: the ``min(t + 1, index_topk)`` positions of largest ``I[t, s]``,
  ties to the lower position (``jax.lax.top_k``); one set for all heads.
* attention, MATERIALISED form only: softmax over the selected positions of
  ``(q_nope . k_nope + q_pe . k_pe) * (d_nope + d_rope)^-0.5``, times ``v``;
  ``W_o``. The system decodes in the absorbed form over selected rows of a
  latent cache; this file never does.
* FFN: the first ``dense_layers_run`` layers (the published
  ``first_k_dense_replace`` where the file names no cut) a SwiGLU MLP. The
  others: ``s = sigmoid(W_g h)``; the ``k`` experts of largest ``s + b``;
  weights ``s_i / (sum of the chosen s + 1e-20) * routed_scaling_factor``,
  without ``b``; ``sum over the HELD chosen experts of w_i expert_i(h) +
  shared(h)``. What the absent experts would have added is left out, as in
  the system.

The router's margin, per position the narrowest over the sparse layers: the
``k``-th largest ``s + b`` less the next, COUNTED ONLY WHERE ONE OF THE TWO IS
AN EXPERT HELD HERE (infinite elsewhere): a flip between two absent experts
changes no expert computed on this chip (it moves the normaliser of the
weights by ``s_9 - s_8``, at most a few hundredths of a sum of about 4).

Departures: rotary pairing (channel ``i`` with ``i + d/2``, as the system:
``common.rope_half_split``); V3.2's Hadamard rotation and fp8 index keys left
out; multi-token prediction not built (the main model's logits do not depend
on it). Controls, for showing that a comparison against this file can fail:
``topk`` (keep that many columns), ``index_dtype`` / ``latent_dtype`` (round
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

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _blocks(fn, arrays, s, block):
    """``fn`` over blocks of query rows (axis 1), one at a time."""
    block = min(block, s)
    n = -(-s // block)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n * block - s)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    split = lambda a: jnp.moveaxis(pad(a).reshape((a.shape[0], n, block) + a.shape[2:]), 1, 0)  # noqa: E731
    outs = jax.lax.map(lambda xs: fn(*xs), tuple(split(a) for a in arrays) + (jnp.arange(n) * block,))
    join = lambda o: jnp.moveaxis(o, 0, 1).reshape((o.shape[1], n * block) + o.shape[3:])[:, :s]  # noqa: E731
    return jax.tree.map(join, outs)


def _rounded(a, dtype):
    """``a`` in float32 after a round trip through ``dtype`` at one scale a
    tensor (its largest magnitude on the dtype's largest)."""
    a = jnp.asarray(a, jnp.float32)
    scale = jnp.maximum(jnp.abs(a).max(), 1e-30) / float(jnp.finfo(dtype).max)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


def held_experts(cfg: dict):
    """``(router outputs, first held, held)`` of a configuration's ``model``
    group: the published file names all its experts; a share names the
    published count and the first expert held under keys of its own."""
    held = int(cfg["n_routed_experts"])
    return int(cfg.get("n_routed_experts_published", held)), int(cfg.get("first_held_expert", 0)), held


class Reference:
    def __init__(self, cfg: dict, params, index_dtype=None, topk=None, latent_dtype=None,
                 bias_in_weights: bool = False, dtype=None):
        self.cfg = cfg
        if dtype is not None:
            index_dtype, latent_dtype = index_dtype or dtype, latent_dtype or dtype

        def f32(tree):
            """The stored weights in float32, through ``dtype`` where it is given."""
            return common.f32(tree) if dtype is None else jax.tree.map(lambda a: _rounded(a, dtype), tree)

        def act(x):
            """A projection's input, through ``dtype`` where it is given."""
            return x if dtype is None else _rounded(x, dtype)

        self.p = params["params"]
        heads = int(cfg["num_attention_heads"])
        d_c, d_n = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"])
        d_r, d_v = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
        h_i, d_i = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
        keep = int(topk if topk is not None else cfg["index_topk"])
        top_k = int(cfg["num_experts_per_tok"])
        _, first, held = held_experts(cfg)
        eps = float(cfg["rms_norm_eps"])
        theta = float(cfg["rope_parameters"]["rope_theta"])
        routed_scale = float(cfg.get("routed_scaling_factor", 1.0))
        renorm = bool(cfg.get("norm_topk_prob", True))
        softmax_scale = (d_n + d_r) ** -0.5
        index_scale = h_i ** -0.5 * d_i ** -0.5
        self.dense_layers = int(cfg.get("dense_layers_run", cfg["first_k_dense_replace"]))

        def rope(x, pos):
            """The first ``d_rope`` channels of each head of x (B, S, H, d)."""
            return common.rope_half_split(x, pos, d_r, theta)

        def latent(a, h, pos):
            """What a latent cache would hold of normed inputs ``h``: ``(c (B,
            S, d_c), k_pe (B, S, 1, d_r))``, rounded to ``latent_dtype``."""
            kv_a = h @ a["kv_a_proj"]["kernel"]
            c = _rms_norm(kv_a[..., :d_c], a["kv_a_norm"]["weight"], eps)
            k_pe = rope(kv_a[..., d_c:][:, :, None, :], pos)
            if latent_dtype is not None:
                c, k_pe = (t.astype(latent_dtype).astype(jnp.float32) for t in (c, k_pe))
            return c, k_pe

        def attention(layer, x, want_sets):
            b, s, _ = x.shape
            a = layer["attn"]
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            h = act(_rms_norm(x, layer["input_norm"]["weight"], eps))
            c_q = act(_rms_norm(h @ a["q_a_proj"]["kernel"], a["q_a_norm"]["weight"], eps))
            q = (c_q @ a["q_b_proj"]["kernel"]).reshape(b, s, heads, d_n + d_r)
            q = jnp.concatenate([q[..., :d_n], rope(q[..., d_n:], pos)], -1)
            c, k_pe = latent(a, h, pos)
            kv = (c @ a["kv_b_proj"].reshape(d_c, heads * (d_n + d_v))).reshape(b, s, heads, d_n + d_v)
            k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(k_pe, (b, s, heads, d_r))], -1)
            v = kv[..., d_n:]
            q_i = rope((c_q @ a["idx_q_proj"]["kernel"]).reshape(b, s, h_i, d_i), pos)
            k_i = rope(_layer_norm(h @ a["idx_k_proj"]["kernel"], a["idx_k_norm"]["scale"],
                                   a["idx_k_norm"]["bias"], 1e-6)[:, :, None, :], pos)[:, :, 0]
            if index_dtype is not None:
                k_i = k_i.astype(index_dtype).astype(jnp.float32)
            w_i = h @ a["idx_w_proj"]["kernel"]
            cols = jnp.arange(s)
            n_keep = min(keep, s)

            def rows(qb, qib, wib, lo):
                t = lo + jnp.arange(qb.shape[1])
                causal = t[:, None] >= cols[None]                           # (Q, S)
                score = jnp.einsum("bqhd,bkd->bhqk", qib, k_i)
                score = jnp.einsum("bhqk,bqh->bqk", jax.nn.relu(score), wib) * index_scale
                score = jnp.where(score == 0, 0.0, score)                   # -0.0 is 0.0
                score = jnp.where(causal[None], score, -jnp.inf)
                vals, picked = jax.lax.top_k(score, n_keep)
                bi, qi = jnp.meshgrid(jnp.arange(b), jnp.arange(qb.shape[1]), indexing="ij")
                sel = jnp.zeros(score.shape, bool).at[bi[..., None], qi[..., None], picked].set(
                    vals > -jnp.inf)
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * softmax_scale
                att = jnp.where(sel[:, None], att, -jnp.inf)
                out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)
                return out, sel if want_sets else sel[:, :, :0]

            out, sel = _blocks(rows, (q, q_i, w_i), s, QUERY_BLOCK)
            return x + act(out.reshape(b, s, heads * d_v)) @ a["o_proj"]["kernel"], sel

        def swiglu(w, h):
            return act(jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"])) @ w["down"]["kernel"]

        def dense_block(want_sets, layer, x):
            layer = f32(layer)
            x, sel = attention(layer, x, want_sets)
            h = act(_rms_norm(x, layer["post_attn_norm"]["weight"], eps))
            return x + swiglu(layer["mlp"], h), jnp.full(x.shape[:2], jnp.inf, jnp.float32), sel

        def routed(moe, ex, h):
            """``(the held experts' part of the routed sum, the router's margin)``
            for normed inputs ``h``: ``moe`` the layer's float32 router (and
            shared expert), ``ex`` its experts as stored, upcast one at a time
            (8 x 3 x 6144 x 2048 in float32 is 1.2 GB a layer)."""
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
            weights = (jax.nn.one_hot(top_e - first, held) * (top_w * routed_scale)[..., None]).sum(-2)

            def one_expert(acc, e):     # a scan: one expert's float32 copy and output at a time
                gate, up, down = f32(e[:3])
                w = e[3]
                return acc + (act(jax.nn.silu(h @ gate) * (h @ up)) @ down) * w[..., None], None

            out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return out, margin

        def split(layer):
            """``(the layer in float32 but for its experts, the experts as stored)``."""
            ex = layer["moe"]["experts"]
            return f32({k: v for k, v in layer.items() if k != "moe"}
                              | {"moe": {k: v for k, v in layer["moe"].items() if k != "experts"}}), ex

        def sparse_block(want_sets, layer, x):
            layer, ex = split(layer)
            x, sel = attention(layer, x, want_sets)
            h = act(_rms_norm(x, layer["post_attn_norm"]["weight"], eps))
            out, margin = routed(layer["moe"], ex, h)
            if "shared" in layer["moe"]:
                out = out + swiglu(layer["moe"]["shared"], h)
            return x + out, margin, sel

        def attention_part(layer, x):
            layer = f32({k: v for k, v in layer.items() if k not in ("moe", "mlp")})
            return attention(layer, x, False)[0] - x

        def latent_part(layer, x):
            layer = f32({k: v for k, v in layer.items() if k not in ("moe", "mlp")})
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
            c, k_pe = latent(layer["attn"], _rms_norm(x, layer["input_norm"]["weight"], eps), pos)
            return jnp.concatenate([c, k_pe[:, :, 0]], axis=-1)

        def routed_part(layer, h):
            layer, ex = split(layer)
            return routed(layer["moe"], ex, jnp.asarray(h, jnp.float32))[0]

        def embed(table, ids):
            return f32(table)[ids]

        def head(norm, lm, x):
            return act(_rms_norm(x, f32(norm["weight"]), eps)) @ f32(lm["kernel"])

        self._dense = {w: common.highest(functools.partial(dense_block, w)) for w in (False, True)}
        self._sparse = {w: common.highest(functools.partial(sparse_block, w)) for w in (False, True)}
        self._embed = common.highest(embed)
        self._head = common.highest(head)
        self._attention_part = common.highest(attention_part)
        self._routed_part = common.highest(routed_part)
        self._latent_part = common.highest(latent_part)

    def _hidden(self, ids, keep_sets=False):
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        sets = []
        for i in range(int(self.cfg["num_hidden_layers"])):
            block = (self._dense if i < self.dense_layers else self._sparse)[keep_sets]
            x, m, sel = block(model[f"layers_{i}"], x)
            margin = jnp.minimum(margin, m)
            if keep_sets:
                sets.append(np.asarray(sel))
        return x, margin, sets

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
        x, margin, _ = self._hidden(ids)
        return self._logits(x), np.asarray(margin)

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]

    def attention_part(self, layer: int, x):
        """What layer ``layer``'s attention block ADDS to the stream ``x`` (B,
        S, hidden): one block alone, for a comparison that the layers after
        it cannot blur (``chip_smoke.py``)."""
        return self._attention_part(self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def latent_part(self, layer: int, x):
        """What layer ``layer``'s cache holds of the stream ``x``: the latent
        and the rotated key of every token, (B, S, d_c + d_r), rounded as
        ``latent_dtype`` says."""
        return self._latent_part(self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def routed_part(self, layer: int, h):
        """The held experts' part of sparse layer ``layer``'s routed sum for
        normed inputs ``h`` (B, S, hidden), without the shared expert."""
        return self._routed_part(self.p["model"][f"layers_{layer}"], h)

    def embed(self, ids):
        return self._embed(self.p["model"]["embed"]["embedding"], jnp.asarray(ids))

    def selected(self, ids):
        """Per layer, the boolean (B, S, S) mask of the keys each query row
        keeps (small sizes: tests and ``chip_smoke.py``)."""
        return self._hidden(ids, keep_sets=True)[2]
