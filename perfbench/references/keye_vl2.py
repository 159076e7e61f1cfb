"""Plain reference of Keye-VL-2.0's language model: grouped-query attention
under a learned sparse-attention indexer, 128 experts top-8, after the
catalog's ``config`` of ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (``sa_config``
included) and DeepSeek-V3.2's published lightning indexer at this config's
sizes. Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no
cache, no batching; fed the system's weights a layer at a time.

Per block, ``x = RMSNorm(h)``, position ``t``, causal, no biases:

* ``h += attn(norm1(h))``; ``h += moe(norm2(h))``; a final RMSNorm and an
  untied output head.
* main heads: ``q = W_q x`` (H x D), ``k = W_k x``, ``v = W_v x`` (Hkv x D);
  RMSNorm over each head's D channels on q and on k; rotary over all D
  channels at ``rope_theta``, frequency pair ``i`` of D/2 taking the temporal,
  height or width position by ``mrope_section`` ([16, 24, 24]).
* indexer: ``qI = W_qI x`` (H_i x d_i), ``kI = LayerNorm(W_kI x)`` (one key of
  d_i a token), ``w = W_w x`` (H_i); rotary on both over all d_i channels;
  ``I[t, s] = H_i^-0.5 d_i^-0.5 sum_j w[t, j] relu(qI[t, j] . kI[s])``, ``s <=
  t`` (the published constants kept).
* selection: the ``min(t + 1, topk)`` positions of largest ``I[t, s]``, ties
  to the lower position (``jax.lax.top_k``); one set for all heads.
* attention: softmax over the selected positions only of ``q_h . k_g(h) /
  sqrt(D)``, times ``v``; ``W_o``.
* experts: ``softmax(W_r x)`` over all experts, top-k, renormalised over the k
  (``norm_topk_prob``), SwiGLU, no shared expert. Experts densely (every
  expert on every token, masked by the routing weights), upcast one at a time
  (a ``scan``: 128 outputs of a long context side by side do not fit).

Attention and index scores run a block of ``QUERY_BLOCK`` query rows at a
time against all keys (``lax.map``), so that a 6k prompt fits beside the
engine; the head is applied in blocks of positions into a HOST array (a
position's float32 logits are 0.61 MB at vocab 151,936).

Assumptions (the config has no key for them) and departures:

* the q/k head RMSNorm: assumed, from the Qwen3-MoE block whose every number
  this config repeats;
* the index key's LayerNorm (eps 1e-6, scale and bias) and the index
  queries' input: V3.2 feeds them from the query latent; no query compression
  exists here, so ``qI`` comes from ``x``: a departure;
* the indexer's rotary extent and stream: all d_i channels with the temporal
  stream (32 pairs cannot carry sections that sum to 64); V3.2 rotates part;
* V3.2's Hadamard rotation before its fp8 index keys is orthogonal and is
  left out with the fp8 (``index_dtype`` rounds what a cache would hold, for
  showing that the comparison can fail);
* rotary pairing: channel ``i`` with ``i + d/2`` directly, as the system
  (``common.rope_half_split`` notes the same for CodeGen);
* the vision tower is not modelled; ``positions`` may carry three unequal
  streams all the same.

Two choices here are step functions, the router's top-k and the indexer's,
and the reference reports per position how narrow its own was, the narrowest
over the layers. The router's: last kept logit less first dropped. The
indexer's: the ``topk``-th score less the next, over the root mean square of
that row's causal scores; infinite where the row keeps every causal key.

Only the router's excuses a token (``logits_and_router_margin``, what
``common.judge_gaps`` reads). Another EXPERT of eight moves a position's
logits by 0.07-0.4 at these widths; another COLUMN of 2048 does not (float8
index keys swap 1.1% of the columns and move the logits by a median of
0.015), and two adjacent order statistics among thousands of scores are
always close (the index margin's median is 5e-5 of the row's rms at a
5.7k-token context), so folding it in would excuse every position. The index
margin is reported for ``chip_smoke.py``, which asks of each selected column
that differs from the reference's whether it lay that near the threshold.
"""

from __future__ import annotations

import functools
import math

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


def _angles(positions, dim, theta, sections):
    """(B, S, dim/2) from ``positions`` (3, B, S): pair ``i`` reads the
    stream its section names, or the temporal one without sections."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    pos = positions.astype(jnp.float32)
    if sections is None:
        return pos[0][..., None] * inv
    stream = np.repeat(np.arange(3), np.asarray(sections))        # (dim/2,)
    return jnp.moveaxis(pos, 0, -1)[..., stream] * inv


def _rope(x, ang):
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(fn, arrays, s, block):
    """``fn`` over blocks of query rows (axis 1), one at a time."""
    block = min(block, s)
    n = -(-s // block)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n * block - s)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    split = lambda a: jnp.moveaxis(pad(a).reshape((a.shape[0], n, block) + a.shape[2:]), 1, 0)  # noqa: E731
    outs = jax.lax.map(lambda xs: fn(*xs), tuple(split(a) for a in arrays) + (jnp.arange(n) * block,))
    join = lambda o: jnp.moveaxis(o, 0, 1).reshape((o.shape[1], n * block) + o.shape[3:])[:, :s]  # noqa: E731
    return jax.tree.map(join, outs)


class Reference:
    """``index_dtype`` / ``kv_dtype``: round the index keys / the rotated keys
    and the values to that type before they are used (what a cache in it
    would hold); ``topk``: keep that many columns instead of the
    configuration's. All three are for showing that a comparison against the
    plain reference can fail (``chip_smoke.py``, PERF.md)."""

    def __init__(self, cfg: dict, params, index_dtype=None, topk=None, kv_dtype=None):
        self.cfg = cfg
        self.p = params["params"]
        heads, hkv, d = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                         int(cfg["head_dim"]))
        sa = cfg["sa_config"]
        h_i, d_i = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
        keep = int(topk if topk is not None else sa["topk"])
        top_k, n_exp = int(cfg["num_experts_per_tok"]), int(cfg["num_experts"])
        eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
        sections = tuple(int(n) for n in cfg["rope_scaling"]["mrope_section"])
        renorm = bool(cfg.get("norm_topk_prob", True))
        index_scale = h_i ** -0.5 * d_i ** -0.5

        def attention(layer, x, positions, want_sets):
            b, s, _ = x.shape
            a = layer["attn"]
            h = _rms_norm(x, layer["input_norm"]["weight"], eps)
            q = (h @ a["qkv"]["q_proj"]["kernel"]).reshape(b, s, heads, d)
            k = (h @ a["qkv"]["k_proj"]["kernel"]).reshape(b, s, hkv, d)
            v = (h @ a["qkv"]["v_proj"]["kernel"]).reshape(b, s, hkv, d)
            main = _angles(positions, d, theta, sections)
            q = _rope(_rms_norm(q, a["q_norm"]["weight"], eps), main)
            k = _rope(_rms_norm(k, a["k_norm"]["weight"], eps), main)
            idx = _angles(positions, d_i, theta, None)
            q_i = _rope((h @ a["idx_q_proj"]["kernel"]).reshape(b, s, h_i, d_i), idx)
            k_i = _rope(_layer_norm(h @ a["idx_k_proj"]["kernel"], a["idx_k_norm"]["scale"],
                                    a["idx_k_norm"]["bias"], 1e-6)[:, :, None, :], idx)[:, :, 0]
            if index_dtype is not None:
                k_i = k_i.astype(index_dtype).astype(jnp.float32)
            if kv_dtype is not None:
                k, v = (t.astype(kv_dtype).astype(jnp.float32) for t in (k, v))
            w_i = h @ a["idx_w_proj"]["kernel"]
            kk = jnp.repeat(k, heads // hkv, axis=2)
            vv = jnp.repeat(v, heads // hkv, axis=2)
            cols = jnp.arange(s)
            n_keep = min(keep, s)

            def rows(qb, qib, wib, lo):
                t = lo + jnp.arange(qb.shape[1])
                causal = t[:, None] >= cols[None]                           # (Q, S)
                score = jnp.einsum("bqhd,bkd->bhqk", qib, k_i)
                score = jnp.einsum("bhqk,bqh->bqk", jax.nn.relu(score), wib) * index_scale
                score = jnp.where(score == 0, 0.0, score)                   # -0.0 is 0.0
                score = jnp.where(causal[None], score, -jnp.inf)
                vals, picked = jax.lax.top_k(score, min(n_keep + 1, s))
                sel = jnp.zeros(score.shape, bool)
                bi, qi = jnp.meshgrid(jnp.arange(b), jnp.arange(qb.shape[1]), indexing="ij")
                sel = sel.at[bi[..., None], qi[..., None], picked[..., :n_keep]].set(
                    vals[..., :n_keep] > -jnp.inf)
                if n_keep < s:
                    rms = jnp.sqrt((jnp.where(causal[None], score, 0.0) ** 2).sum(-1)
                                   / causal.sum(-1)[None])
                    gap = jnp.where(vals[..., n_keep] > -jnp.inf,
                                    (vals[..., n_keep - 1] - vals[..., n_keep]) / rms, jnp.inf)
                else:
                    gap = jnp.full(score.shape[:2], jnp.inf, jnp.float32)
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, kk) / math.sqrt(d)
                att = jnp.where(sel[:, None], att, -jnp.inf)
                out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), vv)
                return out, gap, (sel, score) if want_sets else (sel[:, :, :0], score[:, :, :0])

            out, gap, sel = _blocks(rows, (q, q_i, w_i), s, QUERY_BLOCK)
            return x + out.reshape(b, s, heads * d) @ a["o_proj"]["kernel"], gap, sel

        def block(want_sets, layer, x, positions):
            ex = layer["moe"]["experts"]
            layer = common.f32({k: v for k, v in layer.items() if k != "moe"}
                               | {"moe": {k: v for k, v in layer["moe"].items() if k != "experts"}})
            x, gap, sel = attention(layer, x, positions, want_sets)
            h = _rms_norm(x, layer["post_attn_norm"]["weight"], eps)
            router_logits = h @ layer["moe"]["router"]["weight"]
            ranked = jax.lax.top_k(router_logits, top_k + 1)[0]
            margin = ranked[..., top_k - 1] - ranked[..., top_k]            # (B, S)
            top_w, top_e = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1), top_k)
            if renorm:
                top_w = top_w / top_w.sum(-1, keepdims=True)
            weights = (jax.nn.one_hot(top_e, n_exp) * top_w[..., None]).sum(-2)

            def one_expert(acc, e):     # one expert's float32 copy and output at a time
                gate, up, down, w = (jnp.asarray(t, jnp.float32) for t in e)
                return acc + ((jax.nn.silu(h @ gate) * (h @ up)) @ down) * w[..., None], None

            out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return x + out, margin, gap, sel

        def embed(table, ids):
            return jnp.asarray(table, jnp.float32)[ids]

        def head(norm, lm, x):
            return _rms_norm(x, jnp.asarray(norm["weight"], jnp.float32), eps) @ jnp.asarray(
                lm["kernel"], jnp.float32)

        self._block = {want: common.highest(functools.partial(block, want)) for want in (False, True)}
        self._embed = common.highest(embed)
        self._head = common.highest(head)

    def _hidden(self, ids, positions=None, keep_sets=False):
        ids = jnp.asarray(ids)
        b, s = ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], ids)
        router = jnp.full((b, s), jnp.inf, jnp.float32)
        index = jnp.full((b, s), jnp.inf, jnp.float32)
        sets = []
        for i in range(int(self.cfg["num_hidden_layers"])):
            x, m, g, sel = self._block[keep_sets](model[f"layers_{i}"], x, jnp.asarray(positions))
            router, index = jnp.minimum(router, m), jnp.minimum(index, g)
            if keep_sets:
                sets.append(tuple(np.asarray(a) for a in sel))
        return x, router, index, sets

    def _logits(self, x):
        """The head in blocks of positions, into a host array."""
        b, s, _ = x.shape
        out = np.empty((b, s, int(self.cfg["vocab_size"])), np.float32)
        for lo in range(0, s, HEAD_BLOCK):
            out[:, lo:lo + HEAD_BLOCK] = np.asarray(self._head(
                self.p["model"]["final_norm"], self.p["lm_head"], x[:, lo:lo + HEAD_BLOCK]))
        return out

    def logits_and_margins(self, ids, positions=None):
        """``(logits, router margin, index margin)``, the margins (B, S) as
        the module docstring defines them."""
        x, router, index, _ = self._hidden(ids, positions)
        return self._logits(x), np.asarray(router), np.asarray(index)

    def logits_and_router_margin(self, ids, positions=None):
        """``(logits (B, S, V) on the host, the router's margin (B, S))``."""
        return self.logits_and_margins(ids, positions)[:2]

    def logits(self, ids, positions=None):
        return self.logits_and_margins(ids, positions)[0]

    def selected(self, ids, positions=None):
        """Per layer, the boolean (B, S, S) mask of the keys each query row
        keeps (small sizes: tests and ``chip_smoke.py``'s share of columns
        that differ)."""
        return [sel for sel, _ in self._hidden(ids, positions, keep_sets=True)[3]]

    def selected_and_scores(self, ids, positions=None):
        """Per layer ``(mask, index scores)``, both (B, S, S): the scores are
        ``-inf`` above the diagonal."""
        return self._hidden(ids, positions, keep_sets=True)[3]
