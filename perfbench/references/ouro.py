"""Plain reference of ByteDance's Ouro looped language models (``ouro``): a
stack of decoder layers run ``total_ut_steps`` times over ONE set of weights,
each pass attending the keys and values that pass itself computed; after the
catalog's ``config`` of ``ByteDance/Ouro-2.6B`` and the paper ("Scaling Latent
Reasoning via Looped Language Models", arXiv 2510.25741). Float32
``jax.numpy`` at matmul precision ``highest``; no kernel, no cache (a full
forward of every pass), the mask built from indices, no batching; fed the
system's weights a layer at a time (a layer's float32 copy is made once a
pass), attention computed in blocks of queries.

``h`` hidden, ``H`` query and ``Hkv`` key/value heads of ``D``, RMSNorm eps
``rms_norm_eps`` with a learned scale everywhere, no biases but the gate's;
``L = num_hidden_layers`` layers, ``T = total_ut_steps`` passes:

* ``x = E[ids]``.
* a pass: for each layer in order ``a = Wo Attn(N1(x))``; ``x = x + N2(a)``;
  ``m = Wd (silu(Wg N3(x)) * Wu N3(x))``; ``x = x + N4(m)`` (a norm before and
  after each sublayer); then ``x = N(x)``, the final norm, after EVERY pass.
* ``Attn(u)``: ``q = Wq u`` (H x D), ``k = Wk u``, ``v = Wv u`` (Hkv x D);
  rotary over all D channels of q and k at ``rope_theta``; key ``j`` visible
  to query ``i`` iff ``j <= i``; scores ``/ sqrt(D)``, softmax; query head
  ``n`` reads kv head ``n // (H / Hkv)``. The keys and values are THIS pass's.
* the exit gate: ``lambda_t = sigmoid(w_g . h_t + b_g)`` of each pass's normed
  output; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T``
  the rest (:meth:`Reference.exit_distribution`).
* ``logits = Wh h_T``, untied: at ``early_exit_threshold`` 1 the cumulative
  ``p`` reaches 1 at the last pass alone, so every token runs every pass.

Departures: rotary pairing (channel ``i`` with ``i + D/2``, as the system:
``common.rope_half_split``; the published rotate-half pairing is the same).
Controls, for showing that a comparison against this file can fail:
``shared_cache`` (every pass after the first attends the keys and values the
FIRST pass computed: what a program whose passes share one cache node, or that
keeps one pass's cache for all, would serve), ``passes`` (another number of
passes), ``kv_dtype`` (round what a cache would hold), ``dtype`` (the WHOLE
reference in a lower precision: every weight, every cached value and every
projection's input rounded through it, one scale a tensor; sums still in
float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import common
from perfbench.references.glm_moe_dsa import _blocks, _rms_norm, _rounded

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


class Reference:
    def __init__(self, cfg: dict, params, shared_cache: bool = False, passes=None,
                 kv_dtype=None, dtype=None):
        self.cfg = cfg
        if dtype is not None:
            kv_dtype = kv_dtype or dtype
        if float(cfg.get("early_exit_threshold", 1.0)) < 1.0:
            raise ValueError("an early_exit_threshold below 1 is not modelled: every token runs every pass")
        if cfg.get("rope_scaling") is not None or cfg.get("use_sliding_window", False):
            raise ValueError("a scaled rotary or a sliding window is not modelled: Ouro has neither")

        def f32(tree):
            """The stored weights in float32, through ``dtype`` where it is given."""
            return common.f32(tree) if dtype is None else jax.tree.map(lambda a: _rounded(a, dtype), tree)

        def act(x):
            """A projection's input, through ``dtype`` where it is given."""
            return x if dtype is None else _rounded(x, dtype)

        self.p = params["params"]
        heads, kv_heads, d = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
        eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
        self.layers = int(cfg["num_hidden_layers"])
        self.passes = int(cfg["total_ut_steps"] if passes is None else passes)
        self.shared_cache = bool(shared_cache)

        def keys_values(a, u, pos):
            """What a cache would hold of normed inputs ``u``: ``(k, v)`` (B, S,
            Hkv, D), the keys rotated, rounded to ``kv_dtype``."""
            b, s, _ = u.shape
            k = common.rope_half_split((u @ a["qkv"]["k_proj"]["kernel"]).reshape(b, s, kv_heads, d), pos, d, theta)
            v = (u @ a["qkv"]["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            if kv_dtype is not None:
                k, v = (t.astype(kv_dtype).astype(jnp.float32) for t in (k, v))
            return k, v

        def block(layer, x, kv):
            """One layer of one pass: ``(the stream after it, the keys and
            values it attended)``; ``kv`` given: attend those (the
            ``shared_cache`` control), not this pass's own."""
            layer = f32(layer)
            b, s, _ = x.shape
            a = layer["attn"]
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            u = act(_rms_norm(x, layer["input_norm"]["weight"], eps))
            q = common.rope_half_split((u @ a["qkv"]["q_proj"]["kernel"]).reshape(b, s, heads, d), pos, d, theta)
            k, v = keys_values(a, u, pos) if kv is None else kv
            kr = jnp.repeat(k, heads // kv_heads, axis=2)
            vr = jnp.repeat(v, heads // kv_heads, axis=2)
            cols = jnp.arange(s)

            def rows(qb, lo):
                t = lo + jnp.arange(qb.shape[1])
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, kr) / jnp.sqrt(jnp.float32(d))
                att = jnp.where((t[:, None] >= cols[None])[None, None], att, -jnp.inf)
                return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), vr)

            out = _blocks(rows, (q,), s, QUERY_BLOCK).reshape(b, s, heads * d)
            x = x + _rms_norm(act(out) @ a["o_proj"]["kernel"], layer["post_attn_norm"]["weight"], eps)
            h = act(_rms_norm(x, layer["pre_mlp_norm"]["weight"], eps))
            w = layer["mlp"]
            m = act(jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"])) @ w["down"]["kernel"]
            return x + _rms_norm(m, layer["post_mlp_norm"]["weight"], eps), (k, v)

        def embed(table, ids):
            return f32(table)[ids]

        def norm(w, x):
            return _rms_norm(x, f32(w["weight"]), eps)

        def head(lm, x):
            return act(x) @ f32(lm["kernel"])

        def gate(g, x):
            g = f32(g)
            return (act(x) @ g["kernel"] + g["bias"])[..., 0]

        self._block = common.highest(block)
        self._embed = common.highest(embed)
        self._norm = common.highest(norm)
        self._head = common.highest(head)
        self._gate = common.highest(gate)

    def pass_outputs(self, ids):
        """``[h_1 .. h_T]``, each pass's normed output (B, S, hidden)."""
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        first, outs = {}, []
        for t in range(self.passes):
            for i in range(self.layers):
                x, kv = self._block(model[f"layers_{i}"], x, first.get(i) if self.shared_cache else None)
                if self.shared_cache and t == 0:
                    first[i] = kv
            x = self._norm(model["final_norm"], x)
            outs.append(x)
        return outs

    def logits(self, ids):
        """Pass ``T``'s logits (B, S, V) on the host, the head in blocks of
        positions."""
        x = self.pass_outputs(ids)[-1]
        b, s, _ = x.shape
        out = np.empty((b, s, int(self.cfg["vocab_size"])), np.float32)
        for lo in range(0, s, HEAD_BLOCK):
            out[:, lo:lo + HEAD_BLOCK] = np.asarray(self._head(self.p["lm_head"], x[:, lo:lo + HEAD_BLOCK]))
        return out

    def exit_distribution(self, ids):
        """``p`` (B, S, T): the probability that a token exits after pass
        ``t`` (module docstring); sums to 1 over the passes."""
        lam = jax.nn.sigmoid(jnp.stack(
            [self._gate(self.p["early_exit_gate"], h) for h in self.pass_outputs(ids)], axis=-1))
        stay = jnp.cumprod(1.0 - lam, axis=-1)
        before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
        return np.asarray(jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]], axis=-1))
