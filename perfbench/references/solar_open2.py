"""Plain reference of Upstage's Solar Open 2 language models
(``solar_open2``): gated delta-rule linear-attention layers with a
per-channel decay and, every fourth layer, gated grouped-query softmax
attention with no positional term; every layer softmax-routed experts with a
shared expert; after the catalog's ``config`` of ``upstage/Solar-Open2-250B``.
Float32 ``jax.numpy`` at matmul precision ``highest``; no kernel, no cache, no
state handed around, no batching; fed the system's weights a layer at a time.
It is GIVEN THE SAME SHARE as the system: the routed experts
``[first_held_expert, first_held_expert + n_routed_experts)`` of
``n_routed_experts_published`` router outputs, the vocabulary's slice, and the
first ``num_hidden_layers`` layers of the published pattern (``gqa_layers``).

Per block, ``h`` hidden, RMSNorm eps ``rms_norm_eps`` with a learned scale,
no biases but the output gate's: ``x <- x + Mixer(N1(x))``; ``x <- x +
MoE(N2(x))``; a final RMSNorm and an untied head.

* A LINEAR layer, ``H`` heads of ``d``, ``u = N1(x)``: ``q~, k~, v~ = Wq u, Wk
  u, Wv u``; ``c_t = w_0 a_{t-3} + w_1 a_{t-2} + w_2 a_{t-1} + w_3 a_t`` a
  channel of each (an explicit sum of ``short_conv_kernel_size`` shifted
  terms, zeros before the first token), then SiLU; a head each ``q = c_q /
  |c_q| / sqrt(d)``, ``k = c_k / |c_k|``, ``v = c_v``. ``g = -exp(A_log_h)
  softplus(Wa_up Wa_down u + dt_bias)`` a channel; ``beta = 2 sigmoid(Wb u)`` a
  head. THE RECURRENCE, token by token under ``lax.scan`` (the definition, not
  a chunked form): ``S' = Diag(e^{g_t}) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t
  - S'^T k_t)^T``; ``o_t = S_t^T q_t``; ``S_0 = 0``, float32. Out: ``Wo
  [RMSNorm_head(o) * sigmoid(Wg_up Wg_down u + b_g)]``.
* A GQA layer: ``q`` (``Hq d``), ``k, v`` (``Hkv d``), NO rotary, a materialised
  causal softmax at ``1 / sqrt(d)`` in blocks of queries, query head ``n`` reads
  kv head ``n // (Hq / Hkv)``; ``Wo (o * sigmoid(Wgate u))``.
* MoE: ``p = softmax(Wr u)``; the ``k`` largest; weights ``p_i / sum of the
  chosen`` (``norm_topk_prob``) ``* routed_scaling_factor``; ``sum over the
  HELD chosen experts of w_i expert_i(u) + shared(u)``. What the absent experts
  would have added is left out, as in the system.

The router's margin, per position the narrowest over the layers: the ``k``-th
largest router LOGIT less the next (``log p_k - log p_{k+1}``: a softmax over
320 outputs puts the chosen at a hundredth each, so a difference of
probabilities would read every choice as a tie), COUNTED ONLY WHERE ONE OF THE
TWO IS AN EXPERT HELD HERE (infinite elsewhere), as
``references/glm_moe_dsa.py``.

Controls, for showing that a comparison against this file can fail:
``state_dtype`` (the recurrent state rounded to it after every token: a state
kept in bf16), ``decay`` False (``alpha = 1``: nothing is forgotten),
``beta_factor`` (1: no negative eigenvalue), ``conv`` False (the convolutions
removed: ``c = a``), ``rope_full`` (rotary wrongly applied in the GQA layers),
``gate`` False (both kinds' output gates left out), ``dtype`` (the WHOLE
reference in a lower precision: every weight, every projection's input and the
state rounded through it, one scale a tensor; sums still in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.references import common
from perfbench.references.glm_moe_dsa import _blocks, _rms_norm, _rounded, held_experts

QUERY_BLOCK = 256
HEAD_BLOCK = 1024
ROPE_THETA = 10000.0      # the control's alone: no layer of the model rotates


def layer_kinds(cfg: dict):
    """``"full"`` or ``"linear"`` for each layer that is run: the first
    ``num_hidden_layers`` of the published pattern."""
    gqa = {int(i) for i in cfg["gqa_layers"]}
    return ["full" if i in gqa else "linear" for i in range(int(cfg["num_hidden_layers"]))]


def _state_in(state, dtype):
    """``state`` as a state KEPT in ``dtype`` would read back, in float32.
    ``lax.reduce_precision`` and not a cast there and back: inside the scan
    the chip's compiler takes a pair of converts for excess precision it may
    keep, and elides it (the bf16 and the float8 state both read EXACTLY as
    the float32 one on the chip: my chip runs, PR 51). A 16-bit float has
    float32's range; float8 needs a scale (one a tensor, as ``_rounded``), to
    the largest value an IEEE float of those bits holds (240 for e4m3:
    ``reduce_precision`` keeps the top exponent for infinities)."""
    info = jnp.finfo(dtype)
    if info.bits >= 16:
        return jax.lax.reduce_precision(state, info.nexp, info.nmant)
    largest = (2.0 - 2.0 ** -info.nmant) * 2.0 ** (2 ** (info.nexp - 1) - 1)
    scale = jnp.maximum(jnp.abs(state).max(), 1e-30) / largest
    return jax.lax.reduce_precision(state / scale, info.nexp, info.nmant) * scale


class Reference:
    def __init__(self, cfg: dict, params, state_dtype=None, decay: bool = True, beta_factor: float = 2.0,
                 conv: bool = True, rope_full: bool = False, gate: bool = True, dtype=None):
        self.cfg = cfg
        if dtype is not None:
            state_dtype = state_dtype or dtype

        def f32(tree):
            return common.f32(tree) if dtype is None else jax.tree.map(lambda a: _rounded(a, dtype), tree)

        def act(x):
            return x if dtype is None else _rounded(x, dtype)

        self.p = params["params"]
        heads, kv_heads, d = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
        lin = cfg["linear_attn_config"]
        lin_heads, lin_d, taps = int(lin["num_heads"]), int(lin["head_dim"]), int(lin["short_conv_kernel_size"])
        top_k = int(cfg["num_experts_per_tok"])
        _, first, held = held_experts(cfg)
        eps = float(cfg["rms_norm_eps"])
        route_scale = float(cfg.get("routed_scaling_factor", 1.0))
        renorm = bool(cfg.get("norm_topk_prob", True))
        self.kinds = layer_kinds(cfg)

        def linear_mixer(a, u):
            b, s, _ = u.shape
            u = act(u)

            def shifted(t):
                """The convolution as a sum of shifted terms; zeros before the first token."""
                if not conv:
                    return t
                w = a["conv_weight"]                                    # (taps, channels), the last tap the token's own
                out = jnp.zeros_like(t)
                for j in range(taps):
                    back = taps - 1 - j
                    out = out + w[j] * jnp.pad(t, ((0, 0), (back, 0), (0, 0)))[:, :s]
                return out

            proj = jnp.concatenate([u @ a[f"{n}_proj"]["kernel"] for n in "qkv"], axis=-1)
            c = jax.nn.silu(shifted(proj)).reshape(b, s, 3, lin_heads, lin_d)
            q, k, v = c[:, :, 0], c[:, :, 1], c[:, :, 2]
            q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-12) / jnp.sqrt(jnp.float32(lin_d))
            k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-12)
            low = act(u @ a["decay_down"]["kernel"])
            g = -jnp.exp(a["A_log"])[:, None] * jax.nn.softplus(
                low @ a["decay_up"]["kernel"] + a["dt_bias"]).reshape(b, s, lin_heads, lin_d)
            if not decay:
                g = jnp.zeros_like(g)
            beta = beta_factor * jax.nn.sigmoid(u @ a["beta_proj"]["kernel"])      # (B, S, H)

            def token(state, xs):
                qt, kt, vt, gt, bt = xs                                 # (B, H, d) ...; bt (B, H)
                state = state * jnp.exp(gt)[..., None]                  # S' = Diag(alpha) S
                read = jnp.einsum("bhkv,bhk->bhv", state, kt)           # S'^T k
                state = state + bt[..., None, None] * kt[..., None] * (vt - read)[..., None, :]
                if state_dtype is not None:
                    state = _state_in(state, state_dtype)
                return state, jnp.einsum("bhkv,bhk->bhv", state, qt)    # o = S^T q

            xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
            _, o = jax.lax.scan(token, jnp.zeros((b, lin_heads, lin_d, lin_d), jnp.float32), xs)
            o = _rms_norm(jnp.moveaxis(o, 0, 1), a["head_norm"]["weight"], eps).reshape(b, s, lin_heads * lin_d)
            if gate:
                o = o * jax.nn.sigmoid(act(u @ a["gate_down"]["kernel"]) @ a["gate_up"]["kernel"] + a["gate_bias"])
            return act(o) @ a["o_proj"]["kernel"]

        def full_mixer(a, u):
            b, s, _ = u.shape
            u = act(u)
            q = (u @ a["q_proj"]["kernel"]).reshape(b, s, heads, d)
            k = (u @ a["k_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            v = (u @ a["v_proj"]["kernel"]).reshape(b, s, kv_heads, d)
            if rope_full:
                pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
                q, k = (common.rope_half_split(t, pos, d, ROPE_THETA) for t in (q, k))
            if dtype is not None:
                k, v = _rounded(k, dtype), _rounded(v, dtype)           # what a cache would hold
            k = jnp.repeat(k, heads // kv_heads, axis=2)
            v = jnp.repeat(v, heads // kv_heads, axis=2)
            cols = jnp.arange(s)

            def rows(qb, lo):
                t = lo + jnp.arange(qb.shape[1])
                att = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(jnp.float32(d))
                att = jnp.where((t[:, None] >= cols[None])[None, None], att, -jnp.inf)
                return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)

            out = _blocks(rows, (q,), s, QUERY_BLOCK).reshape(b, s, heads * d)
            if gate:
                out = out * jax.nn.sigmoid(u @ a["gate_proj"]["kernel"])
            return act(out) @ a["o_proj"]["kernel"]

        def swiglu(w, h):
            return act(jax.nn.silu(h @ w["gate"]["kernel"]) * (h @ w["up"]["kernel"])) @ w["down"]["kernel"]

        def routed(moe, ex, h):
            """``(the held experts' part of the routed sum, the router's margin)``."""
            logits = h @ moe["router"]["weight"]                        # (B, S, n_out)
            ranked, order = jax.lax.top_k(logits, top_k + 1)
            here = (order >= first) & (order < first + held)
            margin = jnp.where(here[..., top_k - 1] | here[..., top_k],
                               ranked[..., top_k - 1] - ranked[..., top_k], jnp.inf)
            top_e = order[..., :top_k]
            chosen = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_e, axis=-1)
            top_w = chosen / chosen.sum(-1, keepdims=True) if renorm else chosen
            weights = (jax.nn.one_hot(top_e - first, held) * (top_w * route_scale)[..., None]).sum(-2)

            def one_expert(acc, e):     # a scan: one expert's float32 copy and output at a time
                gate_w, up, down = f32(e[:3])
                return acc + (act(jax.nn.silu(h @ gate_w) * (h @ up)) @ down) * e[3][..., None], None

            out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
                ex["gate_proj"], ex["up_proj"], ex["down_proj"], jnp.moveaxis(weights, -1, 0)))
            return out, margin

        def split(layer):
            """``(the layer in float32 but for its experts, the experts as stored)``."""
            ex = layer["moe"]["experts"]
            return f32({k: v for k, v in layer.items() if k != "moe"}
                       | {"moe": {k: v for k, v in layer["moe"].items() if k != "experts"}}), ex

        def mixer(kind, layer, x):
            u = _rms_norm(x, layer["input_norm"]["weight"], eps)
            return full_mixer(layer["attn"], u) if kind == "full" else linear_mixer(layer["linear_attn"], u)

        def block(kind, layer, x):
            layer, ex = split(layer)
            x = x + mixer(kind, layer, x)
            h = act(_rms_norm(x, layer["pre_moe_norm"]["weight"], eps))
            out, margin = routed(layer["moe"], ex, h)
            if "shared" in layer["moe"]:
                out = out + swiglu(layer["moe"]["shared"], h)
            return x + out, margin

        def mixer_part(kind, layer, x):
            return mixer(kind, f32({k: v for k, v in layer.items() if k != "moe"}), x)

        def moe_part(layer, h):
            layer, ex = split(layer)
            out, _ = routed(layer["moe"], ex, h)
            return out, swiglu(layer["moe"]["shared"], h)

        def head(norm, lm, x):
            return act(_rms_norm(x, f32(norm["weight"]), eps)) @ f32(lm["kernel"])

        self._block = {k: common.highest(functools.partial(block, k)) for k in set(self.kinds)}
        self._mixer_part = {k: common.highest(functools.partial(mixer_part, k)) for k in set(self.kinds)}
        self._moe_part = common.highest(moe_part)
        self._embed = common.highest(lambda table, ids: f32(table)[ids])
        self._head = common.highest(head)

    def _hidden(self, ids):
        model = self.p["model"]
        x = self._embed(model["embed"]["embedding"], jnp.asarray(ids))
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for i, kind in enumerate(self.kinds):
            x, m = self._block[kind](model[f"layers_{i}"], x)
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
        """``(logits (B, S, V) on the host, the router's margin (B, S))``."""
        x, margin = self._hidden(ids)
        return self._logits(x), np.asarray(margin)

    def logits(self, ids):
        return self.logits_and_router_margin(ids)[0]

    def mixer_part(self, layer: int, x):
        """What layer ``layer``'s mixer (either kind) ADDS to the stream ``x``
        (B, S, hidden): one block alone, for a comparison that the layers
        after it cannot blur (``chip_smoke.py``)."""
        return self._mixer_part[self.kinds[layer]](self.p["model"][f"layers_{layer}"], jnp.asarray(x, jnp.float32))

    def moe_part(self, layer: int, h):
        """``(the held experts' part of the routed sum, the shared expert's
        output)`` of layer ``layer`` for normed inputs ``h`` (B, S, hidden)."""
        return self._moe_part(self.p["model"][f"layers_{layer}"], jnp.asarray(h, jnp.float32))

    def embed(self, ids):
        return self._embed(self.p["model"]["embed"]["embedding"], jnp.asarray(ids))
