"""ByteDance's Ouro looped language models (``model_type: ouro``), TPU-native:
a stack of decoder layers that is RUN ``total_ut_steps`` times over ONE set of
weights, each pass attending and writing a K/V cache of its own. Built from
the parallel layers, ``RMSNorm`` and the joined K/V cache as
``models/afmoe.py`` is. Config: ``ByteDance/Ouro-2.6B`` (``config.json``; the
paper is "Scaling Latent Reasoning via Looped Language Models", arXiv
2510.25741).

Equations (``h`` hidden, ``H`` query and ``Hkv`` key/value heads of ``D``,
RMSNorm eps ``rms_eps`` with a learned scale everywhere, no biases but the
gate's; ``L`` layers, ``T = total_ut_steps`` passes)::

    layer_i(x, t):                       # weights of layer i, cache node (t, i)
      a = Wo_i Attn(rope(Wq_i N1_i(x)), K_(t,i), V_(t,i))   # K/V of THIS pass only
      x = x + N2_i(a)                    # sandwich: a norm before AND after
      m = Wd_i (silu(Wg_i N3_i(x)) * Wu_i N3_i(x))
      x = x + N4_i(m)
    h_0 = E[ids]
    for t in 1..T:   h_t = N(layer_L(... layer_1(h_{t-1}, t) ..., t))   # the final norm after EVERY pass
    lambda_t = sigmoid(w_g . h_t + b_g)                                  # the exit gate
    p_t = lambda_t * prod_{j<t} (1 - lambda_j)   (t < T),   p_T = the rest
    exit at the first t with sum_{j<=t} p_j >= early_exit_threshold
    logits = Wh h_T                      # threshold 1: the sum reaches 1 at t = T only

Rotary over all ``D`` channels of q and k (channel ``i`` paired with ``i +
D/2``), scores ``/ sqrt(D)``, causal softmax, grouped-query heads (the
published model has ``Hkv = H``).

Parameters exist ONCE a layer (``layers_<i>`` is one module, called once a
pass). The ``cache`` collection holds ``T`` nodes a layer,
``layers_<i>/attn/pass_<t>``, each a
:class:`~neuronx_distributed_tpu.modules.attention.JoinedKVCache` with no
window: the leaf every paged walker knows, so the page pool, the block table
(ONE: every pass holds the same tokens), the prefix cache and the walking
decode kernel take the nodes as they take any full-attention layer's, ``T *
L`` of them, in the order the stack runs them: pass-major
(``modules/attention.py::_execution_order`` has the rule).

``prefill`` is ROLLED: one lifted scan over the passes with the weights
broadcast, so a prompt bucket's program holds ``L`` layer bodies and not ``T *
L`` (a process traces and lowers every bucket's program before its first
token, compile cache or not); each pass gives its keys and values out of the
scan and they go into the pass's node afterwards, so the cache tree is the
same in every mode. ``decode`` and ``train`` are unrolled: a pass's node is
read inside its attention, under the fused paged frame by name
(ROADMAP queue 2, C12c). ``bucket_prefill_rows``: the serving engine builds a
bucket's prefill with ``max_seq_len`` the bucket's, so a prefill's output is a
row of the bucket's columns.

``train`` returns ``(logits, {"exit_distribution": p})``, ``p`` (B, S, T) in
float32. The served modes return pass ``T``'s logits and do not evaluate the
gate: at the published ``early_exit_threshold`` of 1 the cumulative sum
reaches 1 at the last pass alone, so the output is identical. A threshold
below 1 makes the NUMBER of passes a token's own, and a token that exits
early still owes the later passes' K/V to the tokens after it: that is not
served here, and the config refuses it by name.

Assumed (the config has no key for them; the paper and the published
modelling code as remembered): the sandwich norms, the final norm after every
pass, the gate's form and that it reads the normed ``h_t``, no bias and no
head norm in attention, the rotate-half pairing.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules.attention import (
    ATTN_FULL_SCOPE,
    LOOP_PASS_NODE,
    LOOP_PASS_SCOPE,
    JoinedKVCache,
    ParallelMLP,
    apply_rope,
    attention_op,
    joined_decode_attention,
    prefill_positions,
    rope_frequencies,
)
from neuronx_distributed_tpu.modules.qkv_linear import GQAQKVColumnParallelLinear
from neuronx_distributed_tpu.modules.rms_norm import RMSNorm
from neuronx_distributed_tpu.parallel import mesh as mesh_lib
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    ParallelEmbedding,
    RowParallelLinear,
)
from neuronx_distributed_tpu.parallel.losses import parallel_cross_entropy
from neuronx_distributed_tpu.parallel.sharding import UNC, constrain


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 48
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    # passes of the stack over its one set of weights
    total_ut_steps: int = 4
    # the cumulative exit probability a token stops at; 1: every token runs
    # every pass (the only value served: module docstring)
    early_exit_threshold: float = 1.0
    max_seq_len: int = 4096
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # the serving engine's fused paged path reads this (layers and passes are
    # unrolled)
    scan_layers: bool = False
    # what the serving engine must know of the cache: K and V one joined
    # leaf (modules/attention.py JoinedKVCache), no window and no per-slot
    # state, so a context can be held and shared by its pages
    kv_cache_kind: str = "joined"
    # the serving engine's paged admission builds each prefill bucket's
    # program with ``max_seq_len`` the BUCKET's, so a prefill gives out a row
    # of the bucket's columns and not of the cache's: with T * L nodes and a
    # deployment's two slots a whole row is half the pool again
    # (serving/engine.py ``_bucket_rows``)
    bucket_prefill_rows: bool = True

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps must be >= 1, got {self.total_ut_steps}")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold} is not served: below 1 a "
                "token's number of passes is its own, and a token that exits early still "
                "owes the later passes' K/V to the tokens after it; only the published "
                "early_exit_threshold of 1 (every token runs total_ut_steps passes) is")

    @property
    def kv_cache_nodes(self) -> int:
        """Cache nodes the stack holds: one a layer a pass."""
        return self.num_layers * self.total_ut_steps


def ouro_2_6b(**over) -> OuroConfig:
    """``ByteDance/Ouro-2.6B`` as published."""
    return OuroConfig(**over)


def tiny_ouro(**over) -> OuroConfig:
    """Shrunk config for tests: 2 layers x 3 passes (passes != layers, so a
    walker that confuses the two fails), MHA as published."""
    return OuroConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, total_ut_steps=3,
        max_seq_len=128, dtype=jnp.float32,
    ), **over})


class OuroPassKV(nn.Module):
    """ONE pass's cache node of one layer (no parameter), a
    :class:`JoinedKVCache`. ``decode``: rotary, the write and the attend over
    the cache. ``prefill`` (``q`` None): the write alone, of the rotated keys
    and the values the pass computed inside the rolled prefill's scan, where
    it attended the prompt itself (:class:`OuroAttention`)."""

    config: OuroConfig
    mode: str = "prefill"

    @nn.compact
    def __call__(self, q, k, v, freqs, positions=None, padding_mask=None):
        cfg = self.config
        b, s = k.shape[0], k.shape[1]
        cache = JoinedKVCache(self, b, cfg.max_seq_len, cfg.num_kv_heads, cfg.head_dim, k.dtype)
        if self.mode == "prefill":
            if s > cfg.max_seq_len:
                raise ValueError(f"prompt length {s} exceeds max_seq_len={cfg.max_seq_len}")
            cache.prefill_write(k, v, padding_mask)
            return None
        if self.mode != "decode":
            raise ValueError(f"unknown cached attention mode {self.mode!r}")
        # the scope names the attention for a trace reader: the innermost one,
        # so a Pallas kernel called inside it is named after it
        with jax.named_scope(ATTN_FULL_SCOPE):
            pos, rope_pos = cache.decode_positions(s, positions)
            q, k = apply_rope(q, freqs, rope_pos), apply_rope(k, freqs, rope_pos)
            cache.decode_write(k, v, padding_mask)
            return joined_decode_attention(q, cache.kv.value, pos, cache.valid.value)


class OuroAttention(nn.Module):
    """Rotary GQA whose weights serve every pass and whose K/V are pass
    ``ut_step``'s own (module docstring)."""

    config: OuroConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, ut_step, positions=None, padding_mask=None, store=None):
        """``ut_step`` None: a pass of no cache node of its own (``train``, and
        a rolled prefill's scan body, which also returns the pass's ``(k,
        v)``); ``store``: such a pass's ``(k, v)`` into node ``ut_step``,
        nothing else."""
        cfg = self.config
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        if store is not None:
            OuroPassKV(cfg, self.mode, name=f"{LOOP_PASS_NODE}{ut_step}")(
                None, *store, freqs, positions, padding_mask)
            return None
        lin = dict(use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]
        q, k, v = GQAQKVColumnParallelLinear(
            hidden_size=cfg.hidden_size, num_heads=h, num_kv_heads=hkv, head_dim=d,
            name="qkv", **lin,
        )(x)
        q = constrain(q.reshape(b, s, h, d), P(UNC, UNC, mesh_lib.TP_AXIS))
        k, v = k.reshape(b, s, hkv, d), v.reshape(b, s, hkv, d)
        if ut_step is None:
            with jax.named_scope(ATTN_FULL_SCOPE):
                if self.mode == "prefill" and positions is None and padding_mask is not None:
                    positions = prefill_positions(padding_mask)
                q, k = apply_rope(q, freqs, positions), apply_rope(k, freqs, positions)
                out = attention_op(q, k, v, causal=True, impl=self.attention_impl,
                                   mask=padding_mask)
        else:
            out = OuroPassKV(cfg, self.mode, name=f"{LOOP_PASS_NODE}{ut_step}")(
                q, k, v, freqs, positions, padding_mask)
        out = RowParallelLinear(h * d, cfg.hidden_size, name="o_proj", **lin)(
            out.reshape(b, s, h * d))
        return (out, (k, v)) if self.mode == "prefill" and ut_step is None else out


class OuroDecoderLayer(nn.Module):
    config: OuroConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, x, freqs, ut_step, positions=None, padding_mask=None, store=None):
        """``ut_step`` and ``store`` as :class:`OuroAttention` takes them; a
        rolled prefill's scan body gets ``(x, (k, v))`` back."""
        cfg = self.config
        norm = dict(eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        attention = OuroAttention(cfg, self.attention_impl, self.mode, name="attn")
        if store is not None:
            return attention(None, freqs, ut_step, positions, padding_mask, store)

        def rms(name, y):
            return RMSNorm(cfg.hidden_size, name=name, **norm)(y)

        attn = attention(rms("input_norm", x), freqs, ut_step, positions, padding_mask)
        kv = None
        if self.mode == "prefill" and ut_step is None:
            attn, kv = attn
        x = x + rms("post_attn_norm", attn)
        out = ParallelMLP(
            cfg.hidden_size, cfg.intermediate_size, activation="silu", use_bias=False,
            glu=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp",
        )(rms("pre_mlp_norm", x))
        x = x + rms("post_mlp_norm", out)
        return x if kv is None else (x, kv)


class OuroModel(nn.Module):
    """Backbone without the LM head: ``(h_1 .. h_T)`` stacked on a leading
    axis in ``train`` mode (the gate reads every pass), ``h_T`` alone in the
    served modes."""

    config: OuroConfig
    attention_impl: str = "auto"
    mode: str = "train"

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, padding_mask=None):
        cfg = self.config
        if segment_ids is not None:
            raise NotImplementedError("packed documents are not modelled for the looped stack")
        x = ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
        )(input_ids)
        freqs = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

        # ONE module a layer: called once a pass, it shares its parameters
        # between the passes (a flax module called twice is one set of them)
        def stack():
            return [
                OuroDecoderLayer(cfg, self.attention_impl, self.mode, name=f"layers_{i}")
                for i in range(cfg.num_layers)
            ], RMSNorm(
                cfg.hidden_size, eps=cfg.rms_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="final_norm",
            )

        if self.mode == "prefill":
            # ROLLED: one scan over the passes, the weights broadcast, so the
            # program holds L layer bodies and not T * L (a prompt's program is
            # compiled a bucket, and a process traces and lowers each before
            # its first token). A pass attends the prompt itself and gives its
            # keys and values out of the scan; each goes into its pass's node
            # afterwards, so the cache tree is the unrolled modes' own
            def one_pass(mdl, x, _):
                layers, final_norm = stack()
                held = []
                with jax.named_scope(LOOP_PASS_SCOPE):
                    for layer in layers:
                        x, kv = layer(x, freqs, None, positions, padding_mask)
                        held.append(kv)
                    x = final_norm(x)
                return x, tuple(held)

            x, held = nn.scan(
                one_pass, variable_broadcast="params", split_rngs={"params": False},
                length=cfg.total_ut_steps,
            )(self, x, None)
            for layer, (k, v) in zip(stack()[0], held):
                for t in range(cfg.total_ut_steps):
                    layer(None, freqs, t, positions, padding_mask, store=(k[t], v[t]))
            return x
        layers, final_norm = stack()
        passes = []
        for t in range(cfg.total_ut_steps):
            with jax.named_scope(LOOP_PASS_SCOPE):
                for layer in layers:
                    x = layer(x, freqs, None if self.mode == "train" else t, positions, padding_mask)
                x = final_norm(x)
            passes.append(x)
        return jnp.stack(passes) if self.mode == "train" else x


def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """``p`` (..., T) from the passes' gate logits (..., T): ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)`` for ``t < T`` and ``p_T`` the rest, float32;
    sums to 1."""
    lam = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=-1)                       # prod_{j<=t} (1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
    return jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]], axis=-1)


class OuroForCausalLM(nn.Module):
    """In ``prefill`` mode the head is applied to the LAST position alone
    (logits (B, 1, V)): the contract every causal LM here keeps, stated in
    ``models/__init__.py``. ``train`` returns ``(logits, {"exit_distribution":
    p})``; the served modes return pass ``T``'s logits and do not evaluate the
    exit gate (identical at the published threshold of 1: module docstring).
    Logits at every position of a context: ``mode="train"``, or ``OuroModel``
    in ``prefill`` mode and the head's kernel."""

    config: OuroConfig
    attention_impl: str = "auto"
    mode: str = "train"

    def init(self, rngs, *args, **kwargs):
        """The weights are DRAWN in float32 and then rounded to
        ``param_dtype`` (``jax.random``'s normal drawn IN bfloat16 is biased:
        ``SolarOpen2ForCausalLM.init`` has the numbers)."""
        cfg = self.config
        if cfg.param_dtype == jnp.float32:
            return super().init(rngs, *args, **kwargs)
        wide = self.clone(config=dataclasses.replace(cfg, param_dtype=jnp.float32))
        variables = wide.init(rngs, *args, **kwargs)
        return {**variables, "params": jax.tree.map(lambda a: a.astype(cfg.param_dtype), variables["params"])}

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, padding_mask=None):
        cfg = self.config
        x = OuroModel(cfg, self.attention_impl, self.mode, name="model")(
            input_ids, positions, segment_ids, padding_mask)
        head = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )
        if self.mode != "train":
            return head(x[:, -1:] if self.mode == "prefill" else x)
        gate = ColumnParallelLinear(
            cfg.hidden_size, 1, use_bias=True, gather_output=True, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="early_exit_gate",
        )(x)                                                        # (T, B, S, 1)
        p = exit_distribution(jnp.moveaxis(gate[..., 0], 0, -1))    # (B, S, T)
        return head(x[-1]), {"exit_distribution": p}

    def loss(self, params, input_ids, labels, loss_mask=None):
        """Cross entropy of the last pass's logits."""
        logits, _ = self.apply(params, input_ids)
        tok = parallel_cross_entropy(logits, labels)
        if loss_mask is not None:
            return (tok * loss_mask).sum() / jnp.maximum(loss_mask.sum(), 1)
        return tok.mean()

