"""Olmo-Hybrid-class decoder-only causal LM (`model_type: olmo_hybrid`;
Olmo-Hybrid-7B is the configuration the benchmark runs): residual blocks
with the norm **after** each sublayer, inside its branch (the Olmo 2/3
placement: `h = x + RMSNorm(mixer(x))`, `y = h + RMSNorm(MLP(h))`), whose
token mixer is, by `layer_types`, either a **Gated DeltaNet** (a short
convolution, then the gated delta rule of `ops/delta_rule.py`) or
multi-head attention with RMSNorm over the whole q and k projections and
no positional term (`rope_theta` null); every MLP a dense SwiGLU; an
untied head; next-token loss.

Built from `nn` pieces and described to `HybridPretrainer` as a
`PretrainModel` with **one group a layer** (`pretrainer.run_groups` over
the layers' places and kinds, so no two layers make one run).  The trainer
casts each group's stacked weights to the compute dtype once, at the
step's start, and that copy lives until the group's backward is done: a
run of three delta-rule layers would hold 1.5 GB of bfloat16 weights
through its whole backward beside 3.7 GB of float32 gradients.  A group a
layer frees each layer's copy after its own backward, which is what lets
one period at the published widths train on one 16 GB chip (the step's
temporaries 5.1 GB and not 7.4 at 4096 positions).

No Layer attribute here is named like a region of `utils/xprof.REGIONS` or
like one of the finer scopes (`gdn`, `delta`, `proj`, `core`, …): with
`xprof_scopes` on, an attribute's name is a scope.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer.base import Layer, Parameter
from ..ops import attention as attn_ops
from ..ops.delta_rule import gated_delta_rule
from ..utils import xprof as _xprof
from .deepseek_v3 import _host_device, next_token_loss
from .granite_hybrid import causal_depthwise_conv
from .pretrainer import PretrainModel, run_groups

LINEAR, FULL = "linear_attention", "full_attention"


def published_layer_types(n: int) -> List[str]:
    """The published pattern cut to its first n layers: full attention at
    every fourth layer (3, 7, …), the delta rule everywhere else."""
    return [FULL if i % 4 == 3 else LINEAR for i in range(n)]


class OlmoHybridConfig:
    """The keys of the published `config.json` that shape the model
    (defaults: Olmo-Hybrid-7B), plus `linear_chunk_size`, the delta rule's
    chunk (no key of the source).  Only what the published model is is
    built: no biases, no positions, SiLU, an untied head, β up to 2, as
    many value heads as key heads; a config that says otherwise is
    refused."""

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=30, num_key_value_heads=30,
                 layer_types: Optional[Sequence[str]] = None,
                 linear_num_key_heads=30, linear_num_value_heads=30,
                 linear_key_head_dim=96, linear_value_head_dim=192,
                 linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                 linear_chunk_size=64, attention_bias=False,
                 hidden_act="silu", rope_theta=None,
                 tie_word_embeddings=False, rms_norm_eps=1e-6,
                 initializer_range=0.02):
        layer_types = list(published_layer_types(num_hidden_layers)
                           if layer_types is None else layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types names {num_hidden_layers} layers "
                             f"as {LINEAR!r} or {FULL!r}: {layer_types}")
        if (attention_bias or hidden_act != "silu" or rope_theta is not None
                or tie_word_embeddings or not linear_allow_neg_eigval
                or linear_num_value_heads != linear_num_key_heads
                or hidden_size % num_attention_heads
                or num_attention_heads % num_key_value_heads):
            raise ValueError(
                "built: attention_bias false, hidden_act 'silu', rope_theta "
                "null, an untied head, linear_allow_neg_eigval true, "
                "linear_num_value_heads = "
                "linear_num_key_heads, num_key_value_heads dividing "
                "num_attention_heads dividing hidden_size")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.layer_types = layer_types
        self.linear_num_heads = linear_num_key_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_chunk_size = linear_chunk_size
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def weight_attr(self):
        return type("A", (), {"initializer": nn.initializer.Normal(
            0.0, self.initializer_range)})()


def l2_normalised(x):
    """x / sqrt(Σx² + 1e-6) over the last axis, in float32, back in x's
    dtype (the family's `l2norm`)."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                               + 1e-6)).astype(x.dtype)


class GatedDeltaNet(Layer):
    """The Gated DeltaNet mixer: `[q | k | v | gate | a | b] = x·W_in`
    (widths heads·dk, heads·dk, heads·dv, heads·dv, heads, heads: the
    published q, k, v, g, a and b projections side by side);
    `[q | k | v] ← silu(conv(q | k | v))`, a depthwise causal convolution of
    `linear_conv_kernel_dim` taps without bias (`taps` [taps, channels]); q
    and k L2-normalised a head; `β = 2σ(b)` (`linear_allow_neg_eigval`:
    β in (0, 2)) and the log decay `g = −exp(a_log) ·
    softplus(a + dt_bias)`, both float32; the gated delta rule at
    `linear_chunk_size`; RMSNorm over each head's dv channels (one weight
    of dv), **then** the gate `silu(gate)`; `W_out`.  No state is kept
    between calls (training)."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        attr, heads = cfg.weight_attr(), cfg.linear_num_heads
        keys, values = heads * cfg.linear_key_head_dim, \
            heads * cfg.linear_value_head_dim
        self.cfg = cfg
        self.in_proj = nn.Linear(cfg.hidden_size,
                                 2 * keys + 2 * values + 2 * heads, attr,
                                 bias_attr=False)
        dtype = self.in_proj.weight.value.dtype

        def leaf(shape):
            return Parameter(attr.initializer(shape, dtype),
                             initializer=attr.initializer)

        self.taps = leaf((cfg.linear_conv_kernel_dim, 2 * keys + values))
        self.dt_bias = leaf((heads,))
        self.a_log = leaf((heads,))
        self.out_norm = nn.RMSNorm(cfg.linear_value_head_dim, cfg.rms_norm_eps)
        self.out_proj = nn.Linear(values, cfg.hidden_size, attr,
                                  bias_attr=False)

    def forward(self, x):
        cfg, (b, s, _) = self.cfg, x.shape
        heads, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim)
        keys, values, f32 = heads * dk, heads * dv, jnp.float32
        with jax.named_scope(_xprof.SCOPE_GDN):
            with jax.named_scope(_xprof.SCOPE_PROJ):
                proj = self.in_proj(x)
            with jax.named_scope(_xprof.SCOPE_POINTWISE):
                qkv, gate, a, w = jnp.split(proj, [
                    2 * keys + values, 2 * keys + 2 * values,
                    2 * keys + 2 * values + heads], axis=-1)
                qkv = F.silu(causal_depthwise_conv(qkv, self.taps.value))
                q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
                q = l2_normalised(q.reshape(b, s, heads, dk))
                k = l2_normalised(k.reshape(b, s, heads, dk))
                beta = 2.0 * jax.nn.sigmoid(w.astype(f32))
                g = -jnp.exp(self.a_log.value.astype(f32)) * jax.nn.softplus(
                    a.astype(f32) + self.dt_bias.value.astype(f32))
            o = gated_delta_rule(q, k, v.reshape(b, s, heads, dv), g, beta,
                                 cfg.linear_chunk_size)
            with jax.named_scope(_xprof.SCOPE_POINTWISE):
                o = self.out_norm(o) * F.silu(gate.reshape(b, s, heads, dv))
            with jax.named_scope(_xprof.SCOPE_PROJ):
                return self.out_proj(o.reshape(b, s, values))


class NormedNopeAttention(Layer):
    """Causal attention of `num_attention_heads` query heads over
    `num_key_value_heads` key/value heads, q, k, v from one product
    (`qkv_proj`: q | k | v), RMSNorm over the whole q and the whole k
    projection (one weight of heads·d each, before the heads are split), no
    positional term of any kind, scores times 1/√d."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        attr, d = cfg.weight_attr(), cfg.head_dim
        h, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.cfg = cfg
        self.qkv_proj = nn.Linear(cfg.hidden_size, (h + 2 * kv) * d, attr,
                                  bias_attr=False)
        self.q_norm = nn.RMSNorm(h * d, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(kv * d, cfg.rms_norm_eps)
        self.out_proj = nn.Linear(h * d, cfg.hidden_size, attr,
                                  bias_attr=False)

    def forward(self, x):
        cfg, (b, s, _) = self.cfg, x.shape
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        heads = lambda t: t.reshape(b, s, -1, d).transpose(0, 2, 1, 3)  # noqa: E731
        with jax.named_scope(_xprof.SCOPE_PROJ):
            qkv = self.qkv_proj(x)
        with jax.named_scope(_xprof.SCOPE_PREP):
            q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], axis=-1)
            q, k = self.q_norm(q), self.k_norm(k)
        with jax.named_scope(_xprof.ATTN_CORE):
            out = attn_ops.flash_attention(
                heads(q), heads(k), heads(v), is_causal=True,
                scale=1.0 / math.sqrt(d), training=self.training)
        with jax.named_scope(_xprof.SCOPE_PREP):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            return self.out_proj(out)


def norm_after_block(x, mixer, mixer_norm, ffn, ffn_norm):
    """h = x + mixer_norm(mixer(x)); y = h + ffn_norm(ffn(h)): the norm
    after each sublayer, inside its branch, each part under its region's
    scope (the token mixer's is `attn`, attention or not)."""
    with jax.named_scope(_xprof.REGION_ATTN):
        out = mixer(x)
    with jax.named_scope(_xprof.REGION_LN):
        x = x + mixer_norm(out)
    with jax.named_scope(_xprof.REGION_FFN):
        out = ffn(x)
    with jax.named_scope(_xprof.REGION_LN):
        return x + ffn_norm(out)


class OlmoHybridBlock(Layer):
    """`norm_after_block` of a `GatedDeltaNet` (`mixer ==
    "linear_attention"`) or `NormedNopeAttention` and a dense SwiGLU at
    `intermediate_size`."""

    def __init__(self, cfg: OlmoHybridConfig, mixer: str):
        super().__init__()
        self.mixer = GatedDeltaNet(cfg) if mixer == LINEAR \
            else NormedNopeAttention(cfg)
        self.mixer_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = nn.SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                             cfg.weight_attr())
        self.mlp_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, x):
        return norm_after_block(x, self.mixer, self.mixer_norm, self.mlp,
                                self.mlp_norm)


class OlmoHybridEmbeddings(Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            weight_attr=cfg.weight_attr())

    def forward(self, input_ids):
        return self.word_embeddings(input_ids)


class OlmoHybridLMHead(Layer):
    """Final RMSNorm, then the untied output projection."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_proj = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 cfg.weight_attr(), bias_attr=False)

    def forward(self, hidden):
        return self.lm_proj(self.final_norm(hidden))


def pretrain_model(cfg: OlmoHybridConfig):
    """The model as `HybridPretrainer` takes it: one group a layer, named
    `run<place>_<layer type>`, in order."""
    # drawn on the host, as `deepseek_v3.pretrain_model` says why
    with _host_device():
        return PretrainModel(
            embeddings=OlmoHybridEmbeddings(cfg),
            groups=run_groups(list(enumerate(cfg.layer_types)),
                              lambda layer: layer[1],
                              lambda layer: OlmoHybridBlock(cfg, layer[1])),
            head=OlmoHybridLMHead(cfg), criterion=next_token_loss,
            embed_inputs=("input_ids",), token_keys=("input_ids",),
            config=cfg)
