"""Granite-4.0-H-class decoder-only causal LM (`model_type:
granitemoehybrid`; granite-4.0-h-micro is the configuration the benchmark
runs): pre-norm RMSNorm residual blocks whose token mixer is, by
`layer_types`, either a **Mamba-2 state-space mixer** or grouped-query
attention with no positional encoding at all (`position_embedding_type:
"nope"`: the state-space layers carry the order); every FFN a dense SwiGLU
(`num_local_experts` 0: the shared MLP alone); four scalars on the residual
stream's way — the embedding times `embedding_multiplier`, both branches of
every block times `residual_multiplier`, the attention scores times
`attention_multiplier` (not 1/√d), the logits over `logits_scaling`; the
head tied to the embedding; next-token loss.

Built from `nn` pieces and described to `HybridPretrainer` as a
`PretrainModel` whose groups are the maximal runs of one kind of block in
`layer_types` (`pretrainer.run_groups`, as `text/lfm2_moe.py`): the
published 40 layers are 9 runs (5 mamba, then attention × 1 and mamba × 9 in
turn, ending on 4 mamba).

No Layer attribute here is named like a region of `utils/xprof.REGIONS` or
like one of the finer scopes (`ssm`, `ssd`, `conv`, `core`, …): with
`xprof_scopes` on, an attribute's name is a scope.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer.base import Layer, Parameter
from ..ops import attention as attn_ops
from ..ops.ssd import state_space_scan
from ..utils import xprof as _xprof
from .deepseek_v3 import _host_device, next_token_loss, residual_block
from .pretrainer import PretrainModel, run_groups

MAMBA, ATTENTION = "mamba", "attention"


def published_layer_types(n: int) -> List[str]:
    """The published pattern cut to its first n layers: attention at layers
    5, 15, 25, 35, a Mamba-2 mixer everywhere else."""
    return [ATTENTION if i % 10 == 5 else MAMBA for i in range(n)]


class GraniteHybridConfig:
    """The keys of the published `config.json` that shape the model
    (defaults: granite-4.0-h-micro).  Only what the family's dense members
    are is built: no routed experts, no biases but the convolution's, one
    group of B and C, RMSNorm, SiLU, no positions, the head tied to the
    embedding; a config that says otherwise is refused."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=8, shared_intermediate_size=8192,
                 layer_types: Optional[Sequence[str]] = None,
                 mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                 mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
                 mamba_chunk_size=256, mamba_conv_bias=True,
                 mamba_proj_bias=False, attention_bias=False,
                 num_local_experts=0, position_embedding_type="nope",
                 tie_word_embeddings=True, embedding_multiplier=12.0,
                 residual_multiplier=0.22, attention_multiplier=0.015625,
                 logits_scaling=8.0, rms_norm_eps=1e-5,
                 initializer_range=0.02):
        layer_types = list(published_layer_types(num_hidden_layers)
                           if layer_types is None else layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types names {num_hidden_layers} layers "
                             f"as {MAMBA!r} or {ATTENTION!r}: {layer_types}")
        if (num_local_experts or mamba_proj_bias or attention_bias
                or not mamba_conv_bias or mamba_n_groups != 1
                or position_embedding_type != "nope"
                or not tie_word_embeddings
                or mamba_n_heads * mamba_d_head != mamba_expand * hidden_size):
            raise ValueError(
                "built: num_local_experts 0, mamba_conv_bias true, "
                "mamba_proj_bias and attention_bias false, mamba_n_groups 1, "
                "position_embedding_type 'nope', a tied head and "
                "mamba_n_heads x mamba_d_head = mamba_expand x hidden_size")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.shared_intermediate_size = shared_intermediate_size
        self.layer_types = layer_types
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.attention_multiplier = attention_multiplier
        self.logits_scaling = logits_scaling
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    def weight_attr(self):
        return type("A", (), {"initializer": nn.initializer.Normal(
            0.0, self.initializer_range)})()


def causal_depthwise_conv(x, taps, bias=None):
    """y_t = bias + Σ_j taps[j] · x_{t−(n−1−j)} per channel, x before
    position 0 nought: a depthwise causal `Conv1d(kernel n, groups channels,
    padding n − 1)` cut to the first s outputs, on [b, s, channels] with the
    channels on lanes — one shifted multiply-add a tap over x padded once, in
    x's own dtype (`lfm2_moe._gated_conv_out` says what that saves).  No
    `bias`, no add."""
    s, n = x.shape[-2], taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    out = sum(taps[j] * padded[:, j:j + s] for j in range(n))
    return out if bias is None else out + bias


class Mamba2Mixer(Layer):
    """The Mamba-2 mixer: `[z | xBC | dt] = x·W_in` (widths d_inner |
    d_inner + 2·d_state | heads, in the published order);
    `xBC ← silu(conv(xBC))`, a depthwise causal convolution of
    `mamba_d_conv` taps with bias (`taps` holds the published
    [channels, 1, taps] kernel as [taps, channels]); `[x | B | C] = xBC`
    (B and C shared by every head: one group); `dt ← softplus(dt +
    dt_bias)`, `A = −exp(a_log)` a head, both in float32; the state-space
    scan (`ops/ssd.py`) with the skip `d_skip · x`; the gated norm
    `RMSNorm(y ∘ silu(z))` over all d_inner channels (gate first, then the
    norm); `W_out`.  No state is kept between calls (training)."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        attr, heads = cfg.weight_attr(), cfg.mamba_n_heads
        inner, channels = cfg.mamba_d_inner, \
            cfg.mamba_d_inner + 2 * cfg.mamba_d_state
        self.cfg = cfg
        self.in_proj = nn.Linear(cfg.hidden_size, inner + channels + heads,
                                 attr, bias_attr=False)
        dtype = self.in_proj.weight.value.dtype

        def leaf(shape):
            return Parameter(attr.initializer(shape, dtype),
                             initializer=attr.initializer)

        self.taps = leaf((cfg.mamba_d_conv, channels))
        self.taps_bias = leaf((channels,))
        self.dt_bias = leaf((heads,))
        self.a_log = leaf((heads,))
        self.d_skip = Parameter(jnp.ones((heads,), dtype))
        self.gate_norm = nn.RMSNorm(inner, cfg.rms_norm_eps)
        self.out_proj = nn.Linear(inner, cfg.hidden_size, attr,
                                  bias_attr=False)

    def forward(self, x):
        cfg, (b, s, _) = self.cfg, x.shape
        inner, state, f32 = cfg.mamba_d_inner, cfg.mamba_d_state, jnp.float32
        with jax.named_scope(_xprof.SCOPE_SSM):
            with jax.named_scope(_xprof.SCOPE_PROJ):
                zxbcdt = self.in_proj(x)
            with jax.named_scope(_xprof.SCOPE_POINTWISE):
                z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * state],
                                       axis=-1)
                xbc = F.silu(causal_depthwise_conv(
                    xbc, self.taps.value, self.taps_bias.value))
                u, B, C = jnp.split(xbc, [inner, inner + state], axis=-1)
                dt = jax.nn.softplus(dt.astype(f32)
                                     + self.dt_bias.value.astype(f32))
                u = u.reshape(b, s, cfg.mamba_n_heads, cfg.mamba_d_head)
                a = -jnp.exp(self.a_log.value.astype(f32))
            y = state_space_scan(u, dt, a, B, C, self.d_skip.value,
                                 cfg.mamba_chunk_size)
            with jax.named_scope(_xprof.SCOPE_POINTWISE):
                y = self.gate_norm(y.reshape(b, s, inner) * F.silu(z))
            with jax.named_scope(_xprof.SCOPE_PROJ):
                return self.out_proj(y)


class NopeAttention(Layer):
    """Causal grouped-query attention of `num_attention_heads` query heads
    over `num_key_value_heads` key/value heads (query head j attends head
    j // group), q, k, v from one product (`qkv_proj`: q | k | v), no
    positional term of any kind, the scores times `attention_multiplier`."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        attr, d = cfg.weight_attr(), cfg.head_dim
        self.cfg = cfg
        self.qkv_proj = nn.Linear(
            cfg.hidden_size,
            (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * d,
            attr, bias_attr=False)
        self.out_proj = nn.Linear(cfg.num_attention_heads * d,
                                  cfg.hidden_size, attr, bias_attr=False)

    def forward(self, x):
        cfg, (b, s, _) = self.cfg, x.shape
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        heads = lambda t: t.reshape(b, s, -1, d).transpose(0, 2, 1, 3)  # noqa: E731
        with jax.named_scope(_xprof.SCOPE_PROJ):
            qkv = self.qkv_proj(x)
        with jax.named_scope(_xprof.SCOPE_PREP):
            q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], axis=-1)
        with jax.named_scope(_xprof.ATTN_CORE):
            out = attn_ops.flash_attention(
                heads(q), heads(k), heads(v), is_causal=True,
                scale=cfg.attention_multiplier, training=self.training)
        with jax.named_scope(_xprof.SCOPE_PREP):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            return self.out_proj(out)


class GraniteHybridBlock(Layer):
    """h = x + m · Mixer(RMSNorm(x)); y = h + m · MLP(RMSNorm(h)), m the
    `residual_multiplier`: the mixer a `Mamba2Mixer` (`mixer == "mamba"`) or
    `NopeAttention`, the MLP a dense SwiGLU at `shared_intermediate_size`."""

    def __init__(self, cfg: GraniteHybridConfig, mixer: str):
        super().__init__()
        self.residual_multiplier = cfg.residual_multiplier
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = Mamba2Mixer(cfg) if mixer == MAMBA \
            else NopeAttention(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mlp = nn.SwiGLU(cfg.hidden_size, cfg.shared_intermediate_size,
                             cfg.weight_attr())

    def forward(self, x):
        return residual_block(
            x, self.input_norm, self.mixer, self.post_norm, self.mlp,
            residual_multiplier=self.residual_multiplier)


class GraniteHybridEmbeddings(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.embedding_multiplier = cfg.embedding_multiplier
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            weight_attr=cfg.weight_attr())

    def forward(self, input_ids):
        return self.word_embeddings(input_ids) * self.embedding_multiplier


class GraniteHybridLMHead(Layer):
    """Final RMSNorm, then the logits through the embedding matrix
    (`embedding_weight`, tied: one leaf, under the trainer's "embed") over
    `logits_scaling`."""

    def __init__(self, cfg: GraniteHybridConfig, embedding_weight):
        super().__init__()
        self.logits_scaling = cfg.logits_scaling
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_weight = embedding_weight       # Parameter [V, H], tied

    def forward(self, hidden):
        return jnp.matmul(self.final_norm(hidden),
                          self.lm_weight.value.T) / self.logits_scaling


def pretrain_model(cfg: GraniteHybridConfig):
    """The model as `HybridPretrainer` takes it: one group a maximal run of
    one mixer in `layer_types`, in order."""
    # drawn on the host, as `deepseek_v3.pretrain_model` says why
    with _host_device():
        embeddings = GraniteHybridEmbeddings(cfg)
        return PretrainModel(
            embeddings=embeddings,
            groups=run_groups(cfg.layer_types, str,
                              lambda mixer: GraniteHybridBlock(cfg, mixer)),
            head=GraniteHybridLMHead(cfg, embeddings.word_embeddings.weight),
            criterion=next_token_loss, embed_inputs=("input_ids",),
            token_keys=("input_ids",),
            # one leaf under "embed": its gradient accumulates from both uses
            tied={"lm_weight": "word_embeddings.weight"}, config=cfg)
