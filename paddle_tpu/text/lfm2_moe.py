"""LFM2-MoE-class decoder-only causal LM (`model_type: lfm2_moe`;
LFM2-24B-A2B is the configuration the benchmark runs): pre-norm RMSNorm
residual blocks whose token mixer is, by `layer_types`, either a gated
short convolution or grouped-query attention with an RMSNorm over each
head of q and k; the first `num_dense_layers` FFNs a dense SwiGLU, the rest
sigmoid-routed experts with a selection bias and no shared expert; the
head tied to the embedding; next-token loss.

Built from `nn` pieces and described to `HybridPretrainer` as a
`PretrainModel` whose groups are the **maximal runs of one kind of block**
in `layer_types` × `num_dense_layers` (`block_runs`): a kind is (mixer,
FFN), a run is scanned as any group of uniform blocks is, and the runs
follow each other in the published order.  The published 40 layers are 21
runs (2 dense convolution layers, then `attention` × 1 and `conv` × 3 in
turn, ending on one convolution layer); nothing in the description knows a
period, so a pattern that ends mid-period, as the published one does, needs
no special case.  `held_experts=(first, count)` is this chip's share of an
expert-parallel group (`nn.DroplessMoE`).

No Layer attribute here is named like a region of `utils/xprof.REGIONS` or
like one of the finer scopes (`conv`, `core`, `router`, `experts`): with
`xprof_scopes` on, an attribute's name is a scope.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer.base import Layer, Parameter
from ..ops import attention as attn_ops
from ..utils import xprof as _xprof
from .deepseek_v3 import _host_device, next_token_loss, residual_block
from .pretrainer import PretrainModel, run_groups, runs_of_one_kind

CONV, ATTENTION = "conv", "full_attention"
ROUTER_NORM_EPS = 1e-6      # the published code's, no key of the config


def published_layer_types(n: int) -> List[str]:
    """The published pattern cut to its first n layers: full attention at
    layers 2, 6, 10, …, a gated short convolution everywhere else."""
    return [ATTENTION if i % 4 == 2 else CONV for i in range(n)]


class Lfm2MoeConfig:
    """The keys of the published `config.json` that shape the model
    (defaults: LFM2-24B-A2B; `conv_bias` false, `use_expert_bias` true and
    the head tied to the embedding are the only forms built), plus
    `held_experts`: which routed experts this program holds (None: all of
    them)."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=8, intermediate_size=11776,
                 moe_intermediate_size=1536, num_experts=64,
                 num_experts_per_tok=4, num_dense_layers=2,
                 layer_types: Optional[Sequence[str]] = None,
                 conv_L_cache=3, rope_theta=1e6, norm_eps=1e-5,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 initializer_range=0.02,
                 held_experts: Optional[Tuple[int, int]] = None):
        layer_types = list(published_layer_types(num_hidden_layers)
                           if layer_types is None else layer_types)
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types names {num_hidden_layers} layers "
                             f"as {CONV!r} or {ATTENTION!r}: {layer_types}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_dense_layers = num_dense_layers
        self.layer_types = layer_types
        self.conv_L_cache = conv_L_cache
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.initializer_range = initializer_range
        self.held_experts = held_experts

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def weight_attr(self):
        return type("A", (), {"initializer": nn.initializer.Normal(
            0.0, self.initializer_range)})()


def block_kinds(cfg: Lfm2MoeConfig) -> List[Tuple[str, bool]]:
    """Every layer's kind of block, in order: (mixer, expert FFN?)."""
    return [(mixer, i >= cfg.num_dense_layers)
            for i, mixer in enumerate(cfg.layer_types)]


def block_runs(cfg: Lfm2MoeConfig) -> List[Tuple[str, bool, int]]:
    """The layers in order as maximal runs of one kind of block:
    [(mixer, expert FFN?, layers)]."""
    return [kind + (n,) for kind, n in runs_of_one_kind(block_kinds(cfg))]


def kind_label(kind: Tuple[str, bool]) -> str:
    mixer, expert = kind
    return (f"{'conv' if mixer == CONV else 'attention'}"
            f"_{'expert' if expert else 'dense'}")


def run_name(index: int, mixer: str, expert: bool) -> str:
    """A run's group name: its place, its mixer, its FFN."""
    return f"run{index:02d}_{kind_label((mixer, expert))}"


def rotary_halves(x, theta: float):
    """Rotary positions on the last axis of x [..., S, d], halves form:
    [a | b] -> [a·cos − b·sin | b·cos + a·sin] with angle
    position × theta^(−2i/d) for channel i of each half, angles in float32."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@jax.checkpoint
def _gated_conv_out(bcu, taps, w_out):
    """[B | C | u] -> (C ∘ conv(B ∘ u))·W_out, on [b, s, channels] with the
    channels on lanes: the depthwise causal convolution is one shifted
    multiply-add a tap (tap j weighs z_{t−(taps−1−j)}, zeros before
    position 0) over z padded once, in the projection's own dtype, which
    XLA fuses with both gates.  Timed on one v5e at 8192 × 2048 bf16,
    forward and backward with the out-projection (PERF.md §6, PR 32):
    2.04 ms; the same arithmetic in float32 4.11 (XLA then writes a float32
    copy of the whole projection); `conv1d(groups=channels)` on channels
    first, as the published code has it, 3.37, on channels last 2.75 (both
    copy z into the convolution's layout).  The backward computes the pass
    again from the projection: z, c and the out-projection's input are three
    more tensors of the model's width in every layer of a scanned stack."""
    s, n = bcu.shape[-2], taps.shape[0]
    with jax.named_scope(_xprof.SCOPE_POINTWISE):
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        z = jnp.pad(gate_b * u, ((0, 0), (n - 1, 0), (0, 0)))
        c = gate_c * sum(taps[j] * z[:, j:j + s] for j in range(n))
    with jax.named_scope(_xprof.SCOPE_PROJ):
        return F.linear(c, w_out)


class ShortConv(Layer):
    """The gated short-convolution mixer: `[B | C | u] = x·W_in`,
    `z = B ∘ u`, `c_t = Σ_j k_j·z_{t−(L−1−j)}` per channel (a depthwise
    causal `Conv1d(kernel L, groups hidden, padding L − 1)` cut to the first
    s outputs; `taps` holds `k` as [L, hidden], the published [hidden, 1, L]
    with the channels on lanes), `out = (C ∘ c)·W_out`.  No activation, no
    bias, no state kept between calls (training)."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        attr, hidden = cfg.weight_attr(), cfg.hidden_size
        self.in_proj = nn.Linear(hidden, 3 * hidden, attr, bias_attr=False)
        self.taps = Parameter(
            attr.initializer((cfg.conv_L_cache, hidden),
                             self.in_proj.weight.value.dtype),
            initializer=attr.initializer)
        self.out_proj = nn.Linear(hidden, hidden, attr, bias_attr=False)

    def forward(self, x):
        with jax.named_scope(_xprof.SCOPE_CONV):
            with jax.named_scope(_xprof.SCOPE_PROJ):
                bcu = self.in_proj(x)
            return _gated_conv_out(bcu, self.taps.value,
                                   self.out_proj.weight.value)


class GroupedQueryAttention(Layer):
    """Causal attention of `num_attention_heads` query heads over
    `num_key_value_heads` key/value heads (query head j attends head
    j // group), q, k, v from one product (`qkv_proj`: q | k | v), an
    RMSNorm over each head's channels of q and of k (one weight each),
    then the rotary on all channels, halves form."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        attr, d = cfg.weight_attr(), cfg.head_dim
        self.cfg = cfg
        self.qkv_proj = nn.Linear(
            cfg.hidden_size,
            (cfg.num_attention_heads + 2 * cfg.num_key_value_heads) * d,
            attr, bias_attr=False)
        self.q_norm = nn.RMSNorm(d, cfg.norm_eps)
        self.k_norm = nn.RMSNorm(d, cfg.norm_eps)
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
            q = rotary_halves(heads(self.q_norm(q.reshape(b, s, h, d))),
                              cfg.rope_theta)
            k = rotary_halves(heads(self.k_norm(k.reshape(b, s, kv, d))),
                              cfg.rope_theta)
        with jax.named_scope(_xprof.ATTN_CORE):
            out = attn_ops.flash_attention(
                q, k, heads(v), is_causal=True, scale=1.0 / math.sqrt(d),
                training=self.training)
        with jax.named_scope(_xprof.SCOPE_PREP):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            return self.out_proj(out)


class Lfm2MoeBlock(Layer):
    """h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h)): the mixer a
    `ShortConv` (`mixer == "conv"`) or `GroupedQueryAttention`, the FFN a
    dense SwiGLU (`expert=False`) or the expert layer."""

    def __init__(self, cfg: Lfm2MoeConfig, mixer: str, expert: bool):
        super().__init__()
        self.operator_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)
        self.operator = ShortConv(cfg) if mixer == CONV \
            else GroupedQueryAttention(cfg)
        self.ffn_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)
        if expert:
            self.feed_forward = nn.DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                n_shared_experts=0,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob, norm_eps=ROUTER_NORM_EPS,
                weight_attr=cfg.weight_attr())
        else:
            self.feed_forward = nn.SwiGLU(
                cfg.hidden_size, cfg.intermediate_size, cfg.weight_attr())

    def forward(self, x, routing_stats: bool = False):
        """x -> y; with `routing_stats` (expert blocks), (y, the expert
        layer's `routing_stats` of this call)."""
        return residual_block(x, self.operator_norm, self.operator,
                              self.ffn_norm, self.feed_forward, routing_stats)


class Lfm2MoeEmbeddings(Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            weight_attr=cfg.weight_attr())

    def forward(self, input_ids):
        return self.word_embeddings(input_ids)


class Lfm2MoeLMHead(Layer):
    """Final RMSNorm, then the logits through the embedding matrix
    (`embedding_weight`, tied: one leaf, under the trainer's "embed")."""

    def __init__(self, cfg: Lfm2MoeConfig, embedding_weight):
        super().__init__()
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps)
        self.lm_weight = embedding_weight       # Parameter [V, H], tied

    def forward(self, hidden):
        return jnp.matmul(self.final_norm(hidden), self.lm_weight.value.T)


def pretrain_model(cfg: Lfm2MoeConfig):
    """The model as `HybridPretrainer` takes it: one group a run of
    `block_runs`, in order."""
    # drawn on the host, as `deepseek_v3.pretrain_model` says why
    with _host_device():
        embeddings = Lfm2MoeEmbeddings(cfg)
        return PretrainModel(
            embeddings=embeddings,
            groups=run_groups(block_kinds(cfg), kind_label,
                              lambda kind: Lfm2MoeBlock(cfg, *kind)),
            head=Lfm2MoeLMHead(cfg, embeddings.word_embeddings.weight),
            criterion=next_token_loss, embed_inputs=("input_ids",),
            token_keys=("input_ids",),
            # one leaf under "embed": its gradient accumulates from both uses
            tied={"lm_weight": "word_embeddings.weight"}, config=cfg)
