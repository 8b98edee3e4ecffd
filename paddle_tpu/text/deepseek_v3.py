"""DeepSeek-V3-class decoder-only causal LM (`model_type: deepseek_v3`;
kanana-2-30b-a3b is the configuration the benchmark runs): pre-norm RMSNorm
residual blocks, multi-head latent attention with one shared rotary key per
token, a leading dense SwiGLU layer followed by sigmoid-routed expert layers
with shared experts, untied embedding and head, next-token loss.

Built from `nn` pieces and described to `HybridPretrainer` as a
`PretrainModel` with two groups of uniform blocks (`dense_blocks`,
`expert_blocks`).  `held_experts=(first, count)` is this chip's share of an
expert-parallel group (`nn.DroplessMoE`).

No Layer attribute here is named like a region of `utils/xprof.REGIONS`
(`head`, `encoder`, `embed`, `attn`, `ffn`, `ln`, `loss`) or like one of the
finer scopes: with `xprof_scopes` on, an attribute's name is a scope.
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer.base import Layer
from ..ops import attention as attn_ops
from ..utils import xprof as _xprof
# the routing counters walk any model's groups; kept under this name too
# for the callers that found them here (the benchmark's decoder entry)
from .pretrainer import routing_stats  # noqa: F401


class DeepseekV3Config:
    """The keys of the published `config.json` that shape the model
    (defaults: kanana-2-30b-a3b-instruct-2601), plus `held_experts`: which
    routed experts this program holds (None: all of them)."""

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 intermediate_size=6144, moe_intermediate_size=768,
                 n_routed_experts=128, n_shared_experts=2,
                 num_experts_per_tok=6, first_k_dense_replace=1,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_theta=1e6, rms_norm_eps=1e-6,
                 routed_scaling_factor=2.448, norm_topk_prob=True,
                 initializer_range=0.02,
                 held_experts: Optional[Tuple[int, int]] = None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.initializer_range = initializer_range
        self.held_experts = held_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def weight_attr(self):
        return type("A", (), {"initializer": nn.initializer.Normal(
            0.0, self.initializer_range)})()


def rotary_interleaved(x, theta: float):
    """Rotary positions on the last axis of x [..., S, d], pairs
    (2i, 2i+1) rotated by position × theta^(-2i/d) (`rope_interleave`),
    angles in float32.  The published code first gathers the even and the
    odd channels into halves and rotates those: the same rotation with the
    output's channels in another fixed order, alike for q and k, so every
    score is the same."""
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LatentAttention(Layer):
    """Multi-head latent attention in its training form (no q compression:
    `q_lora_rank` null): keys and values come from a rank-`kv_lora_rank`
    latent, every head's key carries one shared rotary part, q·k runs at
    qk_nope + qk_rope per head and v at `v_head_dim`, causal."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        attr, h = cfg.weight_attr(), cfg.num_attention_heads
        self.cfg = cfg
        self.q_proj = nn.Linear(cfg.hidden_size, h * cfg.qk_head_dim, attr,
                                bias_attr=False)
        self.kv_a_proj = nn.Linear(
            cfg.hidden_size, cfg.kv_lora_rank + cfg.qk_rope_head_dim, attr,
            bias_attr=False)
        self.kv_a_norm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            attr, bias_attr=False)
        self.o_proj = nn.Linear(h * cfg.v_head_dim, cfg.hidden_size, attr,
                                bias_attr=False)

    def forward(self, x):
        cfg, (b, s, _) = self.cfg, x.shape
        h, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        heads = lambda t: t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)  # noqa: E731
        with jax.named_scope(_xprof.SCOPE_PROJ):
            q = self.q_proj(x)
        with jax.named_scope(_xprof.SCOPE_PREP):
            q = heads(q)                                # [b, h, s, 192]
        with jax.named_scope(_xprof.SCOPE_PROJ):
            latent = self.kv_a_proj(x)
        with jax.named_scope(_xprof.SCOPE_PREP):
            latent, k_rope = jnp.split(latent, [cfg.kv_lora_rank], axis=-1)
            latent = self.kv_a_norm(latent)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            kv = self.kv_b_proj(latent)
        with jax.named_scope(_xprof.SCOPE_PREP):
            k_nope, v = jnp.split(heads(kv), [nope], axis=-1)
            q_rope = rotary_interleaved(q[..., nope:], cfg.rope_theta)
            k_rope = rotary_interleaved(k_rope[:, None], cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, h, s, rope))], axis=-1)
        with jax.named_scope(_xprof.ATTN_CORE):
            out = attn_ops.flash_attention(
                q, k, v, is_causal=True,
                scale=1.0 / math.sqrt(cfg.qk_head_dim),
                training=self.training)
        with jax.named_scope(_xprof.SCOPE_PREP):
            out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            return self.o_proj(out)


class DeepseekV3Block(Layer):
    """h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h)), the FFN a dense
    SwiGLU (`expert=False`) or the expert layer."""

    def __init__(self, cfg: DeepseekV3Config, expert: bool):
        super().__init__()
        self.input_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LatentAttention(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if expert:
            self.mlp = nn.DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.held_experts, n_shared_experts=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                weight_attr=cfg.weight_attr())
        else:
            self.mlp = nn.SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                                 cfg.weight_attr())

    def forward(self, x, routing_stats: bool = False):
        """x -> y; with `routing_stats` (expert blocks), (y, the expert
        layer's `routing_stats` of this call)."""
        return residual_block(x, self.input_norm, self.self_attn,
                              self.post_norm, self.mlp, routing_stats)


def residual_block(x, input_norm, mixer, post_norm, ffn,
                   routing_stats: bool = False,
                   residual_multiplier: Optional[float] = None):
    """h = x + mixer(input_norm(x)); y = h + ffn(post_norm(h)), each part
    under its region's scope (the token mixer's is `attn`, attention or
    not).  With `residual_multiplier` both branches are scaled by it before
    they are added (h = x + m · mixer(…)); without, no multiply is emitted.
    With `routing_stats` (`ffn` an expert layer), (y, the layer's
    `routing_stats` of this call)."""
    def add(x, out):
        return x + (out if residual_multiplier is None
                    else residual_multiplier * out)

    with jax.named_scope(_xprof.REGION_LN):
        normed = input_norm(x)
    with jax.named_scope(_xprof.REGION_ATTN):
        out = mixer(normed)
    with jax.named_scope(_xprof.REGION_LN):
        x = add(x, out)
        normed = post_norm(x)
    with jax.named_scope(_xprof.REGION_FFN):
        out = ffn(normed)
    with jax.named_scope(_xprof.REGION_LN):
        y = add(x, out)
    return (y, ffn.routing_stats(normed)) if routing_stats else y


class DeepseekV3Embeddings(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            weight_attr=cfg.weight_attr())

    def forward(self, input_ids):
        return self.word_embeddings(input_ids)


class DeepseekV3LMHead(Layer):
    """Final RMSNorm, then the untied output projection."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.final_norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_proj = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 cfg.weight_attr(), bias_attr=False)

    def forward(self, hidden):
        return self.lm_proj(self.final_norm(hidden))


def next_token_loss(logits, batch):
    """Mean cross-entropy of position t's logits against token t+1, over
    rows × (seq - 1) positions, in float32."""
    ids = batch["input_ids"]
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
    return F.cross_entropy(logits.astype(jnp.float32), labels,
                           ignore_index=-100, reduction="mean")


def pretrain_model(cfg: DeepseekV3Config):
    """The model as `HybridPretrainer` takes it: the leading dense layers
    and the expert layers are two groups of uniform blocks."""
    from .pretrainer import PretrainModel

    def stack(n, expert):
        holder = Layer()
        holder.layers = nn.LayerList(
            [DeepseekV3Block(cfg, expert) for _ in range(n)])
        return holder

    dense = min(cfg.first_k_dense_replace, cfg.num_hidden_layers)
    # The layers' own initial values are drawn on the host: a trainer's
    # step binds the parameters it is given, `place_params` puts
    # `init_params()` on the mesh, and at this size (16 bytes a parameter
    # of training state) the device has no room for a second copy.
    with _host_device():
        groups = {}
        if dense:
            groups["dense_blocks"] = stack(dense, False)
        if cfg.num_hidden_layers > dense:
            groups["expert_blocks"] = stack(cfg.num_hidden_layers - dense,
                                            True)
        return PretrainModel(
            embeddings=DeepseekV3Embeddings(cfg), groups=groups,
            head=DeepseekV3LMHead(cfg), criterion=next_token_loss,
            embed_inputs=("input_ids",), token_keys=("input_ids",),
            config=cfg)


def _host_device():
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:        # this process was told to see no CPU backend
        return contextlib.nullcontext()
