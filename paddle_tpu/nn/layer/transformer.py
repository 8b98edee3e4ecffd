"""Transformer layers (ref: python/paddle/nn/layer/transformer.py, 1114 LoC —
MultiHeadAttention, TransformerEncoder/DecoderLayer, Transformer).

TPU-native: attention dispatches to the Pallas flash-attention kernel when
shapes/backend allow (ops/attention.py); projections are single fused matmuls
feeding the MXU; norm/residual math runs in float32 under bf16 params.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp

from ...ops import attention as attn_ops
from ...utils import xprof as _xprof
from .. import functional as F
from ..functional.norm import _fused_ln_shards
from .base import Layer, LayerList
from .common import Dropout, Linear
from .norm import LayerNorm


class MultiHeadAttention(Layer):
    """ref: transformer.py MultiHeadAttention — q/k/v/out projections +
    scaled-dot-product attention; supports self and cross attention and an
    incremental-decode Cache."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None, vdim=None,
                 need_weights=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.need_weights = need_weights
        kdim = kdim or embed_dim
        vdim = vdim or embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split_heads(self, x):
        b, s, _ = x.shape
        return x.reshape(b, s, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        if cache is None and not self.need_weights:
            # packed fast path: feed the projection outputs straight to the
            # kernel in (b, s, h*d) layout — no split/merge transposes (a
            # layout copy of q, k, v, o and of their gradients a layer)
            with jax.named_scope(_xprof.SCOPE_PROJ):
                qp = self.q_proj(query)
                kp = self.k_proj(key)
                vp = self.v_proj(value)
            with jax.named_scope(_xprof.ATTN_CORE):
                out = attn_ops.flash_attention_packed(
                    qp, kp, vp, self.num_heads, attn_mask=attn_mask,
                    dropout_p=self.dropout, training=self.training)
            if out is not None:
                with jax.named_scope(_xprof.SCOPE_PROJ):
                    return self.out_proj(out)
            with jax.named_scope(_xprof.SCOPE_PREP):
                q = self._split_heads(qp)
                k = self._split_heads(kp)
                v = self._split_heads(vp)
            return self._attend(q, k, v, attn_mask, None)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            q = self.q_proj(query)
        with jax.named_scope(_xprof.SCOPE_PREP):
            q = self._split_heads(q)
        if isinstance(cache, MultiHeadAttention.StaticCache):
            k, v = cache.k, cache.v
        else:
            with jax.named_scope(_xprof.SCOPE_PROJ):
                k = self.k_proj(key)
            with jax.named_scope(_xprof.SCOPE_PREP):
                k = self._split_heads(k)
            with jax.named_scope(_xprof.SCOPE_PROJ):
                v = self.v_proj(value)
            with jax.named_scope(_xprof.SCOPE_PREP):
                v = self._split_heads(v)
                if isinstance(cache, MultiHeadAttention.Cache):
                    k = jnp.concatenate([cache.k, k], axis=2)
                    v = jnp.concatenate([cache.v, v], axis=2)
                    cache = MultiHeadAttention.Cache(k, v)

        return self._attend(q, k, v, attn_mask, cache)

    def _attend(self, q, k, v, attn_mask, cache):
        with jax.named_scope(_xprof.ATTN_CORE):
            out, weights = self._attend_core(q, k, v, attn_mask)
        with jax.named_scope(_xprof.SCOPE_PREP):
            b, h, s, d = out.shape
            out = out.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        with jax.named_scope(_xprof.SCOPE_PROJ):
            out = self.out_proj(out)
        outs = (out,)
        if self.need_weights:
            outs += (weights,)
        if isinstance(cache, MultiHeadAttention.Cache):
            outs += (cache,)
        return outs if len(outs) > 1 else out

    def _attend_core(self, q, k, v, attn_mask):
        """scores -> softmax -> values on split heads: (out, weights)."""
        weights = None
        if self.need_weights:
            # explicit-weights path (flash kernel never materializes them)
            import math

            s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
            s_ = s_ / math.sqrt(self.head_dim)
            if attn_mask is not None:
                s_ = jnp.where(attn_mask, s_, -1e30) if attn_mask.dtype == jnp.bool_ \
                    else s_ + attn_mask.astype(jnp.float32)
            weights = jnp.exp(s_ - jnp.max(s_, axis=-1, keepdims=True))
            weights = (weights / jnp.sum(weights, axis=-1, keepdims=True)).astype(q.dtype)
            p = weights
            if self.dropout > 0.0 and self.training:
                # the reference applies attention dropout on this path too
                # (transformer.py MultiHeadAttention: F.dropout on weights)
                from ...nn import functional as _F

                p = _F.dropout(weights, p=self.dropout, training=True)
            out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        else:
            out = attn_ops.flash_attention(
                q, k, v, attn_mask=attn_mask, dropout_p=self.dropout,
                training=self.training)
        return out, weights

    def gen_cache(self, key, value=None, type=None):
        if type == MultiHeadAttention.StaticCache:
            k = self._split_heads(self.k_proj(key))
            v = self._split_heads(self.v_proj(value if value is not None else key))
            return MultiHeadAttention.StaticCache(k, v)
        b = key.shape[0]
        k = jnp.zeros((b, self.num_heads, 0, self.head_dim), key.dtype)
        return MultiHeadAttention.Cache(k, k)


@jax.named_scope(_xprof.REGION_LN)
def _sublayer_epilogue(layer, out, residual, norm, dropout_layer):
    """src = norm(residual + dropout(out)) — the post-LN sublayer tail
    shared by encoder AND decoder layers.  On TPU this dispatches to the
    fused Pallas kernel (one HBM pass per direction, in-kernel replayable
    dropout); elsewhere or for unsupported shapes it composes the
    reference chain."""
    from ...ops.pallas import layer_norm as _fln
    from ...parallel import mesh as _mesh

    rate = float(dropout_layer.p) if layer.training else 0.0
    n = 0
    if (not layer.normalize_before
            and norm.weight is not None and norm.bias is not None):
        n = _fused_ln_shards(out, tuple(norm.normalized_shape))
    if n:
        seed = attn_ops.draw_dropout_seed(n, rate)

        def kernel(x, res, seed, w, b):
            return _fln.fused_residual_dropout_layer_norm(
                x, res, w, b, dropout_rate=rate, seed=seed,
                epsilon=norm.epsilon)

        return _mesh.per_batch_shard(
            kernel, n, (out, residual, seed),
            (norm.weight.value, norm.bias.value))
    src = residual + dropout_layer(out)
    if not layer.normalize_before:
        src = norm(src)
    return src


@jax.named_scope(_xprof.REGION_LN)
def _pre_norm(norm, x):
    """The pre-LN sublayer head (``normalize_before``)."""
    return norm(x)


@jax.named_scope(_xprof.REGION_FFN)
def _ffn(layer, x):
    """Both products and the activation, encoder and decoder layers.  GELU
    takes the first product's float32 sum + bias and rounds once itself:
    XLA then evaluates value and derivative once, in that product's own
    epilogue (functional/activation.py, over the GELU rules)."""
    lin = layer.linear1
    if layer.activation is F.gelu and isinstance(lin, Linear):
        h = F.gelu(lin(x, out_dtype=jnp.float32), dtype=x.dtype)
    else:
        h = layer.activation(lin(x))
    return layer.linear2(layer.act_dropout(h))


class TransformerEncoderLayer(Layer):
    """ref: transformer.py TransformerEncoderLayer (normalize_before toggles
    pre-/post-LN)."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(
            d_model, nhead, dropout=attn_dropout if attn_dropout is not None else dropout,
            weight_attr=weight_attr, bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.act_dropout = Dropout(act_dropout if act_dropout is not None else dropout)
        self.activation = getattr(F, activation)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = _pre_norm(self.norm1, src)
        with jax.named_scope(_xprof.REGION_ATTN):
            if cache is None:
                out = self.self_attn(src, src, src, attn_mask=src_mask)
            else:
                out, cache = self.self_attn(src, src, src,
                                            attn_mask=src_mask, cache=cache)
        src = _sublayer_epilogue(self, out, residual, self.norm1,
                                 self.dropout1)
        residual = src
        if self.normalize_before:
            src = _pre_norm(self.norm2, src)
        src = _ffn(self, src)
        src = _sublayer_epilogue(self, src, residual, self.norm2,
                                 self.dropout2)
        return src if cache is None else (src, cache)


class TransformerEncoder(Layer):
    """``enable_recompute`` applies per-layer activation checkpointing
    (ref: RecomputeOptimizer fluid/optimizer.py:4513 with the encoder layers
    as the checkpoint variables; here each layer body is a jax.checkpoint
    region rematerialized during backward)."""

    def __init__(self, encoder_layer, num_layers, norm=None,
                 enable_recompute=False, recompute_policy=None):
        super().__init__()
        import copy

        self.layers = LayerList([encoder_layer] + [
            copy.deepcopy(encoder_layer) for _ in range(num_layers - 1)])
        # re-randomize the copies (deepcopy clones weights)
        for layer in list(self.layers)[1:]:
            _reinit(layer)
        self.num_layers = num_layers
        self.norm = norm
        self.enable_recompute = enable_recompute
        self.recompute_policy = recompute_policy

    def forward(self, src, src_mask=None, cache=None):
        from ...autograd import recompute as _recompute

        output = src
        new_caches = []
        remat = self.enable_recompute and self.training and cache is None
        for i, layer in enumerate(self.layers):
            if cache is None:
                if remat:
                    output = _recompute(
                        lambda x, m, _l=layer: _l(x, src_mask=m),
                        output, src_mask, policy=self.recompute_policy)
                else:
                    output = layer(output, src_mask=src_mask)
            else:
                output, c = layer(output, src_mask=src_mask, cache=cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.self_attn.gen_cache(src) for layer in self.layers]


class TransformerDecoderLayer(Layer):
    """ref: transformer.py TransformerDecoderLayer — self attn + cross attn +
    FFN."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        self.normalize_before = normalize_before
        ad = attn_dropout if attn_dropout is not None else dropout
        self.self_attn = MultiHeadAttention(d_model, nhead, dropout=ad,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, dropout=ad,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.act_dropout = Dropout(act_dropout if act_dropout is not None else dropout)
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = _pre_norm(self.norm1, tgt)
        with jax.named_scope(_xprof.REGION_ATTN):
            if cache is None:
                out = self.self_attn(tgt, tgt, tgt, attn_mask=tgt_mask)
            else:
                out, sc = self.self_attn(tgt, tgt, tgt, attn_mask=tgt_mask,
                                         cache=cache[0])
        tgt = _sublayer_epilogue(self, out, residual, self.norm1,
                                 self.dropout1)
        residual = tgt
        if self.normalize_before:
            tgt = _pre_norm(self.norm2, tgt)
        with jax.named_scope(_xprof.REGION_ATTN):
            out = self.cross_attn(
                tgt, memory, memory, attn_mask=memory_mask,
                cache=cache[1] if cache is not None and isinstance(
                    cache[1], MultiHeadAttention.StaticCache) else None)
        tgt = _sublayer_epilogue(self, out, residual, self.norm2,
                                 self.dropout2)
        residual = tgt
        if self.normalize_before:
            tgt = _pre_norm(self.norm3, tgt)
        tgt = _ffn(self, tgt)
        tgt = _sublayer_epilogue(self, tgt, residual, self.norm3,
                                 self.dropout3)
        return tgt if cache is None else (tgt, (sc, cache[1]))


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        import copy

        self.layers = LayerList([decoder_layer] + [
            copy.deepcopy(decoder_layer) for _ in range(num_layers - 1)])
        for layer in list(self.layers)[1:]:
            _reinit(layer)
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None, cache=None):
        output = tgt
        new_caches = []
        for i, layer in enumerate(self.layers):
            if cache is None:
                output = layer(output, memory, tgt_mask=tgt_mask,
                               memory_mask=memory_mask)
            else:
                output, c = layer(output, memory, tgt_mask=tgt_mask,
                                  memory_mask=memory_mask, cache=cache[i])
                new_caches.append(c)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)


class Transformer(Layer):
    """ref: transformer.py Transformer — full encoder-decoder."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            enc_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr)
            enc_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(enc_layer, num_encoder_layers,
                                              enc_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            dec_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr, bias_attr)
            dec_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(dec_layer, num_decoder_layers,
                                              dec_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None, memory_mask=None):
        memory = self.encoder(src, src_mask=src_mask)
        return self.decoder(tgt, memory, tgt_mask=tgt_mask,
                            memory_mask=memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length):
        """Additive causal mask (ref: transformer.py)."""
        return jnp.where(jnp.tril(jnp.ones((length, length), bool)), 0.0, -1e9)


def _reinit(layer):
    """Re-draw parameters of a deep-copied layer tree from each parameter's
    own recorded initializer, so a user-configured weight_attr distribution
    is preserved across the cloned stack."""
    from .. import initializer as init

    for p in layer.parameters():
        ini = getattr(p, "initializer", None)
        if ini is not None:
            p.value = ini(p.value.shape, p.value.dtype)
        elif p.value.ndim >= 2:
            p.value = init.XavierUniform()(p.value.shape, p.value.dtype)
