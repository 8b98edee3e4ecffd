"""Common layers: Linear, Embedding, Dropout, padding, upsample (ref:
python/paddle/nn/layer/common.py; fluid/dygraph/nn.py Linear:970,
Embedding:1453)."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import dtype as _dtype_mod
from .. import functional as F
from .. import initializer as init
from .base import Layer, Parameter


class Linear(Layer):
    """y = x W + b, W: (in_features, out_features) — ref layout (fc weight)."""

    def __init__(self, in_features, out_features, weight_attr=None, bias_attr=None,
                 name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        w_init = getattr(weight_attr, "initializer", None) or init.XavierUniform()
        self.weight = Parameter(w_init((in_features, out_features),
                                       _dtype_mod.get_default_dtype()),
                                name=f"{name or 'linear'}.w", initializer=w_init)
        if bias_attr is False:
            self.bias = None
        else:
            b_init = getattr(bias_attr, "initializer", None) or init.Constant(0.0)
            self.bias = Parameter(b_init((out_features,),
                                         _dtype_mod.get_default_dtype()),
                                  name=f"{name or 'linear'}.b", initializer=b_init)

    def forward(self, x, out_dtype=None):
        return F.linear(x, self.weight.value,
                        None if self.bias is None else self.bias.value,
                        out_dtype)

    def extra_repr(self):
        return f"in={self.in_features}, out={self.out_features}"


class Embedding(Layer):
    """ref: lookup_table_v2; nn/layer/common.py Embedding."""

    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.padding_idx = padding_idx
        self.sparse = sparse
        w_init = getattr(weight_attr, "initializer", None) or init.Normal(0.0, 1.0)
        self.weight = Parameter(w_init((num_embeddings, embedding_dim),
                                       _dtype_mod.get_default_dtype()),
                                name=f"{name or 'embedding'}.w", initializer=w_init)

    def forward(self, x):
        return F.embedding(x, self.weight.value, padding_idx=self.padding_idx)

    def extra_repr(self):
        return f"{self.num_embeddings}, {self.embedding_dim}"


class Dropout(Layer):
    def __init__(self, p=0.5, mode="upscale_in_train"):
        super().__init__()
        self.p = p
        self.mode = mode

    def forward(self, x):
        return F.dropout(x, p=self.p, training=self.training, mode=self.mode)

    def extra_repr(self):
        return f"p={self.p}"


class Dropout2D(Layer):
    def __init__(self, p=0.5):
        super().__init__()
        self.p = p

    def forward(self, x):
        return F.dropout2d(x, p=self.p, training=self.training)


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, x):
        from ... import ops

        return ops.flatten(x, self.start_axis, self.stop_axis)


class Pad2D(Layer):
    def __init__(self, padding, mode="constant", value=0.0, data_format="NCHW"):
        super().__init__()
        self.padding = padding
        self.mode = mode
        self.value = value
        self.data_format = data_format

    def forward(self, x):
        return F.pad(x, self.padding, mode=self.mode, value=self.value,
                     data_format=self.data_format)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.mode = mode
        self.align_corners = align_corners

    def forward(self, x):
        return F.interpolate(x, size=self.size, scale_factor=self.scale_factor,
                             mode=self.mode, align_corners=self.align_corners)
