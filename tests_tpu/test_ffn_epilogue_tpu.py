"""On the chip: what XLA:TPU makes of the transformer FFN's first product
and its GELU under lax.scan, at `ernie-base.s512`'s shapes (PERF.md §6,
PR 27).  `_ffn` hands GELU the product's float32 sum, and the compiler
answers with ONE product fusion per block that evaluates value and
derivative from one erf and one exp and writes the value and both stacked
residuals from its own epilogue.  The fusion pass decides that by its own
cost model (fed the bf16 result instead it re-evaluates erf in three
places); a jax/libtpu upgrade that decides otherwise is seen here, not in a
slower step.  Run `pytest tests_tpu/` on a TPU host."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from paddle_tpu import nn
from paddle_tpu.autograd import functional_call, parameters_dict
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.transformer import _ffn

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="reads the program XLA:TPU compiles for the attached chip")

BLOCKS, BATCH, SEQ, HIDDEN, FFN = 12, 64, 512, 768, 3072


class _Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.linear1 = nn.Linear(HIDDEN, FFN)
        self.linear2 = nn.Linear(FFN, HIDDEN)
        self.act_dropout = nn.Dropout(0.0)
        self.activation = F.gelu

    def forward(self, x):
        return x + _ffn(self, x)


def scanned_ffn():
    template = _Block()
    stacked = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct((BLOCKS,) + v.shape, jnp.bfloat16),
        parameters_dict(template))

    def loss(params, x):
        def body(h, blk):
            return functional_call(template, blk, (h,)).astype(h.dtype), None
        y, _ = lax.scan(body, x, params)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct((BATCH, SEQ, HIDDEN), jnp.bfloat16)
    return jax.jit(jax.value_and_grad(loss)), stacked, x


def fusions(text):
    """[(name, outputs, {opcode: count}, op_name)] of every fusion."""
    bodies, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = re.search(r" = \S+ ([\w\-]+)\(", line)
            if m:
                cur.append(m.group(1))
    out = []
    for line in text.splitlines():
        m = re.search(r"%?([\w.\-]+) = (.*?) fusion\(.*calls=%?([\w.\-]+)",
                      line)
        if not m:
            continue
        ops = bodies.get(m.group(3), [])
        where = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(1), m.group(2).count("["),
                    {o: ops.count(o) for o in set(ops)},
                    where.group(1) if where else ""))
    return out


def check(text):
    """The assertions, on a compiled program's text."""
    fs = fusions(text)
    with_erf = [f for f in fs if f[2].get("erf")]
    assert len(with_erf) == 1, [(f[0], f[3]) for f in with_erf]
    name, outputs, ops, where = with_erf[0]
    assert "ffn" in where and "transpose(" not in where, where
    # the first product itself, the activation in its epilogue, nothing of
    # erfc's expansion (its divides, selects and second exponential)
    assert ops.get("convolution") == 1, (name, ops)
    assert ops["erf"] == 1 and ops.get("exponential") == 1, (name, ops)
    assert not ops.get("divide") and not ops.get("select"), (name, ops)
    # the value for the second product and the two stacked residuals
    assert outputs == 3 and ops.get("dynamic-update-slice") == 2, (name, ops)
    # no other operation evaluates an activation: not the second product's
    # operand, not a pass of its own
    assert sum(f[2].get("exponential", 0) for f in fs) == 1
    products = [f for f in fs if f[2].get("convolution")
                and "transpose(" not in f[3] and "ffn" in f[3]]
    assert len(products) == 2, [(f[0], f[3]) for f in products]


def test_first_product_evaluates_gelu_once_in_its_epilogue():
    step, params, x = scanned_ffn()
    check(step.lower(params, x).compile().as_text())
