"""Static-op long tail, batch 2: collectives, RNN monoliths, fusion ops,
LoD-array/control ops, PS data-plane ops, and host-IO ops.

Reference parity targets: operators/collective/ (c_allreduce_sum & co),
lstm_op.cc / gru_op.cc / lstmp_op.cc / cudnn_lstm_op.cu, operators/fused/
(fusion_lstm, fusion_gru, fusion_repeated_fc_relu, fusion_squared_mat_sub,
fusion_seqpool_concat, fusion_seqconv_eltadd_relu, fused_embedding_fc_lstm),
tensor-array ops (tensor_array_read_write_op.cc, array_to_lod_tensor_op.cc,
shrink_rnn_memory_op.cc), merge/split_lod_tensor_op.cc, PS data-plane ops
(distributed_lookup_table_op.cc, operators/pscore pull/push_sparse),
save/load/print ops (save_op.cc, load_op.cc, print_op.cc, py_func_op.cc),
and the int8 quantize/dequantize pair (operators/mkldnn quantize_op.cc).

TPU-native design notes:
- collectives lower to jax.lax collectives when tracing inside a mapped
  context (the GSPMD/shard_map path the Executor's with_data_parallel
  uses) and degrade to identities on one device — the reference's NCCL
  rings are ICI here, and stream-sync ops are structurally unnecessary
  under XLA's dataflow ordering (documented per-op).
- RNN monolith ops run the recurrence as ONE lax.scan over time — the
  reference's hand-written CPU/GPU kernels collapse into a compiled loop
  whose per-step matmul hits the MXU.
- host-IO ops (save/print/push_sparse) use jax's ordered io_callback so
  side effects survive jit; load materializes at trace time (shapes must
  be static anyway).  Callbacks need PJRT host send/recv, which the CPU
  and TPU runtimes both have: verified on the CPU backend in
  tests/test_ops_tail2.py and on the chip in
  tests_tpu/test_host_callbacks_tpu.py.
- tensor arrays: the executor's var env can hold a python LIST of arrays
  (static length under trace); read/write need a trace-time-constant
  index — dynamic-index array reads belong to the StaticRNN collapse
  (SURVEY §1 L4 mapping), and the rule says so when violated.
"""
from __future__ import annotations



import jax
import jax.numpy as jnp
import numpy as np

from ..nn import functional as F
from .registry import register_op


def _one(ins, slot):
    vs = ins.get(slot, [])
    return vs[0] if vs else None


# =========================================================================
# collective ops (ref operators/collective/c_*.cc)
# =========================================================================

def _data_axis():
    from ..parallel import collective as _coll

    return _coll.bound_data_axis()


def _c_allreduce(reduce_fn, summing=False):
    def rule(ins, attrs, op):
        x = _one(ins, "X")
        axis = _data_axis()
        if axis is None:
            return {"Out": [x]}
        if summing:
            # sum allreduce honors ambient comm options (ShardingPlan /
            # comm_scope: quantized payload, hierarchical schedule) or an
            # explicit `compress` op attr; other reductions stay exact
            from ..parallel import compress as _compress

            kind = attrs.get("compress") or None
            opts = _compress.current_comm()
            if kind is None and opts is not None:
                kind = opts.payload()
            if kind:
                return {"Out": [_compress.optimized_all_reduce(
                    x, axis, compress=kind,
                    block_size=opts.block_size if opts else 256,
                    hierarchy=opts.hierarchy if opts else "auto")]}
        return {"Out": [reduce_fn(x, axis)]}

    return rule


register_op("c_allreduce_sum")(_c_allreduce(jax.lax.psum, summing=True))
register_op("c_allreduce_max")(_c_allreduce(jax.lax.pmax))
register_op("c_allreduce_min")(_c_allreduce(jax.lax.pmin))
register_op("c_allreduce_prod")(_c_allreduce(
    # NOT exp(psum(log)): negatives must keep their sign
    lambda x, ax: jnp.prod(jax.lax.all_gather(x, ax), axis=0)))


@register_op("c_allgather")
def _c_allgather(ins, attrs, op):
    x = _one(ins, "X")
    axis = _data_axis()
    if axis is None:
        return {"Out": [x]}
    g = jax.lax.all_gather(x, axis)          # (n, ...) leading device dim
    return {"Out": [g.reshape((-1,) + x.shape[1:])]}


@register_op("c_reducescatter")
def _c_reducescatter(ins, attrs, op):
    x = _one(ins, "X")
    axis = _data_axis()
    if axis is None:
        return {"Out": [x]}
    return {"Out": [jax.lax.psum_scatter(x, axis, scatter_dimension=0,
                                         tiled=True)]}


@register_op("c_broadcast")
def _c_broadcast(ins, attrs, op):
    x = _one(ins, "X")
    axis = _data_axis()
    if axis is None:
        return {"Out": [x]}
    # broadcast from root: take root's value on every member
    src = attrs.get("root", 0)
    idx = jax.lax.axis_index(axis)
    return {"Out": [jax.lax.psum(
        jnp.where(idx == src, x, jnp.zeros_like(x)), axis)]}


def _comm_noop_rule(why):
    def rule(ins, attrs, op):
        # identity pass-through; the reference op exists to manage NCCL
        # communicators/streams, which XLA's dataflow ordering + the mesh
        # runtime own here (SURVEY N21/N22 mapping): {why}
        del attrs, op
        xs = ins.get("X", [])
        return {"Out": list(xs)} if xs else {}

    rule.__doc__ = why
    return rule


for _name, _why in [
        ("c_comm_init", "communicator creation = jax mesh/distributed init"),
        ("c_comm_init_all", "same; all-rank init is the mesh constructor"),
        ("c_gen_nccl_id", "no NCCL id exchange: ICI topology is static"),
        ("c_sync_calc_stream", "XLA orders compute by dataflow, no streams"),
        ("c_sync_comm_stream", "collectives are dataflow-ordered too"),
        ("gen_nccl_id", "legacy alias of c_gen_nccl_id")]:
    register_op(_name)(_comm_noop_rule(_why))


@register_op("sync_batch_norm")
def _sync_batch_norm(ins, attrs, op):
    """ref sync_batch_norm_op.cu: BN statistics averaged across the data
    axis; degrades to plain BN on one device."""
    x = _one(ins, "X")
    axis = _data_axis()
    training = not attrs.get("is_test", False)
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    if axis is None or not training:
        out, new_rm, new_rv = F.batch_norm(
            x, _one(ins, "Mean"), _one(ins, "Variance"),
            weight=_one(ins, "Scale"), bias=_one(ins, "Bias"),
            training=training, momentum=momentum, epsilon=eps)
        return {"Y": [out], "MeanOut": [new_rm], "VarianceOut": [new_rv]}
    red = (0,) + tuple(range(2, x.ndim))
    shape = [1, -1] + [1] * (x.ndim - 2)
    mean = jax.lax.pmean(jnp.mean(x, axis=red), axis)
    mean_sq = jax.lax.pmean(jnp.mean(jnp.square(x), axis=red), axis)
    var = mean_sq - jnp.square(mean)
    out = (x - mean.reshape(shape)) / jnp.sqrt(var.reshape(shape) + eps)
    scale, bias = _one(ins, "Scale"), _one(ins, "Bias")
    if scale is not None:
        out = out * scale.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    rm, rv = _one(ins, "Mean"), _one(ins, "Variance")
    return {"Y": [out],
            "MeanOut": [momentum * rm + (1 - momentum) * mean],
            "VarianceOut": [momentum * rv + (1 - momentum) * var]}


# =========================================================================
# RNN monolith ops (ref lstm_op.cc, gru_op.cc, lstmp_op.cc, cudnn_lstm,
# fused/fusion_lstm.cc, fusion_gru.cc, fused_embedding_fc_lstm_op.cc)
# — dense (B, T, ...) layout, ONE lax.scan over time
# =========================================================================

def _sig(v):
    return jax.nn.sigmoid(v)


def _lstm_scan(gates_x, w_h, bias, h0, c0, mask=None, proj=None):
    """gates_x: (B, T, 4H) pre-projected inputs; returns (h_seq, c_seq)."""
    B, T, H4 = gates_x.shape
    H = H4 // 4

    def step(carry, t_in):
        h, c = carry
        xt, mt = t_in
        g = xt + h @ w_h + (bias if bias is not None else 0.0)
        i, f, gg, o = jnp.split(g, 4, axis=-1)
        c_new = _sig(f) * c + _sig(i) * jnp.tanh(gg)
        h_new = _sig(o) * jnp.tanh(c_new)
        if proj is not None:
            h_new = h_new @ proj
        if mt is not None:
            h_new = h_new * mt + h * (1 - mt)
            c_new = c_new * mt + c * (1 - mt)
        return (h_new, c_new), (h_new, c_new)

    xs = jnp.swapaxes(gates_x, 0, 1)  # (T, B, 4H)
    ms = (jnp.swapaxes(mask, 0, 1)[..., None]
          if mask is not None else jnp.ones((T, 1, 1), gates_x.dtype))
    (_, _), (hs, cs) = jax.lax.scan(step, (h0, c0), (xs, ms))
    return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1)


@register_op("lstm")
def _lstm_op(ins, attrs, op):
    """ref lstm_op.cc (padded layout): Input (B,T,4H) pre-gates, Weight
    (H,4H), Bias (4H) [+ optional (B,T) Mask] -> Hidden/Cell (B,T,H)."""
    x = _one(ins, "Input")
    w = _one(ins, "Weight")
    b = _one(ins, "Bias")
    mask = _one(ins, "Mask")
    B, T, H4 = x.shape
    H = H4 // 4
    h0 = _one(ins, "H0")
    c0 = _one(ins, "C0")
    if h0 is None:
        h0 = jnp.zeros((B, H), x.dtype)
    if c0 is None:
        c0 = jnp.zeros((B, H), x.dtype)
    hs, cs = _lstm_scan(x, w, b, h0, c0, mask)
    return {"Hidden": [hs], "Cell": [cs]}


@register_op("lstmp")
def _lstmp_op(ins, attrs, op):
    """ref lstmp_op.cc: LSTM with a recurrent projection — the projected
    state (B,T,P) is the recurrent input and the output."""
    x = _one(ins, "Input")          # (B, T, 4H)
    w = _one(ins, "Weight")         # (P, 4H)
    proj = _one(ins, "ProjWeight")  # (H, P)
    b = _one(ins, "Bias")
    mask = _one(ins, "Mask")
    B, T, H4 = x.shape
    H = H4 // 4
    P = proj.shape[1]
    h0 = jnp.zeros((B, P), x.dtype)
    c0 = jnp.zeros((B, H), x.dtype)
    hs, cs = _lstm_scan(x, w, b, h0, c0, mask, proj=proj)
    return {"Projection": [hs], "Cell": [cs]}


@register_op("cudnn_lstm")
def _cudnn_lstm_op(ins, attrs, op):
    """ref cudnn_lstm_op.cu: time-major (T,B,I) input with packed weights;
    single layer, unidirectional subset (the multi-layer/bidir config is a
    stack of this rule).  W packs [Wx (I,4H); Wh (H,4H); b (4H)]."""
    x = _one(ins, "Input")   # (T, B, I)
    w = _one(ins, "W")
    hidden_size = attrs["hidden_size"]
    T, B, inp = x.shape
    H = hidden_size
    wx = w[:inp * 4 * H].reshape(inp, 4 * H)
    wh = w[inp * 4 * H:(inp + H) * 4 * H].reshape(H, 4 * H)
    b = w[(inp + H) * 4 * H:(inp + H) * 4 * H + 4 * H]
    gates = jnp.einsum("tbi,ih->tbh", x, wx)
    hs, cs = _lstm_scan(jnp.swapaxes(gates, 0, 1), wh, b,
                        jnp.zeros((B, H), x.dtype),
                        jnp.zeros((B, H), x.dtype))
    return {"Out": [jnp.swapaxes(hs, 0, 1)],
            "LastH": [hs[:, -1]], "LastC": [cs[:, -1]]}


def _gru_scan(gates_x, w_h, h0, mask=None):
    """gates_x (B,T,3H) pre-projected; w_h (H,3H): [:, :2H] update/reset,
    [:, 2H:] candidate (ref gru_unit_op.h layout)."""
    B, T, H3 = gates_x.shape
    H = H3 // 3

    def step(h, t_in):
        xt, mt = t_in
        uh = h @ w_h[:, :2 * H]
        r = _sig(xt[:, :H] + uh[:, :H])
        z = _sig(xt[:, H:2 * H] + uh[:, H:])
        c = jnp.tanh(xt[:, 2 * H:] + (r * h) @ w_h[:, 2 * H:])
        h_new = z * h + (1 - z) * c
        if mt is not None:
            h_new = h_new * mt + h * (1 - mt)
        return h_new, h_new

    xs = jnp.swapaxes(gates_x, 0, 1)
    ms = (jnp.swapaxes(mask, 0, 1)[..., None]
          if mask is not None else jnp.ones((T, 1, 1), gates_x.dtype))
    _, hs = jax.lax.scan(step, h0, (xs, ms))
    return jnp.swapaxes(hs, 0, 1)


@register_op("gru")
def _gru_op(ins, attrs, op):
    """ref gru_op.cc (padded): Input (B,T,3H), Weight (H,3H), Bias (3H)."""
    x = _one(ins, "Input")
    w = _one(ins, "Weight")
    b = _one(ins, "Bias")
    mask = _one(ins, "Mask")
    if b is not None:
        x = x + b
    B, T, H3 = x.shape
    H = H3 // 3
    h0 = _one(ins, "H0")
    if h0 is None:
        h0 = jnp.zeros((B, H), x.dtype)
    hs = _gru_scan(x, w, h0, mask)
    return {"Hidden": [hs]}


@register_op("fusion_lstm")
def _fusion_lstm_op(ins, attrs, op):
    """ref fused/fusion_lstm_op.cc: X (B,T,M) @ WeightX (M,4H) + lstm —
    the input projection and recurrence in one op."""
    x = _one(ins, "X")
    wx = _one(ins, "WeightX")
    wh = _one(ins, "WeightH")
    b = _one(ins, "Bias")
    mask = _one(ins, "Mask")
    B, T, _ = x.shape
    H = wh.shape[0]
    gates = jnp.einsum("btm,mh->bth", x, wx)
    hs, cs = _lstm_scan(gates, wh, b, jnp.zeros((B, H), x.dtype),
                        jnp.zeros((B, H), x.dtype), mask)
    return {"Hidden": [hs], "Cell": [cs]}


@register_op("fusion_gru")
def _fusion_gru_op(ins, attrs, op):
    """ref fused/fusion_gru_op.cc: X @ WeightX then the GRU recurrence."""
    x = _one(ins, "X")
    wx = _one(ins, "WeightX")
    wh = _one(ins, "WeightH")
    b = _one(ins, "Bias")
    mask = _one(ins, "Mask")
    B, T, _ = x.shape
    H = wh.shape[0]
    gates = jnp.einsum("btm,mh->bth", x, wx)
    if b is not None:
        gates = gates + b
    hs = _gru_scan(gates, wh, jnp.zeros((B, H), x.dtype), mask)
    return {"Hidden": [hs]}


@register_op("fused_embedding_fc_lstm")
def _fused_embedding_fc_lstm_op(ins, attrs, op):
    """ref fused_embedding_fc_lstm_op.cc: ids -> embedding (the fc is
    folded into the embedding table) -> lstm."""
    ids = _one(ins, "Ids")          # (B, T) int
    emb = _one(ins, "Embeddings")   # (V, 4H) pre-projected rows
    wh = _one(ins, "WeightH")
    b = _one(ins, "Bias")
    gates = jnp.take(emb, ids.astype(jnp.int32), axis=0)  # (B,T,4H)
    B = gates.shape[0]
    H = wh.shape[0]
    hs, cs = _lstm_scan(gates, wh, b, jnp.zeros((B, H), gates.dtype),
                        jnp.zeros((B, H), gates.dtype))
    return {"Hidden": [hs], "Cell": [cs]}


# =========================================================================
# fusion ops (ref operators/fused/)
# =========================================================================

@register_op("fusion_repeated_fc_relu")
def _fusion_repeated_fc_relu(ins, attrs, op):
    """ref fusion_repeated_fc_relu_op.cc: x -> [fc -> relu]*N."""
    x = _one(ins, "X")
    for w, b in zip(ins["W"], ins["Bias"]):
        x = jax.nn.relu(x @ w + b)
    return {"Out": [x]}


@register_op("fusion_squared_mat_sub")
def _fusion_squared_mat_sub(ins, attrs, op):
    """ref fusion_squared_mat_sub_op.cc: scalar * ((x@y)^2 - x^2@y^2)."""
    x, y = _one(ins, "X"), _one(ins, "Y")
    s = attrs.get("scalar", 1.0)
    xy = x @ y
    return {"Out": [s * (xy * xy - (x * x) @ (y * y))]}


@register_op("fusion_seqpool_concat")
def _fusion_seqpool_concat(ins, attrs, op):
    """ref fusion_seqpool_concat_op.cc: per-input sequence_pool (padded
    (B,T,D) + shared Length) then feature concat."""
    from ..ops import sequence as S

    length = _one(ins, "Length")
    ptype = attrs.get("pooltype", "SUM").lower()
    pooled = [S.sequence_pool(x, length, pool_type=ptype)
              for x in ins["X"]]
    return {"Out": [jnp.concatenate(pooled, axis=-1)]}


@register_op("fusion_seqconv_eltadd_relu")
def _fusion_seqconv_eltadd_relu(ins, attrs, op):
    """ref fusion_seqconv_eltadd_relu_op.cc: sequence_conv + bias + relu
    over the padded layout."""
    from ..ops import misc as M

    out = M.sequence_conv(_one(ins, "X"), _one(ins, "Filter"),
                          lengths=_one(ins, "Length"),
                          context_length=attrs["contextLength"],
                          context_start=attrs.get("contextStart"))
    return {"Out": [jax.nn.relu(out + _one(ins, "Bias"))]}


@register_op("fsp")
def _fsp(ins, attrs, op):
    """ref fsp_op.cc (knowledge distillation): normalized gram matrix
    between two feature maps, (B, C1, C2)."""
    x, y = _one(ins, "X"), _one(ins, "Y")
    B, C1 = x.shape[0], x.shape[1]
    C2 = y.shape[1]
    hw = x.shape[2] * x.shape[3]
    g = jnp.einsum("bchw,bdhw->bcd", x, y) / hw
    return {"Out": [g.reshape(B, C1, C2)]}


@register_op("inplace_abn")
def _inplace_abn(ins, attrs, op):
    """ref inplace_abn_op.cc: batch_norm + activation (the in-place memory
    trick is XLA's buffer assignment problem, not ours)."""
    training = not attrs.get("is_test", False)
    out, new_rm, new_rv = F.batch_norm(
        _one(ins, "X"), _one(ins, "Mean"), _one(ins, "Variance"),
        weight=_one(ins, "Scale"), bias=_one(ins, "Bias"),
        training=training, momentum=attrs.get("momentum", 0.9),
        epsilon=attrs.get("epsilon", 1e-5))
    act = attrs.get("activation", "identity")
    if act == "leaky_relu":
        out = jax.nn.leaky_relu(out, attrs.get("alpha", 0.01))
    elif act == "elu":
        out = jax.nn.elu(out, attrs.get("alpha", 1.0))
    elif act != "identity":
        out = getattr(jax.nn, act)(out)
    return {"Y": [out], "MeanOut": [new_rm], "VarianceOut": [new_rv]}


# =========================================================================
# pooling tails: max_pool3d_with_index, unpool
# =========================================================================

@register_op("max_pool3d_with_index")
def _max_pool3d_with_index(ins, attrs, op):
    x = _one(ins, "X")
    ks = tuple(attrs["ksize"])
    st = tuple(attrs.get("strides", ks))
    N, C, D, H, W = x.shape
    kd, kh, kw = ks
    sd, sh, sw = st
    od, oh, ow = (D - kd) // sd + 1, (H - kh) // sh + 1, (W - kw) // sw + 1
    # patch-extract view then argmax per window (flat index in the volume)
    patches = jnp.stack([
        x[:, :, i * sd:i * sd + kd, j * sh:j * sh + kh, k * sw:k * sw + kw]
        .reshape(N, C, -1)
        for i in range(od) for j in range(oh) for k in range(ow)], axis=2)
    out = patches.max(axis=-1).reshape(N, C, od, oh, ow)
    arg = patches.argmax(axis=-1).reshape(N, C, od, oh, ow)
    # convert window-local argmax to the global flat D*H*W index
    li = jnp.arange(od)[:, None, None] * sd
    lj = jnp.arange(oh)[None, :, None] * sh
    lk = jnp.arange(ow)[None, None, :] * sw
    wd = arg // (kh * kw)
    wh_ = (arg // kw) % kh
    wk = arg % kw
    gidx = ((li + wd) * H + (lj + wh_)) * W + (lk + wk)
    return {"Out": [out], "Mask": [gidx.astype(jnp.int32)]}


@register_op("unpool")
def _unpool(ins, attrs, op):
    """ref unpool_op.cc: scatter pooled values back to the argmax
    positions recorded by max_pool2d_with_index."""
    x = _one(ins, "X")          # (N, C, oh, ow)
    idx = _one(ins, "Indices")  # flat H*W indices
    H, W = attrs["unpool_size"] if "unpool_size" in attrs else (
        attrs["output_size"][0], attrs["output_size"][1])
    N, C = x.shape[0], x.shape[1]
    flat = jnp.zeros((N, C, H * W), x.dtype)
    out = flat.at[
        jnp.arange(N)[:, None, None], jnp.arange(C)[None, :, None],
        idx.reshape(N, C, -1)].add(x.reshape(N, C, -1))
    return {"Out": [out.reshape(N, C, H, W)]}


# =========================================================================
# tensor-array / LoD control ops (ref tensor_array_read_write_op.cc,
# array_to_lod_tensor_op.cc, shrink_rnn_memory_op.cc,
# merge/split_lod_tensor_op.cc)
# =========================================================================

def _static_index(i, what, op=None, attrs=None):
    """Tensor-array indices must be program-level constants.  Under the
    whole-program jit even a fill_constant value arrives as a tracer, so
    the rule constant-propagates from the producing op in the block (or
    an explicit ``index`` attr); a data-dependent index is structurally
    impossible (dynamic-length arrays cannot exist under jit —
    recurrences belong to StaticRNN/lax.scan, SURVEY §1 L4)."""
    if attrs is not None and "index" in attrs:
        return int(attrs["index"])
    if not isinstance(i, jax.core.Tracer):
        return int(np.asarray(i).reshape(-1)[0])
    if op is not None:
        iname = op.inputs.get("I", [None])[0]
        for prior in op.block.ops:
            if iname in prior.output_names():
                if prior.type == "fill_constant":
                    return int(prior.attrs.get("value", 0))
                break
    raise ValueError(
        f"{what} needs a program-constant index (fill_constant or the "
        "'index' attr): dynamic-length tensor arrays cannot exist under "
        "whole-program jit — recurrences belong to StaticRNN/lax.scan "
        "(SURVEY §1 L4)")


@register_op("write_to_array")
def _write_to_array(ins, attrs, op):
    i = _static_index(_one(ins, "I"), "write_to_array", op, attrs)
    arr = list(ins.get("Array", [None])[0] or []) \
        if ins.get("Array") else []
    while len(arr) <= i:
        arr.append(None)
    arr[i] = _one(ins, "X")
    return {"Out": [arr]}


@register_op("read_from_array")
def _read_from_array(ins, attrs, op):
    i = _static_index(_one(ins, "I"), "read_from_array", op, attrs)
    arr = _one(ins, "X")
    return {"Out": [arr[i]]}


@register_op("array_to_lod_tensor")
def _array_to_lod_tensor(ins, attrs, op):
    """Stack the time-step list back into a padded (T, ...) tensor (dense
    analogue of the LoD re-assembly)."""
    arr = _one(ins, "X")
    return {"Out": [jnp.stack(list(arr), axis=0)]}


@register_op("lod_tensor_to_array")
def _lod_tensor_to_array(ins, attrs, op):
    x = _one(ins, "X")
    return {"Out": [[x[t] for t in range(x.shape[0])]]}


@register_op("shrink_rnn_memory")
def _shrink_rnn_memory(ins, attrs, op):
    """ref shrink_rnn_memory_op.cc: in the dense layout every sequence is
    padded to the same length, so the memory never shrinks — identity,
    with masking handled by the recurrence itself."""
    return {"Out": [_one(ins, "X")]}


@register_op("merge_lod_tensor")
def _merge_lod_tensor(ins, attrs, op):
    """ref merge_lod_tensor_op.cc (IfElse runtime): rows from InTrue where
    Mask else InFalse."""
    mask = _one(ins, "Mask").reshape(-1).astype(bool)
    t, f = _one(ins, "InTrue"), _one(ins, "InFalse")
    shape = (-1,) + (1,) * (t.ndim - 1)
    return {"Out": [jnp.where(mask.reshape(shape), t, f)]}


@register_op("split_lod_tensor")
def _split_lod_tensor(ins, attrs, op):
    """ref split_lod_tensor_op.cc: dense analogue — both branches get the
    full batch with non-selected rows zeroed (static shapes; the IfElse
    merge re-selects by the same mask)."""
    x = _one(ins, "X")
    mask = _one(ins, "Mask").reshape(-1).astype(bool)
    shape = (-1,) + (1,) * (x.ndim - 1)
    m = mask.reshape(shape)
    return {"OutTrue": [jnp.where(m, x, 0)],
            "OutFalse": [jnp.where(m, 0, x)]}


# =========================================================================
# PS data-plane ops (ref distributed_lookup_table_op.cc, pscore
# pull_sparse/push_sparse) — host SparseTable reached via io_callback
# =========================================================================

_PS_TABLES = {}


def register_ps_table(name: str, table) -> None:
    """Bind a SparseTable/RemoteSparseTable for the PS data-plane ops."""
    _PS_TABLES[name] = table


def _table(attrs):
    name = attrs.get("table_name", attrs.get("table_id", "default"))
    try:
        return _PS_TABLES[str(name)]
    except KeyError:
        raise ValueError(
            f"PS table {name!r} not registered; call "
            "static.ops_tail2.register_ps_table(name, table) first"
        ) from None


def _pull_rule(ins, attrs, op):
    """Embedding rows fetched from the host/remote table mid-program:
    jax.pure_callback crosses from the jitted program to the PS client
    (the reference's RPC pull)."""
    ids = _one(ins, "Ids")
    table = _table(attrs)
    dim = int(table.dim)

    def host_pull(ids_np):
        return table.pull(np.asarray(ids_np).reshape(-1)).astype(np.float32)

    flat = ids.reshape(-1)
    rows = jax.pure_callback(
        host_pull,
        jax.ShapeDtypeStruct((flat.shape[0], dim), jnp.float32), flat)
    return {"Outputs" if "Outputs" in op.outputs else "Out":
            [rows.reshape(ids.shape + (dim,))]}


def _push_rule(ins, attrs, op):
    from jax.experimental import io_callback

    ids = _one(ins, "Ids")
    grads = _one(ins, "Grads" if ins.get("Grads") else "X")
    table = _table(attrs)
    lr = attrs.get("lr", 0.1)

    def host_push(ids_np, g_np):
        table.push(np.asarray(ids_np).reshape(-1),
                   np.asarray(g_np, np.float32), float(lr))
        return np.zeros((), np.int32)

    tok = io_callback(host_push, jax.ShapeDtypeStruct((), jnp.int32),
                      ids.reshape(-1),
                      grads.reshape(-1, grads.shape[-1]), ordered=True)
    return {"Out": [tok]} if "Out" in op.outputs else {}


for _name in ("distributed_lookup_table", "pull_sparse", "pull_sparse_v2"):
    register_op(_name)(_pull_rule)
for _name in ("push_sparse", "push_sparse_v2"):
    register_op(_name)(_push_rule)


@register_op("c_embedding")
def _c_embedding(ins, attrs, op):
    """ref collective c_embedding_op.cc: W is one vocab *partition* whose
    global offset is ``start_index``; out-of-partition ids yield zero rows
    and the caller allreduces partial results across the model group (the
    manual Megatron-style layout; the automatic path is
    ShardingPlan(embedding_shard=...) over the whole table)."""
    ids = _one(ins, "Ids")
    w = _one(ins, "W")
    start = int(attrs.get("start_index", 0))
    rows_per = int(w.shape[0])
    flat = ids.reshape(-1).astype(jnp.int32)
    local = flat - start
    mine = (local >= 0) & (local < rows_per)
    rows = jnp.take(w, jnp.clip(local, 0, rows_per - 1), axis=0)
    rows = jnp.where(mine[:, None], rows, jnp.zeros((), w.dtype))
    return {"Out": [rows.reshape(tuple(ids.shape) + (int(w.shape[-1]),))]}


@register_op("merge_ids")
def _merge_ids(ins, attrs, op):
    """ref merge_ids_op.cc: reassemble rows pulled per-shard back into the
    original id order."""
    # dense re-scope pairing split_ids: every shard carries the FULL
    # position-aligned vector with -1 where it does not own the slot, and
    # rows computed for the slots it owns; merging is a mask-select per
    # position (no scatter, no dynamic shapes)
    out = jnp.zeros_like(ins["X"][0])
    for ids_s, rows_s in zip(ins["Ids"], ins["X"]):
        mask = (ids_s.reshape(-1) >= 0)
        out = jnp.where(mask.reshape((-1,) + (1,) * (out.ndim - 1)),
                        rows_s, out)
    return {"Out": [out]}


@register_op("split_ids")
def _split_ids(ins, attrs, op):
    """ref split_ids_op.cc: route ids to N shards by id % N.  Static
    shapes: each shard gets the full-length vector with non-owned slots
    filled by -1 (the dense analogue of the reference's variable-length
    splits)."""
    ids = _one(ins, "Ids").reshape(-1)
    n = len(op.outputs["Out"])
    outs = [jnp.where(ids % n == s, ids, -1) for s in range(n)]
    return {"Out": outs}


@register_op("split_selected_rows")
def _split_selected_rows(ins, attrs, op):
    """Dense SelectedRows split: rows routed by height_sections."""
    x = _one(ins, "X")
    sections = attrs["height_sections"]
    outs, start = [], 0
    for h in sections:
        outs.append(x[start:start + h])
        start += h
    return {"Out": outs}


@register_op("split_byref")
def _split_byref(ins, attrs, op):
    x = _one(ins, "X")
    n = len(op.outputs["Out"])
    return {"Out": list(jnp.split(x, n, axis=0))}


@register_op("lookup_sparse_table_merge")
def _lookup_sparse_table_merge(ins, attrs, op):
    """ref lookup_sparse_table_merge_op.cc: union of id sets (dense:
    concat + unique via sort, padded with -1)."""
    ids = jnp.concatenate([x.reshape(-1) for x in ins["X"]])
    s = jnp.sort(ids)
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    return {"Out": [jnp.where(first, s, -1)]}


# =========================================================================
# host-IO ops (ref save_op.cc, load_op.cc, save_combine_op.cc,
# load_combine_op.cc, print_op.cc, py_func_op.cc)
# =========================================================================

@register_op("save")
def _save_op(ins, attrs, op):
    from jax.experimental import io_callback

    path = attrs["file_path"]
    x = _one(ins, "X")

    def host_save(arr):
        import os as _os

        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "wb") as f:  # exact path: np.save(str) appends .npy
            np.save(f, np.asarray(arr))
        return np.zeros((), np.int32)

    io_callback(host_save, jax.ShapeDtypeStruct((), jnp.int32), x,
                ordered=True)
    return {}


@register_op("save_combine")
def _save_combine_op(ins, attrs, op):
    from jax.experimental import io_callback

    path = attrs["file_path"]
    names = [str(n) for n in op.inputs["X"]]

    def host_save(*arrs):
        import os as _os

        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "wb") as f:  # exact path: np.savez(str) appends .npz
            np.savez(f, **{n: np.asarray(a) for n, a in zip(names, arrs)})
        return np.zeros((), np.int32)

    io_callback(host_save, jax.ShapeDtypeStruct((), jnp.int32),
                *ins["X"], ordered=True)
    return {}


@register_op("load")
def _load_op(ins, attrs, op):
    # shapes must be static under jit, so the file materializes at TRACE
    # time as a constant (the executor re-traces when the program changes)
    return {"Out": [jnp.asarray(np.load(attrs["file_path"]))]}


@register_op("load_combine")
def _load_combine_op(ins, attrs, op):
    data = np.load(attrs["file_path"])
    names = [str(n) for n in op.outputs["Out"]]
    return {"Out": [jnp.asarray(data[n]) for n in names]}


@register_op("print")
def _print_op(ins, attrs, op):
    from jax.experimental import io_callback

    x = _one(ins, "In")
    msg = attrs.get("message", "")

    def host_print(arr):
        print(f"{msg}{np.asarray(arr)}")
        return np.zeros((), np.int32)

    io_callback(host_print, jax.ShapeDtypeStruct((), jnp.int32), x,
                ordered=True)
    return {"Out": [x]}


_PY_FUNCS = {}


def register_py_func(fid: int, fn) -> None:
    """ref py_func_op.cc's python-callable registry."""
    _PY_FUNCS[int(fid)] = fn


@register_op("py_func")
def _py_func_op(ins, attrs, op):
    fn = _PY_FUNCS[int(attrs["forward_callable_id"])]
    out_shapes = attrs["out_shapes"]
    out_dtypes = attrs.get("out_dtypes", ["float32"] * len(out_shapes))
    def call_fn(*a):
        r = fn(*a)
        if not isinstance(r, (tuple, list)):
            r = (r,)
        return tuple(np.asarray(v) for v in r)

    results = jax.pure_callback(
        call_fn,
        tuple(jax.ShapeDtypeStruct(tuple(sh), np.dtype(d))
              for sh, d in zip(out_shapes, out_dtypes)),
        *ins.get("X", []))
    return {"Out": list(results)}


# =========================================================================
# int8 quantize/dequantize pair (ref mkldnn quantize_op.cc — the int8
# deployment data path; requantize rescales between int8 domains)
# =========================================================================

@register_op("quantize")
def _quantize_op(ins, attrs, op):
    x = _one(ins, "Input")
    scale = attrs.get("Scale", attrs.get("scale", 1.0))
    return {"Output": [jnp.clip(jnp.round(x * scale), -128, 127)
                       .astype(jnp.int8)]}


@register_op("dequantize")
def _dequantize_op(ins, attrs, op):
    x = _one(ins, "Input")
    scale = attrs.get("Scale", attrs.get("scale", 1.0))
    return {"Output": [x.astype(jnp.float32) / scale]}


@register_op("requantize")
def _requantize_op(ins, attrs, op):
    x = _one(ins, "Input")
    s_in = attrs.get("Scale_in", 1.0)
    s_out = attrs.get("Scale_out", 1.0)
    return {"Output": [jnp.clip(
        jnp.round(x.astype(jnp.float32) / s_in * s_out), -128, 127)
        .astype(jnp.int8)]}


@register_op("cross_entropy2")
def _cross_entropy2(ins, attrs, op):
    """ref cross_entropy_op2.cc: hard-label CE over PROBABILITIES with the
    intermediate XShape/MatchX the paired grad kernel wants."""
    x = _one(ins, "X")
    label = _one(ins, "Label").reshape(x.shape[:-1]).astype(jnp.int32)
    ignore = attrs.get("ignore_index", -100)
    match = jnp.take_along_axis(x, label[..., None], axis=-1)
    loss = -jnp.log(jnp.clip(match, 1e-12, None))
    loss = jnp.where(label[..., None] == ignore, 0.0, loss)
    return {"Y": [loss], "MatchX": [match], "XShape": [x]}


@register_op("sample_logits")
def _sample_logits(ins, attrs, op):
    """ref sample_logits_op.cc (sampled softmax): gather the true-label
    logit plus ``num_samples`` uniformly sampled negatives, with the
    log-probability correction."""
    from ..core import random as _random

    logits = _one(ins, "Logits")   # (B, C)
    labels = _one(ins, "Labels").reshape(-1).astype(jnp.int32)
    n = attrs["num_samples"]
    B, C = logits.shape
    samples = jax.random.randint(_random.next_key(), (B, n), 0, C)
    idx = jnp.concatenate([labels[:, None], samples], axis=1)  # (B, 1+n)
    sampled = jnp.take_along_axis(logits, idx, axis=1)
    # Q correction: uniform proposal q = n / C (ref subtracts log q)
    logq = jnp.log(jnp.asarray(n / C, jnp.float32))
    out = sampled - logq
    out = out.at[:, 0].set(sampled[:, 0])  # true label: no correction
    return {"SampledLogits": [out], "Samples": [idx],
            "SampledLabels": [jnp.zeros((B,), jnp.int32)]}
