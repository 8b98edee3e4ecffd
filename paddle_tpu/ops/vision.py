"""Detection / vision ops.

Reference parity: paddle/fluid/operators/detection/ (~17K LoC C++/CUDA —
yolo_box_op.cc, yolov3_loss_op.cc, multiclass_nms_op.cc, roi_align_op.cc,
anchor_generator_op.cc, prior_box_op.cc, box_coder_op.cc, iou_similarity_op.cc,
box_clip_op.cc) and their python wrappers fluid/layers/detection.py.

TPU-native design (SURVEY.md §7 step 8 "dynamic shapes policy"): the
reference returns LoD (ragged) detection lists; XLA needs static shapes, so
every op here returns **fixed-size padded outputs plus a valid-count** —
`multiclass_nms` yields (dets[keep_top_k, 6], num_valid) instead of a ragged
LoDTensor, NMS runs as a `lax.fori_loop` over a top-k-bounded candidate set,
and RoIAlign samples a fixed grid with gather/bilinear weights (vectorized,
MXU/VPU-friendly) instead of per-ROI scalar loops.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "iou_similarity", "box_coder", "box_clip", "anchor_generator",
    "prior_box", "yolo_box", "yolo_loss", "multiclass_nms", "roi_align",
    "density_prior_box", "deformable_conv", "psroi_pool",
]


# ------------------------------------------------------------------- boxes --
def iou_similarity(x, y, box_normalized: bool = True, eps: float = 1e-10):
    """Pairwise IoU between two box sets (ref iou_similarity_op.cc).

    x: [N, 4], y: [M, 4] in (x1, y1, x2, y2). Returns [N, M].
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    # +1 for integer-coordinate (non-normalized) boxes, as the reference does
    off = 0.0 if box_normalized else 1.0
    area_x = (x[:, 2] - x[:, 0] + off) * (x[:, 3] - x[:, 1] + off)
    area_y = (y[:, 2] - y[:, 0] + off) * (y[:, 3] - y[:, 1] + off)
    lt = jnp.maximum(x[:, None, :2], y[None, :, :2])
    rb = jnp.minimum(x[:, None, 2:], y[None, :, 2:])
    wh = jnp.clip(rb - lt + off, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    return inter / jnp.maximum(area_x[:, None] + area_y[None, :] - inter, eps)


def box_coder(prior_box, prior_box_var, target_box,
              code_type: str = "encode_center_size",
              box_normalized: bool = True, axis: int = 0):
    """Encode/decode boxes against priors (ref box_coder_op.cc).

    encode: target [N,4] vs priors [M,4] -> [N,M,4] offsets.
    decode: target [N,M,4] (or [N,4] broadcast) offsets -> boxes [N,M,4].
    prior_box_var: None | [M,4] | 4-list of floats.
    """
    prior_box = jnp.asarray(prior_box)
    target_box = jnp.asarray(target_box)
    off = 0.0 if box_normalized else 1.0
    pw = prior_box[:, 2] - prior_box[:, 0] + off
    ph = prior_box[:, 3] - prior_box[:, 1] + off
    pcx = prior_box[:, 0] + 0.5 * pw
    pcy = prior_box[:, 1] + 0.5 * ph
    if prior_box_var is None:
        var = jnp.ones((4,), target_box.dtype)
        var = jnp.broadcast_to(var, prior_box.shape)
    else:
        var = jnp.asarray(prior_box_var, target_box.dtype)
        if var.ndim == 1:
            var = jnp.broadcast_to(var[None, :], prior_box.shape)

    if code_type == "encode_center_size":
        tw = target_box[:, 2] - target_box[:, 0] + off
        th = target_box[:, 3] - target_box[:, 1] + off
        tcx = target_box[:, 0] + 0.5 * tw
        tcy = target_box[:, 1] + 0.5 * th
        dx = (tcx[:, None] - pcx[None, :]) / pw[None, :]
        dy = (tcy[:, None] - pcy[None, :]) / ph[None, :]
        dw = jnp.log(jnp.abs(tw[:, None] / pw[None, :]))
        dh = jnp.log(jnp.abs(th[:, None] / ph[None, :]))
        out = jnp.stack([dx, dy, dw, dh], axis=-1)
        return out / var[None, :, :]
    elif code_type == "decode_center_size":
        if target_box.ndim == 2:
            target_box = target_box[:, None, :]
        # ref box_coder_op.h:138 — axis 0: priors indexed by the col dim;
        # axis 1: priors indexed by the row dim.
        expect = target_box.shape[1] if axis == 0 else target_box.shape[0]
        if prior_box.shape[0] != expect:
            raise ValueError(
                f"decode with axis={axis} needs {expect} priors (target dim "
                f"{1 if axis == 0 else 0} of {tuple(target_box.shape)}); "
                f"got {prior_box.shape[0]}")
        # axis selects whether priors broadcast along rows (0) or cols (1);
        # after the [:, None, :] insert both reduce to broadcasting over dim 1
        t = target_box * var[None, :, :] if axis == 0 else target_box * var[:, None, :]
        pw_b = pw[None, :] if axis == 0 else pw[:, None]
        ph_b = ph[None, :] if axis == 0 else ph[:, None]
        pcx_b = pcx[None, :] if axis == 0 else pcx[:, None]
        pcy_b = pcy[None, :] if axis == 0 else pcy[:, None]
        cx = t[..., 0] * pw_b + pcx_b
        cy = t[..., 1] * ph_b + pcy_b
        w = jnp.exp(t[..., 2]) * pw_b
        h = jnp.exp(t[..., 3]) * ph_b
        return jnp.stack([cx - 0.5 * w, cy - 0.5 * h,
                          cx + 0.5 * w - off, cy + 0.5 * h - off], axis=-1)
    raise ValueError(f"unknown code_type {code_type!r}")


def box_clip(input, im_info):
    """Clip boxes to image bounds (ref box_clip_op.cc).
    input: [..., 4]; im_info: (h, w) or [..., 2]."""
    input = jnp.asarray(input)
    h, w = im_info[0], im_info[1]
    x1 = jnp.clip(input[..., 0], 0, w - 1)
    y1 = jnp.clip(input[..., 1], 0, h - 1)
    x2 = jnp.clip(input[..., 2], 0, w - 1)
    y2 = jnp.clip(input[..., 3], 0, h - 1)
    return jnp.stack([x1, y1, x2, y2], axis=-1)


# ----------------------------------------------------------------- anchors --
def anchor_generator(feature_hw: Tuple[int, int],
                     anchor_sizes: Sequence[float] = (64., 128., 256., 512.),
                     aspect_ratios: Sequence[float] = (0.5, 1.0, 2.0),
                     stride: Sequence[float] = (16., 16.),
                     variances: Sequence[float] = (0.1, 0.1, 0.2, 0.2),
                     offset: float = 0.5):
    """RPN-style anchors (ref anchor_generator_op.cc).

    Returns (anchors [H, W, A, 4] xyxy in input-image coords,
             variances [H, W, A, 4]); A = len(sizes)*len(ratios).
    """
    H, W = feature_hw
    sizes = jnp.asarray(anchor_sizes, jnp.float32)
    ratios = jnp.asarray(aspect_ratios, jnp.float32)
    # all (ratio, size) combos — ratio-major to match the reference's loops;
    # anchor w/h from size & ratio: w = size/sqrt(ratio), h = size*sqrt(ratio)
    r = jnp.repeat(ratios, sizes.shape[0])
    s = jnp.tile(sizes, ratios.shape[0])
    aw = s / jnp.sqrt(r)
    ah = s * jnp.sqrt(r)
    cx = (jnp.arange(W, dtype=jnp.float32) + offset) * stride[0]
    cy = (jnp.arange(H, dtype=jnp.float32) + offset) * stride[1]
    cxg, cyg = jnp.meshgrid(cx, cy)  # [H, W]
    anchors = jnp.stack([
        cxg[..., None] - 0.5 * aw,
        cyg[..., None] - 0.5 * ah,
        cxg[..., None] + 0.5 * aw,
        cyg[..., None] + 0.5 * ah,
    ], axis=-1)  # [H, W, A, 4]
    var = jnp.broadcast_to(jnp.asarray(variances, jnp.float32), anchors.shape)
    return anchors, var


def expand_aspect_ratios(aspect_ratios, flip: bool):
    """ref prior_box_op.cc ExpandAspectRatios: 1.0 always first, near-
    duplicates (within 1e-6) dropped, flip appends reciprocals (also
    deduped).  Shared by the eager kernel and the static DSL's prior-count
    shape inference so the two can never drift."""
    out = [1.0]

    def _add(v):
        if all(abs(v - e) > 1e-6 for e in out):
            out.append(v)

    for a in aspect_ratios:
        _add(float(a))
        if flip:
            _add(1.0 / float(a))
    return out


def prior_box(feature_hw: Tuple[int, int], image_hw: Tuple[int, int],
              min_sizes: Sequence[float], max_sizes: Sequence[float] = (),
              aspect_ratios: Sequence[float] = (1.0,), flip: bool = False,
              clip: bool = False, steps: Sequence[float] = (0.0, 0.0),
              offset: float = 0.5,
              variances: Sequence[float] = (0.1, 0.1, 0.2, 0.2)):
    """SSD prior boxes (ref prior_box_op.cc / layers/detection.py prior_box).

    Returns (boxes [H, W, P, 4] normalized xyxy, variances [H, W, P, 4]).
    """
    H, W = feature_hw
    img_h, img_w = image_hw
    step_w = steps[0] or img_w / W
    step_h = steps[1] or img_h / H
    ratios = expand_aspect_ratios(aspect_ratios, flip)
    if max_sizes and len(max_sizes) != len(min_sizes):
        raise ValueError("max_sizes must pair 1:1 with min_sizes "
                         f"(got {len(max_sizes)} vs {len(min_sizes)})")
    ws, hs = [], []
    for i, ms in enumerate(min_sizes):
        for ar in ratios:
            ws.append(ms * (ar ** 0.5))
            hs.append(ms / (ar ** 0.5))
        if max_sizes:  # ref: one extra sqrt(min*max) prior per min size
            Ms = max_sizes[i]
            ws.append((ms * Ms) ** 0.5)
            hs.append((ms * Ms) ** 0.5)
    ws = jnp.asarray(ws, jnp.float32) / img_w
    hs = jnp.asarray(hs, jnp.float32) / img_h
    cx = (jnp.arange(W, dtype=jnp.float32) + offset) * step_w / img_w
    cy = (jnp.arange(H, dtype=jnp.float32) + offset) * step_h / img_h
    cxg, cyg = jnp.meshgrid(cx, cy)
    boxes = jnp.stack([
        cxg[..., None] - 0.5 * ws,
        cyg[..., None] - 0.5 * hs,
        cxg[..., None] + 0.5 * ws,
        cyg[..., None] + 0.5 * hs,
    ], axis=-1)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    var = jnp.broadcast_to(jnp.asarray(variances, jnp.float32), boxes.shape)
    return boxes, var


# -------------------------------------------------------------------- yolo --
def _yolo_grid(x, anchors, class_num, downsample_ratio, scale_x_y):
    """Shared decode of the YOLO head tensor x [N, A*(5+C), H, W]."""
    N, CC, H, W = x.shape
    A = len(anchors) // 2
    C = class_num
    if CC != A * (5 + C):
        raise ValueError(
            f"yolo head has {CC} channels but {len(anchors)//2} anchors x "
            f"(5+{C}) classes needs {A * (5 + C)}")
    x = x.reshape(N, A, 5 + C, H, W)
    anc = jnp.asarray(anchors, jnp.float32).reshape(A, 2)
    gx = jnp.arange(W, dtype=jnp.float32)
    gy = jnp.arange(H, dtype=jnp.float32)
    gxg, gyg = jnp.meshgrid(gx, gy)  # [H, W]
    bias = 0.5 * (scale_x_y - 1.0)
    cx = (jax.nn.sigmoid(x[:, :, 0]) * scale_x_y - bias + gxg) / W  # [N,A,H,W]
    cy = (jax.nn.sigmoid(x[:, :, 1]) * scale_x_y - bias + gyg) / H
    input_w = downsample_ratio * W
    input_h = downsample_ratio * H
    bw = jnp.exp(x[:, :, 2]) * anc[None, :, 0, None, None] / input_w
    bh = jnp.exp(x[:, :, 3]) * anc[None, :, 1, None, None] / input_h
    obj = jax.nn.sigmoid(x[:, :, 4])
    cls = jax.nn.sigmoid(x[:, :, 5:])  # [N, A, C, H, W]
    return cx, cy, bw, bh, obj, cls


def yolo_box(x, img_size, anchors: Sequence[int], class_num: int,
             conf_thresh: float = 0.01, downsample_ratio: int = 32,
             clip_bbox: bool = True, scale_x_y: float = 1.0):
    """Decode one YOLO head to boxes+scores (ref yolo_box_op.cc).

    x: [N, A*(5+C), H, W]; img_size: [N, 2] (h, w).
    Returns (boxes [N, A*H*W, 4] xyxy in image coords,
             scores [N, A*H*W, C]); low-confidence rows are zeroed (the
    static-shape stand-in for the reference's filtering).
    """
    x = jnp.asarray(x)
    img_size = jnp.asarray(img_size)
    N, _, H, W = x.shape
    cx, cy, bw, bh, obj, cls = _yolo_grid(x, anchors, class_num,
                                          downsample_ratio, scale_x_y)
    img_h = img_size[:, 0].astype(jnp.float32)[:, None, None, None]
    img_w = img_size[:, 1].astype(jnp.float32)[:, None, None, None]
    x1 = (cx - bw / 2) * img_w
    y1 = (cy - bh / 2) * img_h
    x2 = (cx + bw / 2) * img_w
    y2 = (cy + bh / 2) * img_h
    if clip_bbox:
        x1 = jnp.clip(x1, 0.0, img_w - 1)
        y1 = jnp.clip(y1, 0.0, img_h - 1)
        x2 = jnp.clip(x2, 0.0, img_w - 1)
        y2 = jnp.clip(y2, 0.0, img_h - 1)
    boxes = jnp.stack([x1, y1, x2, y2], axis=-1)  # [N, A, H, W, 4]
    conf = obj[..., None]  # [N, A, H, W, 1]
    scores = cls.transpose(0, 1, 3, 4, 2) * conf  # [N, A, H, W, C]
    keep = (conf > conf_thresh).astype(boxes.dtype)
    boxes = boxes * keep
    scores = scores * keep
    M = boxes.shape[1] * H * W
    return boxes.reshape(N, M, 4), scores.reshape(N, M, class_num)


def yolo_loss(x, gt_box, gt_label, anchors: Sequence[int],
              anchor_mask: Sequence[int], class_num: int,
              ignore_thresh: float = 0.7, downsample_ratio: int = 32,
              gt_score=None, use_label_smooth: bool = False,
              scale_x_y: float = 1.0):
    """YOLOv3 training loss for one head (ref yolov3_loss_op.cc/.h).

    x: [N, len(mask)*(5+C), H, W]; gt_box: [N, B, 4] (cx, cy, w, h,
    normalized to [0,1]); gt_label: [N, B] int; rows with w<=0 are padding.
    Returns per-image loss [N].

    Assignment follows the reference: a gt's responsible anchor is the
    global-argmax-IoU anchor over ALL anchors (shape-only IoU); the gt only
    contributes at this head if that anchor is in `anchor_mask`.  Objectness
    of unmatched predictions is trained toward 0 except where their IoU with
    any gt exceeds ignore_thresh.  All built as dense scatters — no ragged
    tensors (static-shape policy).
    """
    # the loss contract is fp32 regardless of head dtype (bf16 heads were
    # no faster on the earlier setup, record deleted — so exact parity wins);
    # casting at entry makes the invariant hold for EVERY term, including
    # the ignore-mask decode below
    x = jnp.asarray(x).astype(jnp.float32)
    gt_box = jnp.asarray(gt_box, jnp.float32)
    gt_label = jnp.asarray(gt_label)
    N, _, H, W = x.shape
    mask = list(anchor_mask)
    A = len(mask)
    C = class_num
    xr = x.reshape(N, A, 5 + C, H, W)
    anc_all = jnp.asarray(anchors, jnp.float32).reshape(-1, 2)
    anc = anc_all[jnp.asarray(mask)]
    input_w = jnp.float32(downsample_ratio * W)
    input_h = jnp.float32(downsample_ratio * H)
    B = gt_box.shape[1]
    valid = gt_box[:, :, 2] > 0  # [N, B]
    if gt_score is None:
        gt_score = valid.astype(jnp.float32)
    else:
        gt_score = jnp.asarray(gt_score, jnp.float32) * valid

    # ---- responsible-anchor assignment (shape-only IoU, centered boxes) ----
    gw = gt_box[:, :, 2] * input_w  # pixels
    gh = gt_box[:, :, 3] * input_h
    inter = (jnp.minimum(gw[..., None], anc_all[None, None, :, 0]) *
             jnp.minimum(gh[..., None], anc_all[None, None, :, 1]))
    union = gw[..., None] * gh[..., None] + \
        anc_all[None, None, :, 0] * anc_all[None, None, :, 1] - inter
    best_anchor = jnp.argmax(inter / jnp.maximum(union, 1e-10), axis=-1)  # [N,B]
    mask_arr = jnp.asarray(mask)
    in_head = (best_anchor[..., None] == mask_arr[None, None, :])  # [N,B,A]
    local_anchor = jnp.argmax(in_head, axis=-1)  # [N,B] (valid where any)
    assigned = valid & jnp.any(in_head, axis=-1)  # [N,B]

    gi = jnp.clip((gt_box[:, :, 0] * W).astype(jnp.int32), 0, W - 1)  # [N,B]
    gj = jnp.clip((gt_box[:, :, 1] * H).astype(jnp.int32), 0, H - 1)

    # ---- dense targets via scatter ----
    tx = gt_box[:, :, 0] * W - gi
    ty = gt_box[:, :, 1] * H - gj
    tw = jnp.log(jnp.maximum(gw / jnp.maximum(anc[local_anchor][..., 0], 1e-6), 1e-9))
    th = jnp.log(jnp.maximum(gh / jnp.maximum(anc[local_anchor][..., 1], 1e-6), 1e-9))
    box_scale = 2.0 - gt_box[:, :, 2] * gt_box[:, :, 3]  # small boxes upweighted

    # Unassigned/padding rows must not write at all (a clamped scatter at
    # (n,0,0,0) would clobber a real target there): push their batch index
    # out of bounds and use mode="drop" so XLA discards those updates.
    bidx = jnp.broadcast_to(jnp.arange(N)[:, None], (N, B))
    bidx = jnp.where(assigned, bidx, N)
    sel = (bidx, local_anchor, gj, gi)

    def scat(vals):
        t = jnp.zeros((N, A, H, W), jnp.float32)
        return t.at[sel].set(vals, mode="drop")

    obj_mask = scat(gt_score)                # positive weight
    t_x, t_y = scat(tx), scat(ty)
    t_w, t_h = scat(tw), scat(th)
    t_scale = scat(box_scale)
    # class targets scattered DIRECTLY in the head's (N, A, C, H, W)
    # layout: the [..., C]-last form needs an fp32 transpose of the whole
    # prediction tensor per head per step
    cls_idx = jnp.clip(gt_label, 0, C - 1)
    t_cls = jnp.zeros((N, A, C, H, W), jnp.float32).at[
        (bidx, local_anchor, cls_idx, gj, gi)].set(1.0, mode="drop")

    # ---- ignore mask: predictions overlapping any gt beyond thresh ----
    # same decode as yolo_box, restricted to this head's anchors
    masked_anchors = [float(v) for i in mask
                      for v in (anchors[2 * i], anchors[2 * i + 1])]
    cx, cy, bw, bh, _, _ = _yolo_grid(x, masked_anchors, C,
                                      downsample_ratio, scale_x_y)
    pb = jnp.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], -1)
    gb = jnp.stack([gt_box[:, :, 0] - gt_box[:, :, 2] / 2,
                    gt_box[:, :, 1] - gt_box[:, :, 3] / 2,
                    gt_box[:, :, 0] + gt_box[:, :, 2] / 2,
                    gt_box[:, :, 1] + gt_box[:, :, 3] / 2], -1)  # [N,B,4]
    pb_flat = pb.reshape(N, -1, 4)
    ious = jax.vmap(iou_similarity)(pb_flat, gb)  # [N, A*H*W, B]
    ious = jnp.where(valid[:, None, :], ious, 0.0)
    best_iou = ious.max(axis=-1).reshape(N, A, H, W)
    ignore = (best_iou > ignore_thresh) & (obj_mask <= 0)

    # ---- loss terms (BCE-with-logits like the reference; everything is
    # fp32 via the entry cast, reductions carry explicit accumulators) ----
    dt = jnp.float32

    def bce(logit, target):
        return jnp.maximum(logit, 0) - logit * target + \
            jnp.log1p(jnp.exp(-jnp.abs(logit)))

    obj = obj_mask.astype(dt)
    tsc = t_scale.astype(dt)
    lx = bce(xr[:, :, 0], t_x.astype(dt)) * tsc * obj
    ly = bce(xr[:, :, 1], t_y.astype(dt)) * tsc * obj
    lw = jnp.abs(xr[:, :, 2] - t_w.astype(dt)) * tsc * obj
    lh = jnp.abs(xr[:, :, 3] - t_h.astype(dt)) * tsc * obj
    pos = bce(xr[:, :, 4], jnp.ones_like(obj)) * obj
    neg = bce(xr[:, :, 4], jnp.zeros_like(obj)) * \
        jnp.where((obj_mask <= 0) & (~ignore), 1.0, 0.0).astype(dt)
    smooth = 1.0 / max(C, 1) if use_label_smooth else 0.0
    t_cls_s = t_cls * (1 - 2 * smooth) + smooth if use_label_smooth else t_cls
    lcls = (bce(xr[:, :, 5:], t_cls_s.astype(dt))
            * obj[:, :, None]).sum(axis=2, dtype=jnp.float32)
    per_img = (lx + ly + lw + lh + pos + neg).sum(
        axis=(1, 2, 3), dtype=jnp.float32) + lcls.sum(axis=(1, 2, 3))
    return per_img


# --------------------------------------------------------------------- nms --
def _nms_one_class(boxes, scores, iou_threshold, score_threshold, top_k,
                   normalized=True):
    """Greedy NMS over the top_k highest-scoring candidates.
    Returns (keep mask [top_k], order indices [top_k] into boxes)."""
    order = jnp.argsort(-scores)[:top_k]
    b = boxes[order]
    s = scores[order]
    iou = iou_similarity(b, b, box_normalized=normalized)
    M = b.shape[0]
    idx = jnp.arange(M)

    def body(i, keep):
        earlier = (idx < i) & keep
        sup = jnp.any(earlier & (iou[i] > iou_threshold))
        ok = (~sup) & (s[i] > score_threshold)
        return keep.at[i].set(ok)

    keep = lax.fori_loop(0, M, body, jnp.ones(M, bool))
    return keep, order


def multiclass_nms(bboxes, scores, score_threshold: float = 0.05,
                   nms_top_k: int = 400, keep_top_k: int = 100,
                   nms_threshold: float = 0.45, normalized: bool = True,
                   background_label: int = -1):
    """Per-class NMS (ref multiclass_nms_op.cc), single image.

    bboxes: [M, 4] (shared across classes) or [M, C, 4];
    scores: [C, M].  Returns (dets [keep_top_k, 6] = (label, score, x1, y1,
    x2, y2) sorted by score, padded with label=-1, and num_valid).
    """
    bboxes = jnp.asarray(bboxes)
    scores = jnp.asarray(scores)
    C, M = scores.shape
    top_k = min(nms_top_k, M)
    if bboxes.ndim == 2:
        per_class_boxes = jnp.broadcast_to(bboxes[None], (C, M, 4))
    else:
        per_class_boxes = bboxes.transpose(1, 0, 2)  # [C, M, 4]

    keep, order = jax.vmap(
        lambda b, s: _nms_one_class(b, s, nms_threshold, score_threshold,
                                    top_k, normalized))(per_class_boxes, scores)
    # gather per-class candidates
    cls_ids = jnp.broadcast_to(jnp.arange(C)[:, None], (C, top_k))
    sel_scores = jnp.take_along_axis(scores, order, axis=1)  # [C, top_k]
    sel_boxes = jnp.take_along_axis(per_class_boxes, order[..., None], axis=1)
    if background_label >= 0:
        keep = keep & (cls_ids != background_label)
    flat_scores = jnp.where(keep, sel_scores, -jnp.inf).reshape(-1)
    flat_boxes = sel_boxes.reshape(-1, 4)
    flat_cls = cls_ids.reshape(-1)
    k = min(keep_top_k, flat_scores.shape[0])
    top_scores, top_idx = lax.top_k(flat_scores, k)
    out_valid = jnp.isfinite(top_scores)
    dets = jnp.concatenate([
        jnp.where(out_valid, flat_cls[top_idx], -1).astype(jnp.float32)[:, None],
        jnp.where(out_valid, top_scores, 0.0)[:, None],
        jnp.where(out_valid[:, None], flat_boxes[top_idx], 0.0),
    ], axis=1)
    if k < keep_top_k:
        pad = jnp.zeros((keep_top_k - k, 6), dets.dtype).at[:, 0].set(-1.0)
        dets = jnp.concatenate([dets, pad], axis=0)
    return dets, out_valid.sum().astype(jnp.int32)


# --------------------------------------------------------------- roi align --
def roi_align(input, rois, output_size, spatial_scale: float = 1.0,
              sampling_ratio: int = -1, aligned: bool = False):
    """RoIAlign (ref roi_align_op.cc/.cu), batch-size-1 feature map.

    input: [C, H, W]; rois: [R, 4] xyxy in input-image coords.
    Returns [R, C, out_h, out_w].  Bilinear sampling over a fixed
    sampling grid, fully vectorized (gather + weighted sum).

    Static-shape policy: with ``sampling_ratio=-1`` the reference
    (roi_align_op) derives ceil(roi_h/pooled_h) samples *per ROI*; that is a
    data-dependent shape XLA cannot compile, so this implementation uses a
    fixed ratio of 2 (detectron2's default).  Outputs diverge from the
    reference for ROIs larger than 2x the output grid; pass an explicit
    ``sampling_ratio`` sized for your expected max ROI if that matters.
    """
    input = jnp.asarray(input)
    rois = jnp.asarray(rois, jnp.float32)
    C, H, W = input.shape
    if isinstance(output_size, int):
        out_h = out_w = output_size
    else:
        out_h, out_w = output_size
    ratio = sampling_ratio if sampling_ratio > 0 else 2
    off = 0.5 if aligned else 0.0

    def one_roi(roi):
        x1, y1, x2, y2 = roi * spatial_scale - off
        rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        bin_w = rw / out_w
        bin_h = rh / out_h
        # sample grid: (out_h*ratio) x (out_w*ratio) points
        sy = y1 + (jnp.arange(out_h * ratio) + 0.5) * bin_h / ratio
        sx = x1 + (jnp.arange(out_w * ratio) + 0.5) * bin_w / ratio
        yy, xx = jnp.meshgrid(sy, sx, indexing="ij")  # [oh*r, ow*r]
        # ref roi_align_op: samples with y/x outside [-1, H]/[-1, W]
        # contribute zero (not border replication)
        in_img = (yy >= -1.0) & (yy <= H) & (xx >= -1.0) & (xx <= W)
        yy_c = jnp.clip(yy, 0.0, H - 1)
        xx_c = jnp.clip(xx, 0.0, W - 1)
        y0 = jnp.floor(yy_c)
        x0 = jnp.floor(xx_c)
        y1i = jnp.clip(y0 + 1, 0, H - 1).astype(jnp.int32)
        x1i = jnp.clip(x0 + 1, 0, W - 1).astype(jnp.int32)
        y0i = y0.astype(jnp.int32)
        x0i = x0.astype(jnp.int32)
        ly = jnp.clip(yy_c - y0, 0.0, 1.0)
        lx = jnp.clip(xx_c - x0, 0.0, 1.0)
        v = (input[:, y0i, x0i] * ((1 - ly) * (1 - lx)) +
             input[:, y0i, x1i] * ((1 - ly) * lx) +
             input[:, y1i, x0i] * (ly * (1 - lx)) +
             input[:, y1i, x1i] * (ly * lx))  # [C, oh*r, ow*r]
        v = jnp.where(in_img, v, 0.0)
        v = v.reshape(C, out_h, ratio, out_w, ratio)
        return v.mean(axis=(2, 4))

    return jax.vmap(one_roi)(rois)


def density_prior_box(feature_hw, image_hw, densities, fixed_sizes,
                      fixed_ratios=(1.0,), clip: bool = False,
                      steps=(0.0, 0.0), offset: float = 0.5,
                      variances=(0.1, 0.1, 0.2, 0.2), flatten_to_2d=False):
    """Density prior boxes (ref density_prior_box_op.cc / layers/detection.py
    density_prior_box): per (density d, fixed_size s, ratio r), a d x d grid
    of shifted centers inside each feature cell carrying an s*sqrt(r) x
    s/sqrt(r) box.

    Returns (boxes [H, W, P, 4] normalized xyxy, variances [...]) or the
    flattened (N, 4) pair when ``flatten_to_2d``.
    """
    H, W = feature_hw
    img_h, img_w = image_hw
    step_w = steps[0] or img_w / W
    step_h = steps[1] or img_h / H
    if len(densities) != len(fixed_sizes):
        raise ValueError("densities must pair 1:1 with fixed_sizes")
    ws, hs, sx, sy = [], [], [], []
    for dens, size in zip(densities, fixed_sizes):
        for ratio in fixed_ratios:
            bw = size * (ratio ** 0.5)
            bh = size / (ratio ** 0.5)
            shift = 1.0 / dens
            for di in range(dens):
                for dj in range(dens):
                    # center shift within the cell, in step units
                    sx.append((dj + 0.5) * shift - 0.5)
                    sy.append((di + 0.5) * shift - 0.5)
                    ws.append(bw)
                    hs.append(bh)
    ws = jnp.asarray(ws, jnp.float32) / img_w
    hs = jnp.asarray(hs, jnp.float32) / img_h
    sx = jnp.asarray(sx, jnp.float32) * step_w / img_w
    sy = jnp.asarray(sy, jnp.float32) * step_h / img_h
    cx = (jnp.arange(W, dtype=jnp.float32) + offset) * step_w / img_w
    cy = (jnp.arange(H, dtype=jnp.float32) + offset) * step_h / img_h
    cxg, cyg = jnp.meshgrid(cx, cy)
    cxs = cxg[..., None] + sx
    cys = cyg[..., None] + sy
    boxes = jnp.stack([cxs - 0.5 * ws, cys - 0.5 * hs,
                       cxs + 0.5 * ws, cys + 0.5 * hs], axis=-1)
    if clip:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    var = jnp.broadcast_to(jnp.asarray(variances, jnp.float32), boxes.shape)
    if flatten_to_2d:
        return boxes.reshape(-1, 4), var.reshape(-1, 4)
    return boxes, var


def _bilinear_sample_nchw(x, ys, xs):
    """Bilinear sample x (C, H, W) at float coords ys/xs (...,); zero
    outside.  Gather-based — lowers to XLA gather, no host sync."""
    C, H, W = x.shape
    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    wy = ys - y0
    wx = xs - x0
    out = 0.0
    for dy, wgt_y in ((0, 1.0 - wy), (1, wy)):
        for dx, wgt_x in ((0, 1.0 - wx), (1, wx)):
            yy = y0 + dy
            xx = x0 + dx
            inb = (yy >= 0) & (yy <= H - 1) & (xx >= 0) & (xx <= W - 1)
            yc = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
            xc = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
            v = x[:, yc, xc]                      # (C, ...)
            w = jnp.where(inb, wgt_y * wgt_x, 0.0)
            out = out + v * w
    return out


def deformable_conv(x, offset, weight, mask=None, stride=1, padding=0,
                    dilation=1, groups: int = 1, deformable_groups: int = 1,
                    bias=None):
    """Deformable convolution v2 (v1 when ``mask`` is None).

    Reference parity: deformable_conv_op.cu / deformable_conv_v1_op.cu
    (modulated_deformable_im2col CUDA kernels).  TPU-native design: the
    offset-shifted bilinear sampling is a batched XLA gather building the
    im2col tensor, then one big einsum hits the MXU — no scatter, no
    dynamic shapes.

    Shapes: x (N,C,H,W); offset (N, 2*dg*kh*kw, Ho, Wo);
    mask (N, dg*kh*kw, Ho, Wo); weight (out_c, C/groups, kh, kw).
    """
    x = jnp.asarray(x)
    offset = jnp.asarray(offset)
    weight = jnp.asarray(weight)
    N, C, H, W = x.shape
    out_c, cpg, kh, kw = weight.shape
    sh, sw = (stride, stride) if isinstance(stride, int) else tuple(stride)
    ph, pw = (padding, padding) if isinstance(padding, int) else tuple(padding)
    dh, dw = (dilation, dilation) if isinstance(dilation, int) else tuple(dilation)
    Ho = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    Wo = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    dg = deformable_groups
    K = kh * kw

    oy = (jnp.arange(Ho) * sh - ph).astype(jnp.float32)
    ox = (jnp.arange(Wo) * sw - pw).astype(jnp.float32)
    ky = (jnp.arange(kh) * dh).astype(jnp.float32)
    kx = (jnp.arange(kw) * dw).astype(jnp.float32)
    base_y = oy[:, None, None, None] + ky[None, None, :, None]  # Ho,1,kh,1
    base_x = ox[None, :, None, None] + kx[None, None, None, :]  # 1,Wo,1,kw
    base_y = jnp.broadcast_to(base_y, (Ho, Wo, kh, kw)).reshape(Ho, Wo, K)
    base_x = jnp.broadcast_to(base_x, (Ho, Wo, kh, kw)).reshape(Ho, Wo, K)

    off = offset.reshape(N, dg, K, 2, Ho, Wo)
    off_y = jnp.moveaxis(off[:, :, :, 0], (2, 3, 4), (4, 2, 3))   # N,dg,Ho,Wo,K
    off_x = jnp.moveaxis(off[:, :, :, 1], (2, 3, 4), (4, 2, 3))
    ys = base_y[None, None] + off_y                               # N,dg,Ho,Wo,K
    xs = base_x[None, None] + off_x
    if mask is not None:
        m = jnp.moveaxis(jnp.asarray(mask).reshape(N, dg, K, Ho, Wo),
                         2, -1)                                   # N,dg,Ho,Wo,K
    else:
        m = jnp.ones((N, dg, Ho, Wo, K), x.dtype)

    cols = jax.vmap(  # over batch
        lambda xb, yb, xbx, mb: jnp.concatenate([
            _bilinear_sample_nchw(
                xb[g * (C // dg):(g + 1) * (C // dg)], yb[g], xbx[g]) * mb[g]
            for g in range(dg)], axis=0)
    )(x, ys, xs, m)                                # (N, C, Ho, Wo, K)
    cols = jnp.moveaxis(cols, -1, 2)               # (N, C, K, Ho, Wo)
    cols = cols.reshape(N, groups, C // groups, K, Ho, Wo)
    wg = weight.reshape(groups, out_c // groups, cpg, K)
    out = jnp.einsum("ngckhw,gock->ngohw", cols, wg,
                     preferred_element_type=jnp.float32)
    out = out.reshape(N, out_c, Ho, Wo).astype(x.dtype)
    if bias is not None:
        out = out + jnp.asarray(bias).reshape(1, -1, 1, 1)
    return out


def psroi_pool(x, rois, roi_batch_id, output_channels: int,
               pooled_height: int, pooled_width: int,
               spatial_scale: float = 1.0):
    """Position-sensitive ROI pooling (ref psroi_pool_op.cc): input channel
    layout (N, out_c*ph*pw, H, W); bin (i, j) of output channel c averages
    input channel c*ph*pw + i*pw + j over the bin's spatial extent."""
    x = jnp.asarray(x)
    rois = jnp.asarray(rois, jnp.float32)
    roi_batch_id = jnp.asarray(roi_batch_id, jnp.int32)
    N, C, H, W = x.shape
    ph, pw = pooled_height, pooled_width
    if C != output_channels * ph * pw:
        raise ValueError(
            f"psroi_pool: input channels {C} != out_c*ph*pw "
            f"({output_channels}*{ph}*{pw})")

    ii = jnp.arange(H, dtype=jnp.float32)[:, None]
    jj = jnp.arange(W, dtype=jnp.float32)[None, :]

    def one_roi(roi, bi):
        # ref psroi_pool_op.h: round the RAW roi, +1 on the end coords,
        # THEN apply spatial_scale (order matters for scale != 1)
        x1 = jnp.round(roi[0]) * spatial_scale
        y1 = jnp.round(roi[1]) * spatial_scale
        x2 = (jnp.round(roi[2]) + 1.0) * spatial_scale
        y2 = (jnp.round(roi[3]) + 1.0) * spatial_scale
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bin_h = rh / ph
        bin_w = rw / pw
        feat = x[bi].reshape(output_channels, ph, pw, H, W)
        gy = jnp.arange(ph, dtype=jnp.float32)
        gx = jnp.arange(pw, dtype=jnp.float32)
        ys = y1 + gy[:, None] * bin_h          # (ph, 1) bin start
        ye = y1 + (gy[:, None] + 1) * bin_h
        xs = x1 + gx[None, :] * bin_w          # (1, pw)
        xe = x1 + (gx[None, :] + 1) * bin_w
        in_y = ((ii[None, None] >= jnp.floor(ys)[..., None, None]) &
                (ii[None, None] < jnp.ceil(ye)[..., None, None]) &
                (ii[None, None] >= 0) & (ii[None, None] <= H - 1))
        in_x = ((jj[None, None] >= jnp.floor(xs)[..., None, None]) &
                (jj[None, None] < jnp.ceil(xe)[..., None, None]) &
                (jj[None, None] >= 0) & (jj[None, None] <= W - 1))
        sel = (in_y & in_x).astype(x.dtype)    # (ph, pw, H, W)
        cnt = jnp.maximum(jnp.sum(sel, axis=(-2, -1)), 1.0)
        s = jnp.einsum("cpqhw,pqhw->cpq", feat, sel)
        return s / cnt

    return jax.vmap(one_roi)(rois, roi_batch_id)
