"""PP-YOLOE-class anchor-free detector (BASELINE.json names PP-YOLOE).

The reference repo predates PP-YOLOE but ships the op substrate this
model family is built from (``paddle/fluid/operators/detection/``:
yolo_box, NMS, anchors, IoU); the detector here is the TPU-native
composition of that op family into the modern anchor-free pipeline:

- **CSPResNet backbone** with RepVGG-style 3×3+1×1 dual-branch blocks,
- **CSP-PAN neck** (top-down + bottom-up, SPP in the deepest stage),
- **ET-head**: per-level classification (varifocal loss) and a
  distribution-focal regression branch (l, t, r, b over ``reg_max+1``
  bins, decoded by expectation),
- **Task-aligned assignment** (TAL) — implemented fully statically:
  per-gt top-k candidate selection and conflict resolution are masked
  tensor ops, no dynamic shapes anywhere,
- eval-time decode → ``vision.ops.multiclass_nms`` (padded/masked, the
  reference ``detection/multiclass_nms_op.cc`` semantics).

Everything jits; ground truth arrives padded ([B, G, 4] boxes and
[B, G] labels with -1 padding), which is also the collate format of
``vision.datasets`` detection pipelines.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.conv import Conv2D, MaxPool2D
from paddle_tpu.nn.norm import BatchNorm2D
from paddle_tpu.vision import ops as V

__all__ = ["PPYOLOEConfig", "PPYOLOE", "ppyoloe_tiny", "ppyoloe_s"]


@dataclass(frozen=True)
class PPYOLOEConfig:
    num_classes: int = 80
    # backbone: channels per stage and blocks per stage
    stage_channels: tuple = (64, 128, 256, 512)
    stage_blocks: tuple = (1, 2, 2, 1)
    stem_channels: int = 32
    # neck output channels per level (P3, P4, P5)
    neck_channels: tuple = (96, 192, 384)
    strides: tuple = (8, 16, 32)
    reg_max: int = 16
    # TAL
    tal_topk: int = 13
    tal_alpha: float = 1.0
    tal_beta: float = 6.0
    # loss weights (PP-YOLOE defaults)
    cls_weight: float = 1.0
    iou_weight: float = 2.5
    dfl_weight: float = 0.5
    # eval
    score_threshold: float = 0.01
    nms_threshold: float = 0.6
    nms_top_k: int = 400
    keep_top_k: int = 100

    @classmethod
    def tiny(cls, num_classes: int = 8):
        return cls(num_classes=num_classes, stage_channels=(32, 48, 64, 96),
                   stage_blocks=(1, 1, 1, 1), stem_channels=16,
                   neck_channels=(32, 48, 64), reg_max=8, nms_top_k=100,
                   keep_top_k=20)


class ConvBNAct(Module):
    def __init__(self, in_c, out_c, k=3, stride=1, groups=1, act="swish",
                 key=None):
        self.conv = Conv2D(in_c, out_c, k, stride=stride,
                           padding=(k - 1) // 2, groups=groups, bias=False,
                           key=key)
        self.bn = BatchNorm2D(out_c)
        self.act = act

    def __call__(self, x, training: bool = False):
        x = self.bn(self.conv(x), training=training)
        return F.swish(x) if self.act == "swish" else x


class RepVggBlock(Module):
    """Dual-branch 3×3 + 1×1 conv-BN (train form). The inference-time
    reparameterization to one 3×3 is a pure weight transform
    (``fuse()``), not a separate architecture."""

    def __init__(self, in_c, out_c, key=None):
        k1, k2 = rng.split_key(key)
        self.conv3 = ConvBNAct(in_c, out_c, 3, act="none", key=k1)
        self.conv1 = ConvBNAct(in_c, out_c, 1, act="none", key=k2)

    def __call__(self, x, training: bool = False):
        return F.swish(self.conv3(x, training=training)
                       + self.conv1(x, training=training))


class ESEAttn(Module):
    """Effective squeeze-excitation (one fc) used by the head stem."""

    def __init__(self, ch, key=None):
        k1, k2 = rng.split_key(key)
        self.fc = Conv2D(ch, ch, 1, key=k1)
        self.conv = ConvBNAct(ch, ch, 1, key=k2)

    def __call__(self, feat, avg_feat, training: bool = False):
        w = F.sigmoid(self.fc(avg_feat))
        return self.conv(feat * w, training=training)


class CSPResStage(Module):
    def __init__(self, in_c, out_c, n_blocks, stride, key=None):
        keys = rng.split_key(key, n_blocks + 4)
        mid = out_c // 2
        self.down = (ConvBNAct(in_c, in_c, 3, stride=stride, key=keys[0])
                     if stride > 1 else None)
        self.conv1 = ConvBNAct(in_c, mid, 1, key=keys[1])
        self.conv2 = ConvBNAct(in_c, mid, 1, key=keys[2])
        self.blocks = tuple(
            RepVggBlock(mid, mid, key=keys[3 + i]) for i in range(n_blocks))
        self.conv3 = ConvBNAct(mid * 2, out_c, 1, key=keys[-1])

    def __call__(self, x, training: bool = False):
        if self.down is not None:
            x = self.down(x, training=training)
        y1 = self.conv1(x, training=training)
        y2 = self.conv2(x, training=training)
        for b in self.blocks:
            y2 = b(y2, training=training)
        return self.conv3(jnp.concatenate([y1, y2], axis=1),
                          training=training)


class CSPResNet(Module):
    """Backbone; returns (C3, C4, C5) feature maps at strides 8/16/32."""

    def __init__(self, cfg: PPYOLOEConfig, key=None):
        keys = rng.split_key(key, 3 + len(cfg.stage_channels))
        sc = cfg.stem_channels
        self.stem1 = ConvBNAct(3, sc, 3, stride=2, key=keys[0])
        self.stem2 = ConvBNAct(sc, sc * 2, 3, stride=1, key=keys[1])
        chans = (sc * 2,) + cfg.stage_channels
        self.stages = tuple(
            CSPResStage(chans[i], chans[i + 1], cfg.stage_blocks[i],
                        stride=2, key=keys[2 + i])
            for i in range(len(cfg.stage_channels)))

    def __call__(self, x, training: bool = False):
        x = self.stem2(self.stem1(x, training=training), training=training)
        feats = []
        for st in self.stages:
            x = st(x, training=training)
            feats.append(x)
        return feats[-3], feats[-2], feats[-1]


class SPP(Module):
    def __init__(self, in_c, out_c, key=None):
        self.pools = tuple(MaxPool2D(k, 1, k // 2) for k in (5, 9, 13))
        self.conv = ConvBNAct(in_c * 4, out_c, 1, key=key)

    def __call__(self, x, training: bool = False):
        parts = [x] + [p(x) for p in self.pools]
        return self.conv(jnp.concatenate(parts, axis=1), training=training)


class CSPStage(Module):
    def __init__(self, in_c, out_c, n=1, spp: bool = False, key=None):
        keys = rng.split_key(key, n + 4)
        mid = out_c // 2
        self.conv1 = ConvBNAct(in_c, mid, 1, key=keys[0])
        self.conv2 = ConvBNAct(in_c, mid, 1, key=keys[1])
        blocks = []
        for i in range(n):
            blocks.append(RepVggBlock(mid, mid, key=keys[2 + i]))
        self.blocks = tuple(blocks)
        self.spp = SPP(mid, mid, key=keys[-2]) if spp else None
        self.conv3 = ConvBNAct(mid * 2, out_c, 1, key=keys[-1])

    def __call__(self, x, training: bool = False):
        y1 = self.conv1(x, training=training)
        y2 = self.conv2(x, training=training)
        for b in self.blocks:
            y2 = b(y2, training=training)
        if self.spp is not None:
            y2 = self.spp(y2, training=training)
        return self.conv3(jnp.concatenate([y1, y2], axis=1),
                          training=training)


def _upsample2(x):
    n, c, h, w = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :, None],
                            (n, c, h, 2, w, 2)).reshape(n, c, h * 2, w * 2)


class CSPPAN(Module):
    """Top-down FPN + bottom-up PAN, CSP blocks at every fusion."""

    def __init__(self, in_chs, out_chs, key=None):
        keys = rng.split_key(key, 12)
        c3, c4, c5 = in_chs
        o3, o4, o5 = out_chs
        self.reduce5 = CSPStage(c5, o5, spp=True, key=keys[0])
        self.lat4 = ConvBNAct(o5, o4, 1, key=keys[1])
        self.td4 = CSPStage(c4 + o4, o4, key=keys[2])
        self.lat3 = ConvBNAct(o4, o3, 1, key=keys[3])
        self.td3 = CSPStage(c3 + o3, o3, key=keys[4])
        self.down3 = ConvBNAct(o3, o3, 3, stride=2, key=keys[5])
        self.bu4 = CSPStage(o3 + o4, o4, key=keys[6])
        self.down4 = ConvBNAct(o4, o4, 3, stride=2, key=keys[7])
        self.bu5 = CSPStage(o4 + o5, o5, key=keys[8])

    def __call__(self, feats, training: bool = False):
        c3, c4, c5 = feats
        p5 = self.reduce5(c5, training=training)
        up4 = _upsample2(self.lat4(p5, training=training))
        p4 = self.td4(jnp.concatenate([c4, up4], axis=1), training=training)
        up3 = _upsample2(self.lat3(p4, training=training))
        p3 = self.td3(jnp.concatenate([c3, up3], axis=1), training=training)
        n4 = self.bu4(jnp.concatenate(
            [self.down3(p3, training=training), p4], axis=1),
            training=training)
        n5 = self.bu5(jnp.concatenate(
            [self.down4(n4, training=training), p5], axis=1),
            training=training)
        return p3, n4, n5


class PPYOLOEHead(Module):
    def __init__(self, cfg: PPYOLOEConfig, key=None):
        nl = len(cfg.neck_channels)
        keys = rng.split_key(key, 4 * nl)
        self.cfg = cfg
        self.stem_cls = tuple(ESEAttn(c, key=keys[i])
                              for i, c in enumerate(cfg.neck_channels))
        self.stem_reg = tuple(ESEAttn(c, key=keys[nl + i])
                              for i, c in enumerate(cfg.neck_channels))
        # bias init: cls prior ~1% positive (focal-style); reg biased to
        # the first distance bin so initial boxes start ~1 stride wide
        self.pred_cls = tuple(
            Conv2D(c, cfg.num_classes, 3, padding=1, key=keys[2 * nl + i])
            for i, c in enumerate(cfg.neck_channels))
        self.pred_reg = tuple(
            Conv2D(c, 4 * (cfg.reg_max + 1), 3, padding=1,
                   key=keys[3 * nl + i])
            for i, c in enumerate(cfg.neck_channels))
        prior = -math.log((1 - 0.01) / 0.01)
        self.pred_cls = tuple(
            m.replace(bias=m.bias + prior) for m in self.pred_cls)
        reg_bias = jnp.tile(
            jnp.asarray([4.0] + [0.0] * cfg.reg_max, jnp.float32), 4)
        self.pred_reg = tuple(
            m.replace(bias=m.bias + reg_bias) for m in self.pred_reg)

    def __call__(self, feats, training: bool = False):
        """Returns (cls_logits [B, L, NC], reg_dist [B, L, 4, reg_max+1],
        anchor points [L, 2], strides [L, 1])."""
        cfg = self.cfg
        cls_list, reg_list, shapes = [], [], []
        for i, f in enumerate(feats):
            B, C, H, W = f.shape
            avg = jnp.mean(f, axis=(2, 3), keepdims=True)
            cl = self.pred_cls[i](
                self.stem_cls[i](f, avg, training=training) + f)
            rg = self.pred_reg[i](
                self.stem_reg[i](f, avg, training=training))
            cls_list.append(cl.reshape(B, cfg.num_classes, H * W)
                            .transpose(0, 2, 1))
            reg_list.append(
                rg.reshape(B, 4, cfg.reg_max + 1, H * W)
                .transpose(0, 3, 1, 2))
            shapes.append((H, W))
        points, strides = V.generate_anchor_points(shapes, cfg.strides)
        return (jnp.concatenate(cls_list, axis=1),
                jnp.concatenate(reg_list, axis=1), points, strides)


def _dfl_expect(reg_dist):
    """[..., 4, reg_max+1] logits → expected (l, t, r, b) in stride
    units (distribution-focal decode)."""
    n_bins = reg_dist.shape[-1]
    proj = jnp.arange(n_bins, dtype=jnp.float32)
    return jnp.sum(jax.nn.softmax(reg_dist, axis=-1) * proj, axis=-1)


def _tal_assign(pred_scores, pred_bboxes, points, gt_boxes, gt_labels,
                *, topk: int, alpha: float, beta: float, num_classes: int):
    """Task-aligned assignment for ONE image, fully static.

    pred_scores [L, NC] (sigmoid), pred_bboxes [L, 4] (pixels),
    points [L, 2], gt_boxes [G, 4], gt_labels [G] int (-1 = pad).
    Returns (target_labels [L] int (num_classes = bg), target_boxes
    [L, 4], target_scores [L, NC] soft).
    """
    L = points.shape[0]
    G = gt_boxes.shape[0]
    valid_gt = gt_labels >= 0                                   # [G]

    iou = V.box_iou_xyxy(gt_boxes, pred_bboxes)                 # [G, L]
    safe_labels = jnp.clip(gt_labels, 0, num_classes - 1)
    cls_score = pred_scores[:, safe_labels].T                   # [G, L]
    metric = (cls_score ** alpha) * (iou ** beta)

    # candidates must have their center inside the gt box
    inside = ((points[None, :, 0] >= gt_boxes[:, None, 0])
              & (points[None, :, 0] <= gt_boxes[:, None, 2])
              & (points[None, :, 1] >= gt_boxes[:, None, 1])
              & (points[None, :, 1] <= gt_boxes[:, None, 3]))   # [G, L]
    metric = jnp.where(inside & valid_gt[:, None], metric, 0.0)

    # per-gt top-k candidate mask (static k)
    k = min(topk, L)
    kth = -jax.lax.top_k(metric, k)[0][:, -1:]                  # [G, 1]
    cand = (metric >= jnp.maximum(-kth, 1e-12)) & (metric > 0)  # [G, L]

    # conflicts: an anchor claimed by several gts goes to the max-IoU one
    iou_cand = jnp.where(cand, iou, -1.0)
    owner = jnp.argmax(iou_cand, axis=0)                        # [L]
    assigned = jnp.max(iou_cand, axis=0) > 0                    # [L]

    t_labels = jnp.where(assigned, gt_labels[owner], num_classes)
    t_boxes = gt_boxes[owner]

    # normalized soft targets: metric scaled per gt to its max IoU
    m_max = jnp.max(metric, axis=1, keepdims=True)              # [G, 1]
    i_max = jnp.max(jnp.where(cand, iou, 0.0), axis=1, keepdims=True)
    norm_metric = metric / jnp.maximum(m_max, 1e-9) * i_max     # [G, L]
    t_score_val = jnp.where(assigned, norm_metric[owner, jnp.arange(L)], 0.0)
    t_scores = jax.nn.one_hot(t_labels, num_classes) \
        * t_score_val[:, None]                                  # [L, NC]
    return t_labels, t_boxes, t_scores


def _varifocal_loss(logits, target_scores, t_labels, num_classes,
                    alpha=0.75, gamma=2.0):
    """VFL: positives weighted by their (soft) target score, negatives by
    alpha·p^gamma (PP-YOLOE classification loss)."""
    p = jax.nn.sigmoid(logits)
    pos = (t_labels < num_classes)[:, None] * (target_scores > 0)
    weight = jnp.where(pos, target_scores, alpha * p ** gamma)
    bce = jnp.maximum(logits, 0) - logits * target_scores \
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(weight * bce)


def _giou(b1, b2):
    iou = V.box_iou_xyxy(b1[:, None], b2[:, None])[:, 0, 0]
    x1 = jnp.minimum(b1[:, 0], b2[:, 0])
    y1 = jnp.minimum(b1[:, 1], b2[:, 1])
    x2 = jnp.maximum(b1[:, 2], b2[:, 2])
    y2 = jnp.maximum(b1[:, 3], b2[:, 3])
    hull = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)
    a1 = jnp.maximum(b1[:, 2] - b1[:, 0], 0) \
        * jnp.maximum(b1[:, 3] - b1[:, 1], 0)
    a2 = jnp.maximum(b2[:, 2] - b2[:, 0], 0) \
        * jnp.maximum(b2[:, 3] - b2[:, 1], 0)
    inter = iou * jnp.maximum(a1 + a2, 1e-9) / jnp.maximum(1 + iou, 1e-9)
    union = a1 + a2 - inter
    return iou - (hull - union) / jnp.maximum(hull, 1e-9)


class PPYOLOE(Module):
    """Full detector. ``loss(images, gt_boxes, gt_labels)`` for training
    (padded gt, -1 labels); ``predict(images, img_size)`` for eval
    (decoded + class-aware NMS, fixed-shape [B, keep_top_k, 6])."""

    def __init__(self, cfg: PPYOLOEConfig, key=None):
        keys = rng.split_key(key, 3)
        self.config = cfg
        self.backbone = CSPResNet(cfg, key=keys[0])
        bb = (cfg.stage_channels[-3], cfg.stage_channels[-2],
              cfg.stage_channels[-1])
        self.neck = CSPPAN(bb, cfg.neck_channels, key=keys[1])
        self.head = PPYOLOEHead(cfg, key=keys[2])

    def __call__(self, images, training: bool = False):
        feats = self.neck(self.backbone(images, training=training),
                          training=training)
        return self.head(feats, training=training)

    def _decode(self, reg_dist, points, strides):
        dist = _dfl_expect(reg_dist) * strides[None]        # [B, L, 4] px
        return V.distance2bbox(points[None], dist)

    def loss(self, images, gt_boxes, gt_labels, training: bool = True):
        """Scoped mixed precision: only the network forward
        (backbone/neck/head convs — the FLOPs) rides an ambient
        ``amp.auto_cast``; decode, TAL assignment (top-k/IoU) and the
        VFL/DFL/GIoU losses below are pinned fp32 via ``amp.suspend``.
        Whole-model autocast was far slower than fp32 on an earlier
        build (not measured on the current code): per-op cast
        boundaries inside the assignment break XLA fusion; the head
        outputs are small, so casting once here is free."""
        from paddle_tpu import amp as _amp

        cls_logits, reg_dist, points, strides = self(
            images, training=training)
        with _amp.suspend():
            cls_logits = cls_logits.astype(jnp.float32)
            reg_dist = reg_dist.astype(jnp.float32)
            return self._loss_tail(cls_logits, reg_dist, points, strides,
                                   gt_boxes, gt_labels)

    def _loss_tail(self, cls_logits, reg_dist, points, strides,
                   gt_boxes, gt_labels):
        cfg = self.config
        pred_boxes = self._decode(reg_dist, points, strides)
        pred_scores = jax.nn.sigmoid(cls_logits)

        assign = jax.vmap(lambda s, b, gb, gl: _tal_assign(
            s, b, points, gb, gl, topk=cfg.tal_topk, alpha=cfg.tal_alpha,
            beta=cfg.tal_beta, num_classes=cfg.num_classes))
        t_labels, t_boxes, t_scores = assign(
            jax.lax.stop_gradient(pred_scores),
            jax.lax.stop_gradient(pred_boxes), gt_boxes, gt_labels)

        B, L = t_labels.shape
        pos = t_labels < cfg.num_classes                      # [B, L]
        score_sum = jnp.maximum(jnp.sum(t_scores), 1.0)

        cls_loss = jax.vmap(lambda lg, ts, tl: _varifocal_loss(
            lg, ts, tl, cfg.num_classes))(cls_logits, t_scores,
                                          t_labels).sum() / score_sum

        # box losses on positives, weighted by the assigned soft score
    # (flatten batch; masked)
        w = jnp.where(pos, jnp.sum(t_scores, axis=-1), 0.0).reshape(-1)
        pb = pred_boxes.reshape(-1, 4)
        tb = t_boxes.reshape(-1, 4)
        giou = _giou(pb, tb)
        iou_loss = jnp.sum(w * (1.0 - giou)) / score_sum

        # DFL: distribution over bins vs the (clipped) true distance
        tdist = V.bbox2distance(
            jnp.broadcast_to(points[None], (B, L, 2)).reshape(-1, 2), tb,
            max_dist=None) / jnp.broadcast_to(
                strides[None], (B, L, 1)).reshape(-1, 1)
        tdist = jnp.clip(tdist, 0.0, cfg.reg_max - 0.01)      # [BL, 4]
        li = jnp.floor(tdist)
        wr = tdist - li
        logp = jax.nn.log_softmax(reg_dist.reshape(-1, 4, cfg.reg_max + 1),
                                  axis=-1)
        gl = jnp.take_along_axis(logp, li.astype(jnp.int32)[..., None],
                                 axis=-1)[..., 0]
        gr = jnp.take_along_axis(logp, (li + 1).astype(jnp.int32)[..., None],
                                 axis=-1)[..., 0]
        dfl = -(gl * (1 - wr) + gr * wr).mean(axis=-1)        # [BL]
        dfl_loss = jnp.sum(w * dfl) / score_sum

        total = (cfg.cls_weight * cls_loss + cfg.iou_weight * iou_loss
                 + cfg.dfl_weight * dfl_loss)
        return total

    def predict(self, images, img_size=None, training: bool = False):
        """→ (out [B, keep_top_k, 6] rows (label, score, x1, y1, x2, y2),
        num_valid [B])."""
        cfg = self.config
        cls_logits, reg_dist, points, strides = self(
            images, training=training)
        boxes = self._decode(reg_dist, points, strides)        # [B, L, 4]
        if img_size is not None:
            boxes = V.box_clip(boxes, img_size.astype(jnp.float32))
        scores = jax.nn.sigmoid(cls_logits).transpose(0, 2, 1)  # [B, NC, L]
        nms = jax.vmap(lambda b, s: V.multiclass_nms(
            b, s, cfg.score_threshold, cfg.nms_top_k, cfg.keep_top_k,
            cfg.nms_threshold, normalized=False))
        return nms(boxes, scores)


def ppyoloe_tiny(num_classes: int = 8, **kw):
    return PPYOLOE(PPYOLOEConfig.tiny(num_classes=num_classes), **kw)


def ppyoloe_s(num_classes: int = 80, **kw):
    return PPYOLOE(PPYOLOEConfig(num_classes=num_classes), **kw)
