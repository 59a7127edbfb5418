"""Small ResNet classifier — the paper's own FL workload (speech keywords).

Pure-JAX functional ResNet (He et al., CVPR'16) over 1x32x32 mel-like inputs,
35 classes, sized for the edge-device simulation (matches the paper's
ResNet-on-Google-Speech setup at the FedScale scale).

Convolutions and the head run at full f32 precision. At the TPU's
default, which rounds f32 operands to bfloat16, a cohort's local SGD
depends on the width the cohort is compiled at (on a v5e by up to 10%
of a client's mean loss, ``precision_probe.py``): the host loop, which
trains the clients that succeeded, and the fused engines, which train
every slot, would rank clients differently and select different ones.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
F32 = jax.lax.Precision.HIGHEST


def _conv_init(key, k, cin, cout):
    scale = (k * k * cin) ** -0.5
    return scale * jax.random.normal(key, (k, k, cin, cout), jnp.float32)


def conv2d(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=F32)


def group_norm(x, gamma, beta, groups=8, eps=1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(B, H, W, C) * gamma + beta


def _norm_init(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}


def init_resnet(key, cfg) -> Params:
    w = cfg.width
    widths = [w, 2 * w, 4 * w]
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    p: Params = {
        "stem": _conv_init(keys[next(ki)], 3, cfg.in_channels, w),
        "stem_norm": _norm_init(w),
        "stages": [],
    }
    cin = w
    for si, cout in enumerate(widths):
        blocks = []
        for bi in range(cfg.blocks_per_stage):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = {
                "conv1": _conv_init(keys[next(ki)], 3, cin, cout),
                "norm1": _norm_init(cout),
                "conv2": _conv_init(keys[next(ki)], 3, cout, cout),
                "norm2": _norm_init(cout),
            }
            if cin != cout or stride != 1:
                blk["proj"] = _conv_init(keys[next(ki)], 1, cin, cout)
            blocks.append(blk)
            cin = cout
        p["stages"].append(blocks)
    p["head_w"] = (cin ** -0.5) * jax.random.normal(
        keys[next(ki)], (cin, cfg.n_classes), jnp.float32)
    p["head_b"] = jnp.zeros((cfg.n_classes,))
    return p


def resnet_forward(cfg, p: Params, x):
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    h = conv2d(x, p["stem"])
    h = jax.nn.relu(group_norm(h, **p["stem_norm"]))
    for si, blocks in enumerate(p["stages"]):
        for bi, blk in enumerate(blocks):
            r = h
            s = 2 if (bi == 0 and si > 0) else 1
            h2 = conv2d(h, blk["conv1"], stride=s)
            h2 = jax.nn.relu(group_norm(h2, **blk["norm1"]))
            h2 = conv2d(h2, blk["conv2"])
            h2 = group_norm(h2, **blk["norm2"])
            if "proj" in blk:
                r = conv2d(r, blk["proj"], stride=s)
            h = jax.nn.relu(r + h2)
    h = h.mean(axis=(1, 2))
    return jnp.dot(h, p["head_w"], precision=F32) + p["head_b"]


def resnet_loss(cfg, p: Params, batch):
    """batch: {x: (B,H,W,C), y: (B,)} -> (mean_loss, per_sample_loss)."""
    logits = resnet_forward(cfg, p, batch["x"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][:, None], axis=-1)[:, 0]
    per_sample = logz - gold
    return per_sample.mean(), per_sample


def resnet_accuracy(cfg, p: Params, batch):
    logits = resnet_forward(cfg, p, batch["x"])
    return (jnp.argmax(logits, -1) == batch["y"]).mean()
