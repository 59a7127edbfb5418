"""Plain reference of He et al.'s CIFAR ResNet family (arXiv 1512.03385,
Sec. 4.2) in the form the configurations state: a 3x3 stem, three stages
of ``blocks_per_stage`` basic blocks at widths (w, 2w, 4w), stride 2
entering stages 2 and 3, a 1x1 projection where the shape changes
(``shortcut: projection``), GroupNorm with 8 groups (``norm:
group_norm_8``), global average pooling and a linear classifier.
Straight ``jax.numpy``; it imports nothing of the program. ``prec`` is
the precision of every convolution and matrix product
(``reference.precision``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.precision import product


def check(model: dict) -> None:
    if model["norm"] != "group_norm_8" or model["shortcut"] != "projection":
        raise ValueError(f"the reference ResNet has group_norm_8 and "
                         f"projection shortcuts, not {model['norm']!r} and "
                         f"{model['shortcut']!r}")


def init(key, m: dict):
    w = m["width"]
    keys = iter(jax.random.split(key, 64))

    def conv(k, cin, cout):
        return (k * k * cin) ** -0.5 * jax.random.normal(
            next(keys), (k, k, cin, cout), jnp.float32)

    def norm(c):
        return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,))}

    p = {"stem": conv(3, m["in_channels"], w), "stem_norm": norm(w),
         "stages": []}
    cin = w
    for si, cout in enumerate((w, 2 * w, 4 * w)):
        blocks = []
        for bi in range(m["blocks_per_stage"]):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = {"conv1": conv(3, cin, cout), "norm1": norm(cout),
                   "conv2": conv(3, cout, cout), "norm2": norm(cout)}
            if cin != cout or stride != 1:
                blk["proj"] = conv(1, cin, cout)
            blocks.append(blk)
            cin = cout
        p["stages"].append(blocks)
    p["head_w"] = cin ** -0.5 * jax.random.normal(
        next(keys), (cin, m["n_classes"]), jnp.float32)
    p["head_b"] = jnp.zeros((m["n_classes"],))
    return p


def _gn(x, gamma, beta, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(b, h, w, c) * gamma + beta


def forward(p, x, prec):
    def conv(h, w, s=1):
        return product(lambda a, b, q: jax.lax.conv_general_dilated(
            a, b, (s, s), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=q), h, w, prec)

    h = jax.nn.relu(_gn(conv(x, p["stem"]), **p["stem_norm"]))
    for si, blocks in enumerate(p["stages"]):
        for bi, blk in enumerate(blocks):
            s = 2 if (bi == 0 and si > 0) else 1
            h2 = jax.nn.relu(_gn(conv(h, blk["conv1"], s), **blk["norm1"]))
            h2 = _gn(conv(h2, blk["conv2"]), **blk["norm2"])
            r = conv(h, blk["proj"], s) if "proj" in blk else h
            h = jax.nn.relu(r + h2)
    h = h.mean(axis=(1, 2))
    return product(lambda a, b, q: jnp.dot(a, b, precision=q), h,
                   p["head_w"], prec) + p["head_b"]


def per_sample_loss(p, x, y, prec):
    logits = forward(p, x, prec)
    return (jax.nn.logsumexp(logits, axis=-1)
            - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
