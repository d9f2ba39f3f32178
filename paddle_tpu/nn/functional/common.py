"""Common functionals: linear, dropout, padding, embedding, interpolate …
(reference: nn/functional/common.py, input.py; operators/dropout_op.cu,
lookup_table_v2_op.cu, interpolate_v2, pad3d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import dtype as _dt
from ...framework.flags import flag_value
from ...framework.random import next_rng_key
from ...ops._helpers import norm_shape, to_tensor_like, value_of
from ...ops.dispatch import apply


def _precision():
    p = flag_value("tpu_matmul_precision")
    return None if p == "default" else p


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b with paddle weight layout [in_features, out_features]
    (reference matmul_v2 + elementwise_add; one fused MXU matmul here)."""
    x, weight = to_tensor_like(x), to_tensor_like(weight)
    if bias is not None:
        return apply(
            "linear",
            lambda v, w, b: jnp.matmul(v, w, precision=_precision()) + b,
            x, weight, to_tensor_like(bias),
        )
    return apply("linear", lambda v, w: jnp.matmul(v, w, precision=_precision()),
                 x, weight)


def _mask_key(k):
    """Re-key mask-bit generation onto the XLA RngBitGenerator ('rbg')
    PRNG: threefry materializes ~10 u32 vector ops per element, which on
    an HBM-bound transformer step made dropout cost 25% of step time
    (v5e, BERT-base b32: 102.7k -> 132.5k tok/s).  The threefry chain
    still provides the SEED (one tiny fold), so framework seeding
    semantics are unchanged; only the per-element bit generator differs.
    """
    seed = jax.random.key_data(k).reshape(-1)[:2].astype(jnp.uint32)
    return jax.random.wrap_key_data(jnp.tile(seed, 2)[:4], impl="rbg")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = to_tensor_like(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply("dropout_scale", lambda v: v * (1.0 - p), x)
        return x
    if p == 1.0:
        return apply("dropout", lambda v: jnp.zeros_like(v), x)
    key = next_rng_key()

    def f(v, k):
        shape = list(v.shape)
        if axis is not None:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(_mask_key(k), 1.0 - p, tuple(shape))
        keep = jnp.broadcast_to(keep, v.shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)
        return jnp.where(keep, v, 0.0).astype(v.dtype)

    # the key rides as an op INPUT (not a closure constant) so static-graph
    # replay can refresh it per run — otherwise every Executor.run would
    # re-apply the identical dropout mask
    from ...static.program import _active_recorder
    from ...tensor import Tensor as _Tensor

    key_t = _Tensor(key, stop_gradient=True)
    prog = _active_recorder()
    if prog is not None:
        from ...framework.random import default_generator

        prog.note_state(key_t, refresh=default_generator.split_key,
                        spec=("rng", None))
    return apply("dropout", f, x, key_t)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p=p, axis=list(ax), training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p=p, axis=list(ax), training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = to_tensor_like(x)
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772848170429916717
    scale = 1.0507009873554804934193349852946
    alpha_p = -alpha * scale
    key = next_rng_key()

    def f(v):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = (1.0 / ((1 - p) * (1 + p * alpha_p**2)) ** 0.5)
        b = -a * alpha_p * p
        return (a * jnp.where(keep, v, alpha_p) + b).astype(v.dtype)

    return apply("alpha_dropout", f, x)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    x = to_tensor_like(x)
    if isinstance(pad, (list, tuple)) and len(pad) == 2 * x.ndim and mode == "constant" \
            and not isinstance(pad[0], (list, tuple)):
        # full-rank paddle format: [d0_lo, d0_hi, d1_lo, d1_hi, ...]
        pairs = [(int(pad[2 * i]), int(pad[2 * i + 1])) for i in range(x.ndim)]
        return apply("pad", lambda v: jnp.pad(v, pairs, constant_values=value), x)

    # NCHW-style spatial pad: pad given as [left, right, top, bottom, ...] on
    # the spatial dims (reversed order, torch/paddle convention).
    n_spatial = x.ndim - 2
    pad = [int(value_of(p)) for p in pad]
    pairs_spatial = []
    for i in range(len(pad) // 2):
        pairs_spatial.append((pad[2 * i], pad[2 * i + 1]))
    pairs_spatial = pairs_spatial[::-1]  # last spatial dim listed first
    while len(pairs_spatial) < n_spatial:
        pairs_spatial.insert(0, (0, 0))
    if data_format.startswith("NC"):
        pairs = [(0, 0), (0, 0)] + pairs_spatial
    else:
        pairs = [(0, 0)] + pairs_spatial + [(0, 0)]

    jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge",
             "circular": "wrap"}[mode]

    def f(v):
        if jmode == "constant":
            return jnp.pad(v, pairs, constant_values=value)
        return jnp.pad(v, pairs, mode=jmode)

    return apply("pad3d", f, x)


def zeropad2d(x, padding, data_format="NCHW", name=None):
    return pad(x, padding, mode="constant", value=0.0, data_format=data_format)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Gather rows (reference lookup_table_v2).  ``sparse=True`` selects the
    SelectedRows grad path (selected_rows.h:41): the weight cotangent is an
    IndexedSlices of (touched rows, row grads) — the [vocab, dim] dense
    gradient is never materialized, and optimizers apply row-sparse updates
    (sparse_grad.rowwise_update)."""
    x, weight = to_tensor_like(x), to_tensor_like(weight)
    pad = None
    if padding_idx is not None:
        pad = padding_idx if padding_idx >= 0 else weight.shape[0] + padding_idx

    def f(w, idx):
        out = jnp.take(w, idx.astype(jnp.int32), axis=0)
        if pad is not None:
            mask = (idx == pad)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    from ...autograd.tape import Edge, GradNode, is_grad_enabled

    if sparse and is_grad_enabled() and weight._tracked:
        from ...ops.dispatch import wrap
        from ...sparse_grad import IndexedSlices, embedding_sparse_vjp

        out_val = f(weight._value, x._value)
        wgrad = embedding_sparse_vjp(x._value, weight.shape[0], pad)
        dense_shape = tuple(weight._value.shape)

        def vjp_fn(ct):
            rows, values = wgrad(ct)
            return (IndexedSlices(rows, values, dense_shape),)

        flat, treedef = jax.tree_util.tree_flatten(out_val)
        node = GradNode("lookup_table_v2_sparse", vjp_fn, [Edge(weight)],
                        [(out_val.shape, out_val.dtype)], treedef)
        return wrap(out_val, node=node, index=0)

    return apply("lookup_table_v2", f, weight, x)


def one_hot(x, num_classes, name=None):
    x = to_tensor_like(x)
    n = int(value_of(num_classes))
    return apply("one_hot_v2",
                 lambda v: jax.nn.one_hot(v.astype(jnp.int32), n, dtype=jnp.float32), x)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    label = to_tensor_like(label)
    if prior_dist is not None:
        pd = to_tensor_like(prior_dist)
        return apply("label_smooth",
                     lambda l, p: (1 - epsilon) * l + epsilon * p, label, pd)
    k = label.shape[-1]
    return apply("label_smooth", lambda l: (1 - epsilon) * l + epsilon / k, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    x1, x2 = to_tensor_like(x1), to_tensor_like(x2)

    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)

    return apply("cosine_similarity", f, x1, x2)


def bilinear(x1, x2, weight, bias=None, name=None):
    x1, x2, weight = to_tensor_like(x1), to_tensor_like(x2), to_tensor_like(weight)

    def f(a, b, w, *mb):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b, precision=_precision())
        if mb:
            out = out + mb[0]
        return out

    if bias is not None:
        return apply("bilinear", f, x1, x2, weight, to_tensor_like(bias))
    return apply("bilinear", f, x1, x2, weight)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW", name=None):
    x = to_tensor_like(x)
    channel_last = not data_format.startswith("NC")
    n_spatial = x.ndim - 2
    spatial_shape = x.shape[1:-1] if channel_last else x.shape[2:]
    if size is not None:
        out_size = tuple(int(value_of(s)) for s in (size if isinstance(size, (list, tuple)) else [size]))
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * n_spatial
        out_size = tuple(int(s * float(value_of(f_))) for s, f_ in zip(spatial_shape, sf))

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def f(v):
        if channel_last:
            target = (v.shape[0],) + out_size + (v.shape[-1],)
        else:
            target = (v.shape[0], v.shape[1]) + out_size
        if jmode == "nearest":
            return jax.image.resize(v, target, method="nearest")
        if align_corners:
            # jax.image.resize has no align_corners; emulate with explicit gather
            return _resize_align_corners(v, target, jmode, channel_last)
        return jax.image.resize(v, target, method=jmode)

    return apply("interpolate", f, x)


def _resize_align_corners(v, target, method, channel_last):
    nd = v.ndim
    spatial_axes = range(1, nd - 1) if channel_last else range(2, nd)
    out = v
    for ax, tgt in zip(spatial_axes, (target[1:-1] if channel_last else target[2:])):
        in_sz = out.shape[ax]
        if tgt == in_sz:
            continue
        if tgt == 1 or in_sz == 1:
            idx = jnp.zeros(tgt, jnp.float32)
        else:
            idx = jnp.linspace(0.0, in_sz - 1, tgt)
        lo = jnp.floor(idx).astype(jnp.int32)
        hi = jnp.minimum(lo + 1, in_sz - 1)
        w = (idx - lo).astype(v.dtype)
        shape = [1] * out.ndim
        shape[ax] = -1
        a = jnp.take(out, lo, axis=ax)
        b = jnp.take(out, hi, axis=ax)
        out = a * (1 - w.reshape(shape)) + b * w.reshape(shape)
    return out


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    x = to_tensor_like(x)
    r = int(upscale_factor)

    def f(v):
        if data_format == "NCHW":
            N, C, H, W = v.shape
            v = v.reshape(N, C // (r * r), r, r, H, W)
            v = jnp.transpose(v, (0, 1, 4, 2, 5, 3))
            return v.reshape(N, C // (r * r), H * r, W * r)
        N, H, W, C = v.shape
        v = v.reshape(N, H, W, r, r, C // (r * r))
        v = jnp.transpose(v, (0, 1, 3, 2, 4, 5))
        return v.reshape(N, H * r, W * r, C // (r * r))

    return apply("pixel_shuffle", f, x)


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    x = to_tensor_like(x)
    r = int(downscale_factor)

    def f(v):
        if data_format == "NCHW":
            N, C, H, W = v.shape
            v = v.reshape(N, C, H // r, r, W // r, r)
            v = jnp.transpose(v, (0, 1, 3, 5, 2, 4))
            return v.reshape(N, C * r * r, H // r, W // r)
        N, H, W, C = v.shape
        v = v.reshape(N, H // r, r, W // r, r, C)
        v = jnp.transpose(v, (0, 1, 3, 5, 2, 4))
        return v.reshape(N, H // r, W // r, C * r * r)

    return apply("pixel_unshuffle", f, x)


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    x = to_tensor_like(x)

    def f(v):
        if data_format == "NCHW":
            N, C, H, W = v.shape
            v = v.reshape(N, groups, C // groups, H, W)
            v = jnp.swapaxes(v, 1, 2)
            return v.reshape(N, C, H, W)
        N, H, W, C = v.shape
        v = v.reshape(N, H, W, groups, C // groups)
        v = jnp.swapaxes(v, 3, 4)
        return v.reshape(N, H, W, C)

    return apply("channel_shuffle", f, x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference operators/math/im2col) via conv patch extraction."""
    x = to_tensor_like(x)
    from .conv import _norm_tuple

    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    if isinstance(paddings, int):
        p = [(paddings, paddings), (paddings, paddings)]
    else:
        pl = list(paddings)
        if len(pl) == 2:
            p = [(pl[0], pl[0]), (pl[1], pl[1])]
        else:
            p = [(pl[0], pl[2]), (pl[1], pl[3])]

    def f(v):
        N, C, H, W = v.shape
        patches = jax.lax.conv_general_dilated_patches(
            v, filter_shape=k, window_strides=s, padding=p, rhs_dilation=d,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )
        # patches: [N, C*k0*k1, L0, L1] -> [N, C*k0*k1, L]
        return patches.reshape(N, patches.shape[1], -1)

    return apply("unfold", f, x)


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    x = to_tensor_like(x)
    from .conv import _norm_tuple

    out_hw = _norm_tuple(output_sizes, 2)
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d = _norm_tuple(dilations, 2)
    pp = _norm_tuple(paddings, 2) if not isinstance(paddings, int) else (paddings, paddings)

    def f(v):
        N, CK, L = v.shape
        C = CK // (k[0] * k[1])
        H = (out_hw[0] + 2 * pp[0] - (d[0] * (k[0] - 1) + 1)) // s[0] + 1
        W = (out_hw[1] + 2 * pp[1] - (d[1] * (k[1] - 1) + 1)) // s[1] + 1
        cols = v.reshape(N, C, k[0], k[1], H, W)
        out = jnp.zeros((N, C, out_hw[0] + 2 * pp[0], out_hw[1] + 2 * pp[1]), v.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                hi = i * d[0]
                wj = j * d[1]
                out = out.at[:, :, hi : hi + H * s[0] : s[0], wj : wj + W * s[1] : s[1]].add(
                    cols[:, :, i, j]
                )
        return out[:, :, pp[0] : pp[0] + out_hw[0], pp[1] : pp[1] + out_hw[1]]

    return apply("fold", f, x)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    theta = to_tensor_like(theta)
    shp = norm_shape(out_shape)

    def f(th):
        N, _, H, W = shp

        def axis_coords(n):
            if align_corners:
                return jnp.linspace(-1.0, 1.0, n)
            return (jnp.arange(n, dtype=jnp.float32) * 2 + 1) / n - 1.0

        ys = axis_coords(H)
        xs = axis_coords(W)
        gx, gy = jnp.meshgrid(xs, ys)
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # H,W,3
        return jnp.einsum("hwi,nji->nhwj", base, th)

    return apply("affine_grid", f, theta)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    x, grid = to_tensor_like(x), to_tensor_like(grid)

    def f(v, g):
        N, C, H, W = v.shape

        def unnorm(c, size):
            if align_corners:
                return (c + 1) * (size - 1) / 2
            return ((c + 1) * size - 1) / 2

        gx = unnorm(g[..., 0], W)
        gy = unnorm(g[..., 1], H)
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        x1, y1 = x0 + 1, y0 + 1

        def sample(yy, xx):
            yi = jnp.clip(yy, 0, H - 1).astype(jnp.int32)
            xi = jnp.clip(xx, 0, W - 1).astype(jnp.int32)
            out = v[jnp.arange(N)[:, None, None], :, yi, xi]  # N,Ho,Wo,C
            if padding_mode == "zeros":
                valid = ((yy >= 0) & (yy <= H - 1) & (xx >= 0) & (xx <= W - 1))
                out = out * valid[..., None].astype(out.dtype)
            return out

        if mode == "nearest":
            out = sample(jnp.round(gy), jnp.round(gx))
            return jnp.transpose(out, (0, 3, 1, 2))
        wa = (x1 - gx) * (y1 - gy)
        wb = (x1 - gx) * (gy - y0)
        wc = (gx - x0) * (y1 - gy)
        wd = (gx - x0) * (gy - y0)
        out = (
            sample(y0, x0) * wa[..., None]
            + sample(y1, x0) * wb[..., None]
            + sample(y0, x1) * wc[..., None]
            + sample(y1, x1) * wd[..., None]
        )
        return jnp.transpose(out, (0, 3, 1, 2)).astype(v.dtype)

    return apply("grid_sampler", f, x, grid)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    x = to_tensor_like(x)

    def f(v):
        NT, C, H, W = v.shape
        N = NT // seg_num
        v5 = v.reshape(N, seg_num, C, H, W)
        c1 = int(C * shift_ratio)
        c2 = int(C * 2 * shift_ratio)
        back = jnp.concatenate([v5[:, 1:, :c1], jnp.zeros_like(v5[:, :1, :c1])], axis=1)
        fwd = jnp.concatenate([jnp.zeros_like(v5[:, :1, c1:c2]), v5[:, :-1, c1:c2]], axis=1)
        keep = v5[:, :, c2:]
        return jnp.concatenate([back, fwd, keep], axis=2).reshape(NT, C, H, W)

    return apply("temporal_shift", f, x)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    from . import loss as _loss

    return _loss.npair_loss(anchor, positive, labels, l2_reg)
