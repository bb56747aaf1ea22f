"""Neural-net functional ops: linear/conv/pool/norm/dropout/embedding/losses.

Parity surface: `python/paddle/nn/functional/` in the reference, with kernels
from `phi/kernels/gpudnn/` (conv/pool via cuDNN) and `phi/kernels/gpu/`
replaced by XLA-native lowerings:
  - conv → `lax.conv_general_dilated` (XLA tiles it onto the MXU directly;
    no cuDNN algorithm search — XLA autotunes),
  - norm ops → fused elementwise+reduce jnp expressions (XLA fusion does what
    the reference's hand-fused `layer_norm_kernel.cu` does),
  - attention → `scaled_dot_product_attention` with optional Pallas flash
    kernel on TPU (reference: `fused_attention_op.cu`, dynloaded flashattn).
Data layout: paddle uses NCHW by default; on TPU, XLA canonicalizes layouts
internally so we keep the NCHW API and let XLA choose tilings.
"""
from __future__ import annotations

import builtins
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core import random as prandom
from ..core.dispatch import forward, unwrap
from ..core.dispatch import note as _note
from ..core.tensor import Tensor

__all__ = [
    "linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
    "conv2d_transpose", "conv3d_transpose", "max_pool1d", "max_pool2d",
    "max_pool3d", "avg_pool1d", "avg_pool2d", "avg_pool3d",
    "adaptive_avg_pool1d", "adaptive_avg_pool2d", "adaptive_avg_pool3d",
    "adaptive_max_pool2d", "batch_norm", "layer_norm", "instance_norm",
    "group_norm", "rms_norm", "local_response_norm", "normalize", "dropout",
    "dropout2d", "dropout3d", "alpha_dropout", "embedding", "one_hot",
    "interpolate", "upsample", "pixel_shuffle", "pixel_unshuffle",
    "grid_sample", "affine_grid", "unfold", "fold",
    "cross_entropy", "softmax_with_cross_entropy", "mse_loss", "l1_loss",
    "nll_loss", "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "hinge_embedding_loss",
    "cosine_similarity", "cosine_embedding_loss", "label_smooth",
    "log_loss", "square_error_cost", "sigmoid_focal_loss", "dice_loss",
    "ctc_loss", "triplet_margin_loss", "pairwise_distance", "npair_loss",
    "scaled_dot_product_attention", "paged_attention", "flash_prefill",
    "paged_kv_write",
    "paged_kv_view", "sequence_mask",
    "temporal_shift", "channel_shuffle",
]


# =========================== linear / conv ===================================

def linear(x, weight, bias=None, name=None):
    """y = x @ W + b (reference `phi/kernels/impl/matmul_kernel_impl.h` +
    bias epilogue; XLA fuses the bias add into the MXU matmul)."""
    if bias is None:
        return forward(lambda a, w: a @ w, (x, weight), name="linear")
    return forward(lambda a, w, b: a @ w + b, (x, weight, bias), name="linear")


def _norm_tuple(v, n):
    if isinstance(v, (int, np.integer)):
        return (int(v),) * n
    return tuple(int(i) for i in v)


def normalize_conv_padding(n, padding, channels_last):
    """Paddle conv padding forms -> "SAME"/"VALID" or n (lo, hi) pairs:
    int, [p_dim...], [lo0, hi0, lo1, hi1, ...], [(lo, hi)...] spatial
    pairs, or the full-rank pairs form including batch/channel dims
    (which must be zero-padded)."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, (int, np.integer)):
        return [(int(padding), int(padding))] * n
    padding = list(padding)
    if padding and isinstance(padding[0], (list, tuple)):
        pairs = [tuple(int(q) for q in p) for p in padding]
        if len(pairs) == n:
            return pairs
        if len(pairs) == n + 2:
            # full-rank form: [N, (spatial...), C] or [N, C, spatial...]
            other = [pairs[0], pairs[-1]] if channels_last else pairs[:2]
            spatial = pairs[1:1 + n] if channels_last else pairs[2:]
            if any(p != (0, 0) for p in other):
                raise ValueError(
                    "conv padding on batch/channel dims must be (0, 0); "
                    f"got {padding!r}")
            return spatial
        raise ValueError(f"conv padding pairs form needs {n} or {n + 2} "
                         f"pairs; got {padding!r}")
    if len(padding) == n:
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1]))
                for i in range(n)]
    raise ValueError(f"unsupported conv padding form {padding!r}")


def _conv_nd(n, x, weight, bias, stride, padding, dilation, groups,
             data_format, name):
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    channels_last = data_format in ("NHWC", "NLC", "NDHWC")
    pad = normalize_conv_padding(n, padding, channels_last)
    spatial = "".join("DHW"[3 - n:])
    if channels_last:
        dn_in = "N" + spatial + "C"
    else:
        dn_in = "NC" + spatial
    dn = jax.lax.conv_dimension_numbers(
        x._data.shape if isinstance(x, Tensor) else x.shape,
        weight._data.shape if isinstance(weight, Tensor) else weight.shape,
        (dn_in, "OI" + spatial, dn_in))

    def f(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad, rhs_dilation=dilation,
            dimension_numbers=dn, feature_group_count=groups,
            preferred_element_type=None)
        if b:
            bias_shape = [1] * out.ndim
            # out_spec = (batch_pos, feature_pos, *spatial_pos): the
            # channel lands at out_spec[1] (.index(1) found the POSITION
            # holding the value 1 — wrong for NHWC, where that's H)
            bias_shape[dn.out_spec[1] if hasattr(dn, "out_spec")
                       else (out.ndim - 1 if channels_last else 1)] = -1
            out = out + b[0].reshape(bias_shape)
        return out

    ins = (x, weight) if bias is None else (x, weight, bias)
    return forward(f, ins, name=name)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _conv_nd(1, x, weight, bias, stride, padding, dilation, groups,
                    data_format, "conv1d")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv_nd(2, x, weight, bias, stride, padding, dilation, groups,
                    data_format, "conv2d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv_nd(3, x, weight, bias, stride, padding, dilation, groups,
                    data_format, "conv3d")


def _conv_transpose_nd(n, x, weight, bias, stride, padding, output_padding,
                       dilation, groups, data_format, name):
    stride = _norm_tuple(stride, n)
    dilation = _norm_tuple(dilation, n)
    if isinstance(padding, (int, np.integer)):
        padding = _norm_tuple(padding, n)
    else:
        padding = tuple(int(p) for p in padding)
    out_pad = _norm_tuple(output_padding, n)
    spatial = "".join("DHW"[3 - n:])
    channels_last = data_format in ("NHWC", "NLC", "NDHWC")
    dn_in = ("N" + spatial + "C") if channels_last else ("NC" + spatial)

    def f(a, w, *b):
        # grad-of-conv formulation: transposed conv = lhs-dilated conv with
        # flipped spatial kernel and swapped I/O channels
        # (reference: conv2d_transpose → cudnnConvolutionBackwardData)
        k = [(w.shape[2 + i] - 1) * dilation[i] for i in range(n)]
        pad = [(k[i] - padding[i], k[i] - padding[i] + out_pad[i])
               for i in range(n)]
        w_flip = jnp.flip(w, axis=tuple(range(2, 2 + n)))
        # weight layout is (in, out//groups, *k) for paddle conv_transpose
        w_t = jnp.swapaxes(w_flip, 0, 1)
        if groups > 1:
            ci, co_g = w.shape[0], w.shape[1]
            wg = w_flip.reshape((groups, ci // groups, co_g) + w.shape[2:])
            wg = jnp.swapaxes(wg, 1, 2)
            w_t = wg.reshape((co_g * groups, ci // groups) + w.shape[2:])
        dn = jax.lax.conv_dimension_numbers(a.shape, w_t.shape,
                                            (dn_in, "OI" + spatial, dn_in))
        out = jax.lax.conv_general_dilated(
            a, w_t, window_strides=(1,) * n, padding=pad,
            lhs_dilation=stride, rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups)
        if b:
            shape = [1] * out.ndim
            shape[out.ndim - 1 if channels_last else 1] = -1
            out = out + b[0].reshape(shape)
        return out

    ins = (x, weight) if bias is None else (x, weight, bias)
    return forward(f, ins, name=name)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     data_format="NCL", name=None):
    return _conv_transpose_nd(1, x, weight, bias, stride, padding,
                              output_padding, dilation, groups, data_format,
                              "conv1d_transpose")


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     data_format="NCHW", output_size=None, name=None):
    return _conv_transpose_nd(2, x, weight, bias, stride, padding,
                              output_padding, dilation, groups, data_format,
                              "conv2d_transpose")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     data_format="NCDHW", output_size=None, name=None):
    return _conv_transpose_nd(3, x, weight, bias, stride, padding,
                              output_padding, dilation, groups, data_format,
                              "conv3d_transpose")


# =========================== pooling =========================================

def _pool_nd(n, x, kind, kernel_size, stride, padding, ceil_mode, data_format,
             count_include_pad=True, name="pool"):
    ks = _norm_tuple(kernel_size, n)
    st = _norm_tuple(stride if stride is not None else kernel_size, n)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _norm_tuple(padding, n)
        pad = [(pi, pi) for pi in p]
    channels_last = data_format in ("NHWC", "NLC", "NDHWC")
    if channels_last:
        dims = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
        pads = pad if isinstance(pad, str) else [(0, 0)] + list(pad) + [(0, 0)]
    else:
        dims = (1, 1) + ks
        strides = (1, 1) + st
        pads = pad if isinstance(pad, str) else [(0, 0), (0, 0)] + list(pad)

    def f(a):
        if kind == "max":
            init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
            return jax.lax.reduce_window(a, init, jax.lax.max, dims, strides, pads)
        s = jax.lax.reduce_window(a, 0.0, jax.lax.add, dims, strides, pads)
        if count_include_pad or isinstance(pads, str):
            denom = np.prod(ks)
            return s / denom
        ones = jnp.ones_like(a)
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strides, pads)
        return s / cnt

    return forward(f, (x,), name=name)


def _max_pool_maybe_mask(n, x, kernel_size, stride, padding, return_mask,
                         ceil_mode, data_format, name):
    if return_mask:
        # reference max_pool*(return_mask=True) → max_pool_with_index
        # kernel; only the default layout + numeric padding make sense for
        # flat in-plane indices
        if data_format not in ("NCL", "NCHW", "NCDHW"):
            raise ValueError(
                f"{name}(return_mask=True) requires channels-first layout, "
                f"got {data_format!r}")
        if isinstance(padding, str):
            raise ValueError(
                f"{name}(return_mask=True) requires numeric padding")
        f = _max_pool_index_nd(n, x, kernel_size, stride, padding)
        return forward(f, (x,), name=f"{name}_with_index")
    return _pool_nd(n, x, "max", kernel_size, stride, padding, ceil_mode,
                    data_format, name=name)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCL", name=None):
    return _max_pool_maybe_mask(1, x, kernel_size, stride, padding,
                                return_mask, ceil_mode, data_format,
                                "max_pool1d")


def max_pool2d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCHW", name=None):
    return _max_pool_maybe_mask(2, x, kernel_size, stride, padding,
                                return_mask, ceil_mode, data_format,
                                "max_pool2d")


def max_pool3d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, data_format="NCDHW", name=None):
    return _max_pool_maybe_mask(3, x, kernel_size, stride, padding,
                                return_mask, ceil_mode, data_format,
                                "max_pool3d")


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, data_format="NCL", name=None):
    return _pool_nd(1, x, "avg", kernel_size, stride, padding, ceil_mode,
                    data_format, count_include_pad=not exclusive,
                    name="avg_pool1d")


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool_nd(2, x, "avg", kernel_size, stride, padding, ceil_mode,
                    data_format, count_include_pad=not exclusive,
                    name="avg_pool2d")


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    return _pool_nd(3, x, "avg", kernel_size, stride, padding, ceil_mode,
                    data_format, count_include_pad=not exclusive,
                    name="avg_pool3d")


def _adaptive_pool(x, output_size, n, kind, data_format):
    out_sz = _norm_tuple(output_size, n)

    def f(a):
        # channels-first assumed (paddle default)
        spatial = a.shape[2:2 + n]
        out = a
        for d in range(n):
            in_d = spatial[d]
            out_d = out_sz[d]
            if in_d % out_d == 0:
                k = in_d // out_d
                shape = out.shape[:2 + d] + (out_d, k) + out.shape[2 + d + 1:]
                r = out.reshape(shape)
                out = r.max(axis=2 + d + 1) if kind == "max" else r.mean(axis=2 + d + 1)
            else:
                # general case: mean/max over variable windows via cumsum trick
                starts = (np.arange(out_d) * in_d) // out_d
                ends = ((np.arange(out_d) + 1) * in_d + out_d - 1) // out_d
                slices = [jnp.take(out, jnp.arange(s, e), axis=2 + d).max(axis=2 + d)
                          if kind == "max" else
                          jnp.take(out, jnp.arange(s, e), axis=2 + d).mean(axis=2 + d)
                          for s, e in zip(starts, ends)]
                out = jnp.stack(slices, axis=2 + d)
        return out

    return forward(f, (x,), name=f"adaptive_{kind}_pool{n}d")


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_pool(x, output_size, 1, "avg", "NCL")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool(x, output_size, 2, "avg", data_format)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool(x, output_size, 3, "avg", data_format)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive_pool(x, output_size, 2, "max", "NCHW")


# =========================== normalization ===================================
# Stats accumulate in fp32 for low-precision inputs (the reference's CUDA
# norm kernels do the same; on fp16 the BACKWARD of rsqrt(var+eps) produces
# (var+eps)^-1.5 ~ 3e7 which overflows fp16's 65504 max into inf -> NaN).

def _stats_cast(a):
    if a.dtype in (jnp.float16, jnp.bfloat16):
        return a.astype(jnp.float32)
    return a

def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Reference `phi/kernels/gpu/batch_norm_kernel.cu` (cuDNN BN). On TPU the
    reduce+scale fuses into one XLA kernel. Running-stat update is functional:
    in training mode the caller's running_mean/var tensors are rebound to the
    updated values (mirroring the reference's in-place MeanOut/VarianceOut)."""
    channels_last = data_format in ("NHWC", "NLC", "NDHWC")
    use_batch_stats = training and not (use_global_stats or False)

    ch_axis = (x._data.ndim - 1) if channels_last else 1
    red_axes = tuple(i for i in range(x._data.ndim) if i != ch_axis)

    def f_train(a, rm, rv, *wb):
        af = _stats_cast(a)
        mean = af.mean(axis=red_axes)
        var = af.var(axis=red_axes)
        shape = [1] * a.ndim
        shape[ch_axis] = -1
        inv = jax.lax.rsqrt(var + epsilon)
        out = ((af - mean.reshape(shape)) *
               inv.reshape(shape)).astype(a.dtype)
        if wb:
            w, b = wb
            out = out * w.reshape(shape) + b.reshape(shape)
        n = a.size // a.shape[ch_axis]
        unbiased = var * n / builtins.max(n - 1, 1)
        new_rm = momentum * rm + (1 - momentum) * mean.astype(rm.dtype)
        new_rv = momentum * rv + (1 - momentum) * unbiased.astype(rv.dtype)
        return out, new_rm, new_rv

    def f_eval(a, rm, rv, *wb):
        af = _stats_cast(a)
        shape = [1] * a.ndim
        shape[ch_axis] = -1
        inv = jax.lax.rsqrt(_stats_cast(rv) + epsilon)
        out = ((af - _stats_cast(rm).reshape(shape)) *
               inv.reshape(shape)).astype(a.dtype)
        if wb:
            w, b = wb
            out = out * w.reshape(shape) + b.reshape(shape)
        return out

    wb = ()
    if weight is not None:
        wb = (weight, bias)
    if use_batch_stats:
        out, new_rm, new_rv = forward(f_train, (x, running_mean, running_var, *wb),
                                      name="batch_norm")
        running_mean._data = new_rm._data
        running_var._data = new_rv._data
        return out
    return forward(f_eval, (x, running_mean, running_var, *wb),
                   name="batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = (int(normalized_shape),)
    n = len(tuple(normalized_shape))

    def f(a, *wb):
        axes = tuple(range(a.ndim - n, a.ndim))
        af = _stats_cast(a)
        mean = af.mean(axis=axes, keepdims=True)
        var = af.var(axis=axes, keepdims=True)
        out = ((af - mean) * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        if wb:
            w = wb[0]
            out = out * w
            if len(wb) > 1:
                out = out + wb[1]
        return out

    ins = [x]
    if weight is not None:
        ins.append(weight)
    if bias is not None:
        ins.append(bias)
    return forward(f, tuple(ins), name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    def f(a, *w):
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
        out = (a.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        return out * w[0] if w else out
    ins = (x,) if weight is None else (x, weight)
    return forward(f, ins, name="rms_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    def f(a, *wb):
        axes = tuple(range(2, a.ndim))
        af = _stats_cast(a)
        mean = af.mean(axis=axes, keepdims=True)
        var = af.var(axis=axes, keepdims=True)
        out = ((af - mean) * jax.lax.rsqrt(var + eps)).astype(a.dtype)
        if wb:
            shape = [1, -1] + [1] * (a.ndim - 2)
            out = out * wb[0].reshape(shape)
            if len(wb) > 1:
                out = out + wb[1].reshape(shape)
        return out
    ins = [x]
    if weight is not None:
        ins.append(weight)
    if bias is not None:
        ins.append(bias)
    return forward(f, tuple(ins), name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def f(a, *wb):
        N, C = a.shape[0], a.shape[1]
        rest = a.shape[2:]
        g = _stats_cast(a).reshape((N, num_groups, C // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean = g.mean(axis=axes, keepdims=True)
        var = g.var(axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)
               ).reshape(a.shape).astype(a.dtype)
        if wb:
            shape = [1, -1] + [1] * (a.ndim - 2)
            out = out * wb[0].reshape(shape)
            if len(wb) > 1:
                out = out + wb[1].reshape(shape)
        return out
    ins = [x]
    if weight is not None:
        ins.append(weight)
    if bias is not None:
        ins.append(bias)
    return forward(f, tuple(ins), name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def f(a):
        sq = jnp.square(a)
        half = size // 2
        pad_cfg = [(0, 0)] * a.ndim
        pad_cfg[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_cfg)
        acc = sum(jax.lax.slice_in_dim(padded, i, i + a.shape[1], axis=1)
                  for i in range(size))
        return a / jnp.power(k + alpha * acc / size, beta)
    return forward(f, (x,), name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(a):
        nrm = jnp.power(jnp.sum(jnp.power(jnp.abs(a), p), axis=axis,
                                keepdims=True), 1.0 / p)
        return a / jnp.maximum(nrm, epsilon)
    return forward(f, (x,), name="normalize")


# =========================== dropout / embedding =============================

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    """Reference `phi/kernels/gpu/dropout_kernel.cu`. The mask draw uses the
    functional generator (TP-safe dropout = seeding per mesh axis, see
    distributed.fleet.meta_parallel.random)."""
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return forward(lambda a: a * (1.0 - p), (x,), name="dropout")
        return forward(lambda a: a, (x,), name="dropout")
    ax = tuple(axis) if isinstance(axis, (list, tuple)) else (None if axis is None else (axis,))

    def f(k, a):
        shape = a.shape if ax is None else tuple(
            a.shape[i] if i in ax else 1 for i in range(a.ndim))
        keep = jax.random.bernoulli(k, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return forward(f, (prandom.split_key(), x), name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return forward(lambda a: a, (x,), name="alpha_dropout")
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(k, a):
        keep = jax.random.bernoulli(k, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return (a_coef * jnp.where(keep, a, alpha_p) + b_coef).astype(a.dtype)

    return forward(f, (prandom.split_key(), x), name="alpha_dropout")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Reference `phi/kernels/gpu/embedding_kernel.cu`. XLA lowers take() to a
    gather; under jit the backward scatter-add fuses into the update, so
    traced code always uses the dense path. `sparse=True` honors the
    reference's SelectedRows gradient in EAGER mode: weight.grad becomes
    a SelectedRows (rows = looked-up ids, values = output cotangents)
    and row-capable optimizers (SGD, Adam lazy_mode) update only those
    rows — `phi/kernels/selected_rows/` role."""
    def f(i, w):
        out = jnp.take(w, i, axis=0)
        if padding_idx is not None:
            mask = (i == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    if sparse:
        from ..core import dispatch as _dispatch
        from ..core import lazy as _lazy
        from ..core import autograd as ag
        from ..core.selected_rows import SelectedRows
        from ..core.dispatch import trace_state_clean

        eager = (_dispatch.static_recorder is None and not _lazy.enabled()
                 and _dispatch.amp_cast_hook is None and trace_state_clean()
                 and ag.is_grad_enabled()
                 and isinstance(weight, Tensor) and not weight.stop_gradient
                 # leaf tables only: an upstream node's jax pullback
                 # cannot consume a SelectedRows cotangent, so a derived
                 # table (w * s, casted, ...) keeps the dense path
                 and weight._grad_node is None)
        if eager:
            ids = unwrap(x)
            w = unwrap(weight)
            out = f(ids, w)
            V = w.shape[0]

            def vjp_fn(cts, _ids=ids, _V=V):
                ct = cts[0]
                flat_ids = _ids.reshape(-1)
                vals = ct.reshape((-1,) + ct.shape[len(_ids.shape):])
                if padding_idx is not None:
                    keep = flat_ids != padding_idx
                    vals = vals * keep[:, None].astype(vals.dtype)
                return (None, SelectedRows(flat_ids, vals, _V))

            node = ag.GradNode("embedding_sparse", vjp_fn,
                               [(out.shape, out.dtype)],
                               [None, ("leaf", weight)])
            t = Tensor(out, stop_gradient=False)
            t._grad_node, t._out_idx = node, 0
            return t
    return forward(f, (x, weight), name="embedding")


def one_hot(x, num_classes, name=None):
    from .creation import one_hot as _oh
    return _oh(x, num_classes)


# =========================== resize / shuffle ================================

def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    def f(a):
        spatial_in = a.shape[2:]
        if size is not None:
            out_sz = _norm_tuple(size, len(spatial_in))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * len(spatial_in)
            out_sz = tuple(int(s * f_) for s, f_ in zip(spatial_in, sf))
        method = {"nearest": "nearest", "bilinear": "linear",
                  "trilinear": "linear", "bicubic": "cubic",
                  "linear": "linear", "area": "linear"}[mode]
        out_shape = a.shape[:2] + out_sz
        if method == "nearest":
            idxs = [jnp.clip((jnp.arange(o) * (i / o)).astype(jnp.int32), 0, i - 1)
                    for o, i in zip(out_sz, spatial_in)]
            out = a
            for d, idx in enumerate(idxs):
                out = jnp.take(out, idx, axis=2 + d)
            return out
        return jax.image.resize(a, out_shape, method=method)
    return forward(f, (x,), name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = int(upscale_factor)
    def f(a):
        N, C, H, W = a.shape
        out = a.reshape(N, C // (r * r), r, r, H, W)
        out = out.transpose(0, 1, 4, 2, 5, 3)
        return out.reshape(N, C // (r * r), H * r, W * r)
    return forward(f, (x,), name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = int(downscale_factor)
    def f(a):
        N, C, H, W = a.shape
        out = a.reshape(N, C, H // r, r, W // r, r)
        out = out.transpose(0, 1, 3, 5, 2, 4)
        return out.reshape(N, C * r * r, H // r, W // r)
    return forward(f, (x,), name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def f(a):
        N, C, H, W = a.shape
        return a.reshape(N, groups, C // groups, H, W).transpose(0, 2, 1, 3, 4) \
                .reshape(N, C, H, W)
    return forward(f, (x,), name="channel_shuffle")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def f(a):
        NT, C, H, W = a.shape
        N = NT // seg_num
        v = a.reshape(N, seg_num, C, H, W)
        c1 = int(C * shift_ratio)
        c2 = int(C * 2 * shift_ratio)
        left = jnp.concatenate([v[:, 1:, :c1], jnp.zeros_like(v[:, :1, :c1])], 1)
        mid = jnp.concatenate([jnp.zeros_like(v[:, :1, c1:c2]), v[:, :-1, c1:c2]], 1)
        rest = v[:, :, c2:]
        return jnp.concatenate([left, mid, rest], axis=2).reshape(NT, C, H, W)
    return forward(f, (x,), name="temporal_shift")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    def f(a, g):
        N, C, H, W = a.shape
        gx, gy = g[..., 0], g[..., 1]
        if align_corners:
            ix = (gx + 1) * (W - 1) / 2
            iy = (gy + 1) * (H - 1) / 2
        else:
            ix = ((gx + 1) * W - 1) / 2
            iy = ((gy + 1) * H - 1) / 2
        x0 = jnp.floor(ix).astype(jnp.int32)
        y0 = jnp.floor(iy).astype(jnp.int32)
        x1, y1 = x0 + 1, y0 + 1
        wx = ix - x0
        wy = iy - y0

        def sample(yy, xx):
            valid = (xx >= 0) & (xx < W) & (yy >= 0) & (yy < H)
            xx = jnp.clip(xx, 0, W - 1)
            yy = jnp.clip(yy, 0, H - 1)
            out = a[jnp.arange(N)[:, None, None], :, yy, xx]
            return jnp.where(valid[..., None], out, 0.0)

        v00 = sample(y0, x0)
        v01 = sample(y0, x1)
        v10 = sample(y1, x0)
        v11 = sample(y1, x1)
        out = (v00 * ((1 - wx) * (1 - wy))[..., None]
               + v01 * (wx * (1 - wy))[..., None]
               + v10 * ((1 - wx) * wy)[..., None]
               + v11 * (wx * wy)[..., None])
        return jnp.moveaxis(out, -1, 1)
    return forward(f, (x, grid), name="grid_sample")


def affine_grid(theta, out_shape, align_corners=True, name=None):
    shape = _norm_tuple(out_shape, len(out_shape))
    def f(th):
        N, _, H, W = shape
        if align_corners:
            xs = jnp.linspace(-1, 1, W)
            ys = jnp.linspace(-1, 1, H)
        else:
            xs = jnp.linspace(-1 + 1 / W, 1 - 1 / W, W)
            ys = jnp.linspace(-1 + 1 / H, 1 - 1 / H, H)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # H,W,3
        return jnp.einsum("hwk,njk->nhwj", base, th)
    return forward(f, (theta,), name="affine_grid")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)
    dl = _norm_tuple(dilations, 2)
    def f(a):
        N, C, H, W = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=ks, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl)
        # patches: N, C*kh*kw, oh, ow
        return patches.reshape(N, patches.shape[1], -1)
    return forward(f, (x,), name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    out_sz = _norm_tuple(output_sizes, 2)
    ks = _norm_tuple(kernel_sizes, 2)
    st = _norm_tuple(strides, 2)
    pd = _norm_tuple(paddings, 2)
    def f(a):
        N, CKK, L = a.shape
        C = CKK // (ks[0] * ks[1])
        oh = (out_sz[0] + 2 * pd[0] - ks[0]) // st[0] + 1
        ow = (out_sz[1] + 2 * pd[1] - ks[1]) // st[1] + 1
        cols = a.reshape(N, C, ks[0], ks[1], oh, ow)
        out = jnp.zeros((N, C, out_sz[0] + 2 * pd[0], out_sz[1] + 2 * pd[1]),
                        a.dtype)
        for i in range(ks[0]):
            for j in range(ks[1]):
                out = out.at[:, :, i:i + oh * st[0]:st[0],
                             j:j + ow * st[1]:st[1]].add(cols[:, :, i, j])
        return out[:, :, pd[0]:out.shape[2] - pd[0] or None,
                   pd[1]:out.shape[3] - pd[1] or None]
    return forward(f, (x,), name="fold")


# =========================== losses ==========================================

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True,
                  label_smoothing=0.0, name=None):
    """Reference `python/paddle/nn/functional/loss.py` cross_entropy →
    `c_softmax_with_cross_entropy` kernels. Single fused logsumexp on TPU."""
    def f(logits, lab, *w):
        lp = jax.nn.log_softmax(logits, axis=axis) if use_softmax else jnp.log(
            jnp.maximum(logits, 1e-30))
        if soft_label:
            loss = -(lab * lp).sum(axis=axis)
        else:
            lab_ = lab.astype(jnp.int32)
            if lab_.ndim == lp.ndim:
                lab_ = lab_.squeeze(axis)
            if label_smoothing > 0.0:
                n = lp.shape[axis]
                onehot = jax.nn.one_hot(lab_, n, dtype=lp.dtype, axis=axis)
                soft = onehot * (1 - label_smoothing) + label_smoothing / n
                loss = -(soft * lp).sum(axis=axis)
            else:
                loss = -jnp.take_along_axis(
                    lp, jnp.expand_dims(lab_, axis), axis=axis).squeeze(axis)
            if ignore_index >= 0:
                mask = (lab_ != ignore_index)
                loss = jnp.where(mask, loss, 0.0)
                if reduction == "mean":
                    return loss.sum() / jnp.maximum(mask.sum(), 1)
            if w:
                loss = loss * jnp.take(w[0], lab_)
        return _reduce_loss(loss, reduction)
    ins = (input, label) if weight is None else (input, label, weight)
    return forward(f, ins, name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    # paddle returns loss with label's dims (keepdim on class axis)
    from .manipulation import unsqueeze
    loss = unsqueeze(loss, axis)
    if return_softmax:
        from .activation import softmax as _sm
        return loss, _sm(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean", name=None):
    return forward(lambda a, b: _reduce_loss(jnp.square(a - b), reduction),
                   (input, label), name="mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    return forward(lambda a, b: _reduce_loss(jnp.abs(a - b), reduction),
                   (input, label), name="l1_loss")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def f(lp, lab, *w):
        lab_ = lab.astype(jnp.int32)
        loss = -jnp.take_along_axis(lp, lab_[:, None], axis=1).squeeze(1)
        wt = jnp.ones_like(loss) if not w else jnp.take(w[0], lab_)
        if ignore_index >= 0:
            wt = jnp.where(lab_ == ignore_index, 0.0, wt)
        loss = loss * wt
        if reduction == "mean":
            return loss.sum() / jnp.maximum(wt.sum(), 1e-12)
        return _reduce_loss(loss, reduction)
    ins = (input, label) if weight is None else (input, label, weight)
    return forward(f, ins, name="nll_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def f(p, y, *w):
        loss = -(y * jnp.log(jnp.maximum(p, 1e-12))
                 + (1 - y) * jnp.log(jnp.maximum(1 - p, 1e-12)))
        if w:
            loss = loss * w[0]
        return _reduce_loss(loss, reduction)
    ins = (input, label) if weight is None else (input, label, weight)
    return forward(f, ins, name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    def f(z, y, *rest):
        loss = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        i = 0
        if pos_weight is not None:
            pw = rest[i]; i += 1
            log_w = (pw - 1) * y + 1
            loss = loss * log_w
        if weight is not None:
            loss = loss * rest[i]
        return _reduce_loss(loss, reduction)
    ins = [logit, label]
    if pos_weight is not None:
        ins.append(pos_weight)
    if weight is not None:
        ins.append(weight)
    return forward(f, tuple(ins), name="bce_with_logits")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce_loss(loss, reduction)
    return forward(f, (input, label), name="smooth_l1_loss")


def kl_div(input, label, reduction="mean", name=None):
    def f(lp, y):
        loss = y * (jnp.log(jnp.maximum(y, 1e-12)) - lp)
        if reduction == "batchmean":
            return loss.sum() / lp.shape[0]
        return _reduce_loss(loss, reduction)
    return forward(f, (input, label), name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return forward(
        lambda a, b, y: _reduce_loss(jnp.maximum(0.0, -y * (a - b) + margin),
                                     reduction),
        (input, other, label), name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return forward(
        lambda a, y: _reduce_loss(
            jnp.where(y == 1, a, jnp.maximum(0.0, margin - a)), reduction),
        (input, label), name="hinge_embedding_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def f(a, b):
        dot = (a * b).sum(axis=axis)
        na = jnp.sqrt(jnp.square(a).sum(axis=axis))
        nb = jnp.sqrt(jnp.square(b).sum(axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return forward(f, (x1, x2), name="cosine_similarity")


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def f(a, b, y):
        cos = (a * b).sum(-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce_loss(loss, reduction)
    return forward(f, (input1, input2, label), name="cosine_embedding_loss")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(y, *pd):
        n = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / n
    ins = (label,) if prior_dist is None else (label, prior_dist)
    return forward(f, ins, name="label_smooth")


def log_loss(input, label, epsilon=1e-4, name=None):
    return forward(
        lambda p, y: -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon),
        (input, label), name="log_loss")


def square_error_cost(input, label, name=None):
    return forward(lambda a, b: jnp.square(a - b), (input, label),
                   name="square_error_cost")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def f(z, y, *nm):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if nm:
            loss = loss / nm[0]
        return _reduce_loss(loss, reduction)
    ins = (logit, label) if normalizer is None else (logit, label, normalizer)
    return forward(f, ins, name="sigmoid_focal_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    def f(p, y):
        yf = jax.nn.one_hot(y.squeeze(-1), p.shape[-1], dtype=p.dtype)
        inter = (p * yf).sum(axis=tuple(range(1, p.ndim)))
        union = p.sum(axis=tuple(range(1, p.ndim))) + yf.sum(
            axis=tuple(range(1, p.ndim)))
        return (1 - (2 * inter + epsilon) / (union + epsilon)).mean()
    return forward(f, (input, label), name="dice_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    try:
        import optax
        def f(lp, lab, il, ll):
            # optax expects [B, T, C] logits and paddings
            lp_btc = jnp.swapaxes(lp, 0, 1)
            B, T, _ = lp_btc.shape
            logitpad = (jnp.arange(T)[None, :] >= il[:, None]).astype(lp.dtype)
            L = lab.shape[1]
            labpad = (jnp.arange(L)[None, :] >= ll[:, None]).astype(lp.dtype)
            loss = optax.ctc_loss(lp_btc, logitpad, lab.astype(jnp.int32),
                                  labpad, blank_id=blank)
            return _reduce_loss(loss, reduction)
        return forward(f, (log_probs, labels, input_lengths, label_lengths),
                       name="ctc_loss")
    except ImportError:  # pragma: no cover
        raise NotImplementedError("ctc_loss requires optax")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        dp = jnp.power(jnp.sum(jnp.power(jnp.abs(a - pos) + epsilon, p), -1), 1 / p)
        dn = jnp.power(jnp.sum(jnp.power(jnp.abs(a - neg) + epsilon, p), -1), 1 / p)
        if swap:
            dsn = jnp.power(jnp.sum(jnp.power(jnp.abs(pos - neg) + epsilon, p), -1), 1 / p)
            dn = jnp.minimum(dn, dsn)
        return _reduce_loss(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return forward(f, (input, positive, negative), name="triplet_margin_loss")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    return forward(
        lambda a, b: jnp.power(
            jnp.sum(jnp.power(jnp.abs(a - b) + epsilon, p), -1,
                    keepdims=keepdim), 1.0 / p),
        (x, y), name="pairwise_distance")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def f(a, p, y):
        B = a.shape[0]
        sim = a @ p.T
        y = y.reshape(-1, 1)
        same = (y == y.T).astype(a.dtype)
        same = same / same.sum(axis=1, keepdims=True)
        ce = -(jax.nn.log_softmax(sim, axis=1) * same).sum(1).mean()
        reg = l2_reg * (jnp.square(a).sum(1).mean() + jnp.square(p).sum(1).mean()) / 2
        return ce + reg
    return forward(f, (anchor, positive, labels), name="npair_loss")


# =========================== attention =======================================

def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """Flash-attention equivalent (reference hooks libflashattn via
    `phi/kernels/gpu/flash_attn_kernel.cu`). On TPU we route to a Pallas
    flash kernel when available (paddle_tpu.ops.pallas_ops), else
    `jax.nn.dot_product_attention` (XLA fuses the softmax).

    Layout: [batch, seq, heads, head_dim] — same as the reference.
    """
    from . import pallas_ops

    def f(q, k, v, *m):
        mask = m[0] if m else None
        return pallas_ops.flash_attention(q, k, v, mask=mask, causal=is_causal)

    ins = (query, key, value) if attn_mask is None else (query, key, value,
                                                         attn_mask)
    out = forward(f, ins, name="flash_attention")
    if dropout_p > 0.0 and training:
        out = dropout(out, dropout_p)
    return out


def paged_kv_write(k_pool, v_pool, key, value, block_tables, offsets,
                   seq_lens, name=None):
    """Write a step's new K/V rows ``[B, T, H, Dh]`` into the block pools
    ``[num_blocks, block_size, H*Dh]`` (``ops/kv_pool.py``) through each
    slot's ``block_tables`` [B, M] row: row ``offsets[b] + t`` of slot b,
    rows outside ``[0, seq_lens[b])`` into the reserved garbage block 0.
    A row scatter on the pool as it is — in place when the pools are
    donated to the step. Returns the two pools. Inference-only."""
    from . import kv_pool

    return forward(kv_pool.write_span,
                   (k_pool, v_pool, key, value, block_tables, offsets,
                    seq_lens), name="paged_kv_write", nondiff=True)


def paged_kv_view(pool, block_tables, num_heads, name=None):
    """Every slot's logical ``[B, M*block_size, H, Dh]`` view of a block
    pool, gathered whole blocks in table order (the XLA read path of
    prefill; the fused kernel never forms it). Inference-only."""
    from . import kv_pool

    return forward(kv_pool.gather_view, (pool, block_tables),
                   attrs={"num_heads": int(num_heads)},
                   name="paged_kv_view", nondiff=True)


def paged_attention(query, k_pool, v_pool, block_tables, seq_lens,
                    q_offsets, kernel="xla", mesh=None, name=None):
    """Fused paged-KV attention (ISSUE 14): ``query`` [B, T, H, Dh] reads
    each slot's logical KV view straight out of the shared block pool
    [num_blocks, block_size, H*Dh] through its ``block_tables`` [B, M]
    row — no gathered [B, M*bs, H, Dh] view is ever materialized on the
    Pallas routes. ``kernel`` is a STATIC choice ("pallas" | "interpret"
    | "xla"), resolved once per engine by
    ``pallas_ops.select_paged_kernel``; a ``mesh`` with mp>1 routes the
    fused kinds per-shard through shard_map (ISSUE 16), head-sharded.
    Inference-only (nondiff): the decode/verify hot path never
    backpropagates."""
    from . import pallas_ops

    def f(q, kp, vp, bt, sl, qo):
        return pallas_ops.paged_attention(q, kp, vp, bt, sl, qo,
                                          kernel=kernel, mesh=mesh)

    return forward(f, (query, k_pool, v_pool, block_tables, seq_lens,
                       q_offsets), name="paged_attention", nondiff=True)


def flash_prefill(query, k_pool, v_pool, block_tables, seq_lens,
                  q_offsets, kernel="xla", mesh=None, name=None):
    """The prompt span's attention (ISSUE 36): ``query`` [B, T, H, Dh], a
    bucket of new rows a slot already written to the pools, reads the
    slot's rows through ``block_tables`` under :func:`paged_attention`'s
    mask, a block of query rows at a time with a running softmax: no
    [H, T, S] scores, no mask array and no gathered view on the Pallas
    routes. ``kernel`` is STATIC, resolved once per engine by
    ``pallas_ops.select_prefill_kernel``. Inference-only (nondiff)."""
    from . import pallas_ops

    def f(q, kp, vp, bt, sl, qo):
        return pallas_ops.flash_prefill(q, kp, vp, bt, sl, qo,
                                        kernel=kernel, mesh=mesh)

    return forward(f, (query, k_pool, v_pool, block_tables, seq_lens,
                       q_offsets), name="flash_prefill", nondiff=True)


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    d = dtypes.convert_dtype(dtype)
    if maxlen is None:
        maxlen = int(np.asarray(lengths.numpy()).max())
    return forward(
        lambda l: (jnp.arange(maxlen)[None, :] < l[..., None]).astype(d),
        (lengths,), name="sequence_mask", nondiff=True)


def _export(fn):
    __all__.append(fn.__name__)
    return fn


# ---------------- max pool indices + unpool (coverage batch) -----------------
# reference: phi/kernels/pool_kernel.h (max_pool2d_with_index) +
# phi/kernels/unpool_kernel.h. Indices are flat positions in each input
# plane (paddle convention), computed from window patches so the whole op
# stays one fused XLA gather/scatter.

def _max_pool_index_nd(n, x, kernel_size, stride, padding):
    """Returns (pooled, flat_indices) for NC{spatial} input."""
    ks = _norm_tuple(kernel_size, n)
    st = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _norm_tuple(padding, n)
    pads = [(pi, pi) for pi in p]

    def f(a):
        N, C = a.shape[0], a.shape[1]
        sp = a.shape[2:]
        # pad with the dtype minimum FIRST (conv_general_dilated_patches
        # zero-pads, which would beat negative inputs at the borders — same
        # reason _pool_nd uses a -inf init; finite min, not -inf, because
        # the patch extractor is a one-hot conv and -inf*0 would be NaN)
        neg = jnp.finfo(a.dtype).min if jnp.issubdtype(
            a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
        ap = jnp.pad(a, [(0, 0), (0, 0)] + list(pads), constant_values=neg)
        patches = jax.lax.conv_general_dilated_patches(
            ap, ks, st, [(0, 0)] * n)  # [N, C*prod(ks), *out_sp]
        out_sp = patches.shape[2:]
        K = int(np.prod(ks))
        patches = patches.reshape(N, C, K, *out_sp)
        idx_w = jnp.argmax(patches, axis=2)  # [N, C, *out_sp]
        pooled = jnp.max(patches, axis=2)
        # window origin per output position (original, unpadded coords)
        origins = []
        for d in range(n):
            o = jnp.arange(out_sp[d]) * st[d] - p[d]
            shape = [1] * (2 + n)
            shape[2 + d] = out_sp[d]
            origins.append(o.reshape(shape))
        # unravel idx_w into per-dim offsets
        flat = jnp.zeros_like(idx_w)
        rem = idx_w
        mul = 1
        coords = []
        for d in range(n - 1, -1, -1):
            coords.append(rem % ks[d])
            rem = rem // ks[d]
        coords = coords[::-1]
        for d in range(n):
            pos = jnp.clip(origins[d] + coords[d], 0, sp[d] - 1)
            flat = flat * sp[d] + pos
        del mul
        return pooled, flat.astype(jnp.int32)

    return f


@_export
def max_pool2d_with_index(x, kernel_size, stride=None, padding=0, name=None):
    f = _max_pool_index_nd(2, x, kernel_size, stride, padding)
    return forward(f, (x,), name="max_pool2d_with_index")


@_export
def max_pool3d_with_index(x, kernel_size, stride=None, padding=0, name=None):
    f = _max_pool_index_nd(3, x, kernel_size, stride, padding)
    return forward(f, (x,), name="max_pool3d_with_index")


def _unpool_nd(n, x, indices, kernel_size, stride, padding, output_size,
               name):
    ks = _norm_tuple(kernel_size, n)
    st = _norm_tuple(stride if stride is not None else kernel_size, n)
    p = _norm_tuple(padding, n)

    def f(a, idx, *, out_sp):
        N, C = a.shape[0], a.shape[1]
        hw = int(np.prod(out_sp))
        flatv = a.reshape(N, C, -1)
        flati = idx.reshape(N, C, -1)
        out = jnp.zeros((N, C, hw), a.dtype)
        bidx = jnp.arange(N).reshape(N, 1, 1)
        cidx = jnp.arange(C).reshape(1, C, 1)
        out = out.at[bidx, cidx, flati].set(flatv)
        return out.reshape(N, C, *out_sp)

    xa = x._data if hasattr(x, "_data") else x
    in_sp = xa.shape[2:]
    if output_size is None:
        out_sp = tuple((in_sp[d] - 1) * st[d] - 2 * p[d] + ks[d]
                       for d in range(n))
    else:
        out_sp = tuple(output_size[-n:])
    return forward(f, (x, indices), {"out_sp": out_sp}, name=name)


@_export
def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    return _unpool_nd(2, x, indices, kernel_size, stride, padding,
                      output_size, "max_unpool2d")


@_export
def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    return _unpool_nd(3, x, indices, kernel_size, stride, padding,
                      output_size, "max_unpool3d")


@_export
def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    return _unpool_nd(1, x, indices, kernel_size, stride, padding,
                      output_size, "max_unpool1d")


@_export
def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean", name=None):
    """ArcFace/CosFace margin softmax CE (reference
    phi/kernels/margin_cross_entropy_kernel.h): logits are cosines; the
    target class logit is transformed cos(m1·θ + m2) − m3 then everything
    is scaled before softmax CE."""

    def f(lg, lab, *, m1, m2, m3, s, reduction):
        lab = lab.reshape(lab.shape[0])
        theta = jnp.arccos(jnp.clip(lg, -1.0 + 1e-7, 1.0 - 1e-7))
        target = jnp.cos(m1 * theta + m2) - m3
        oh = jax.nn.one_hot(lab, lg.shape[-1], dtype=lg.dtype)
        adj = jnp.where(oh > 0, target, lg) * s
        logp = jax.nn.log_softmax(adj.astype(jnp.float32), -1)
        loss = -jnp.take_along_axis(logp, lab[:, None], -1)
        if reduction == "mean":
            loss_out = loss.mean()
        elif reduction == "sum":
            loss_out = loss.sum()
        else:
            loss_out = loss
        return loss_out, jnp.exp(logp)

    out = forward(f, (logits, label),
                  {"m1": float(margin1), "m2": float(margin2),
                   "m3": float(margin3), "s": float(scale),
                   "reduction": reduction}, name="margin_cross_entropy")
    return out if return_softmax else out[0]


@_export
def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference
    phi/kernels/cpu/hsigmoid_loss_kernel.cc): classify via a binary tree —
    the default tree is the complete binary tree over num_classes leaves
    (Huffman-style custom trees via path_table/path_code). Per sample:
    loss = Σ_d softplus((1-2·code_d)·(w_{node_d}·x + b_{node_d}))."""
    if path_table is None:
        # complete-binary-tree paths: leaf = label + num_classes - 1 in a
        # heap-ordered tree with num_classes-1 internal nodes
        depth = int(np.ceil(np.log2(max(num_classes, 2))))
        tables, codes = [], []
        for c in range(num_classes):
            node = c + num_classes - 1
            t, k = [], []
            while node > 0:
                parent = (node - 1) // 2
                t.append(parent)
                k.append(node % 2)  # 1 if left child (odd index)
                node = parent
            t = t[::-1][:depth] + [-1] * max(0, depth - len(t))
            k = k[::-1][:depth] + [0] * max(0, depth - len(k))
            tables.append(t[:depth])
            codes.append(k[:depth])
        path_table = jnp.asarray(np.asarray(tables, np.int64))
        path_code = jnp.asarray(np.asarray(codes, np.int64))
    else:
        path_table = path_table._data if hasattr(path_table, "_data") \
            else jnp.asarray(path_table)
        path_code = path_code._data if hasattr(path_code, "_data") \
            else jnp.asarray(path_code)

    def f(x, lab, w, *rest):
        lab = lab.reshape(-1)
        nodes = jnp.take(path_table, lab, axis=0)      # [B, D]
        codes = jnp.take(path_code, lab, axis=0)       # [B, D]
        valid = nodes >= 0
        ni = jnp.clip(nodes, 0, w.shape[0] - 1)
        wn = jnp.take(w, ni, axis=0)                   # [B, D, F]
        logits = jnp.einsum("bdf,bf->bd", wn, x)
        if rest:
            logits = logits + jnp.take(rest[0].reshape(-1), ni, axis=0)
        sgn = 1.0 - 2.0 * codes.astype(logits.dtype)
        per_node = jax.nn.softplus(sgn * logits)
        return jnp.sum(jnp.where(valid, per_node, 0.0), -1,
                       keepdims=True)

    ins = (input, label, weight) if bias is None else (input, label,
                                                      weight, bias)
    return forward(f, ins, name="hsigmoid_loss")


@_export
def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample negative class centers for margin-based losses (reference
    phi/kernels/gpu/class_center_sample_kernel.cu): keep all positive
    classes, pad with sampled negatives to num_samples, return the labels
    remapped into the sampled index space."""
    _note('class_center_sample')
    lab = np.asarray(jax.device_get(
        label._data if hasattr(label, "_data") else label)).reshape(-1)
    pos = np.unique(lab)
    if len(pos) >= num_samples:
        sampled = pos[:num_samples]
    else:
        rng = np.random.default_rng(abs(hash(tuple(lab.tolist()))) % 2**32)
        neg_pool = np.setdiff1d(np.arange(num_classes), pos)
        extra = rng.choice(neg_pool, num_samples - len(pos), replace=False)
        sampled = np.concatenate([pos, extra])
    remap = -np.ones(num_classes, np.int64)
    remap[sampled] = np.arange(len(sampled))
    remapped = remap[lab]
    from ..core.tensor import Tensor

    return (Tensor(jnp.asarray(remapped)), Tensor(jnp.asarray(sampled)))


@_export
def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.0, reduction="mean", name=None):
    """RNN-T transducer loss (reference phi/kernels/warprnnt — dynloaded
    warprnnt): forward-variable DP over the (T, U) lattice in log space,
    as a lax.scan over time with an in-row scan over the label axis.
    input: [B, T, U+1, V] log-probs (or logits — normalized here)."""

    def f(logits, lab, in_len, lab_len, *, blank):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        B, T, U1, V = logp.shape
        blank_lp = logp[..., blank]                       # [B, T, U+1]
        lab_c = jnp.clip(lab, 0, V - 1)
        lab_lp = jnp.take_along_axis(
            logp[:, :, :-1, :], jnp.broadcast_to(
                lab_c[:, None, :, None], (B, T, U1 - 1, 1)), -1)[..., 0]
        neg_inf = jnp.float32(-1e30)

        def row_scan(alpha_prev_t, t):
            # emit transitions within the row: alpha[t, u] from alpha[t,u-1]
            blank_t = blank_lp[:, t]                      # [B, U+1]
            lab_t = lab_lp[:, t]                          # [B, U]
            from_top = jnp.where(
                t > 0, alpha_prev_t + blank_lp[:, jnp.maximum(t - 1, 0)],
                jnp.where(jnp.arange(U1)[None, :] == 0, 0.0, neg_inf))

            def emit(carry, u):
                cur = jnp.logaddexp(
                    from_top[:, u],
                    jnp.where(u > 0, carry + lab_t[:, jnp.maximum(u - 1, 0)],
                              neg_inf))
                # t==0 row: alpha[0,0]=0; alpha[0,u]=prefix label emits
                cur = jnp.where(
                    t == 0,
                    jnp.where(u == 0, 0.0,
                              carry + lab_t[:, jnp.maximum(u - 1, 0)]),
                    cur)
                return cur, cur

            _, rows = jax.lax.scan(emit, jnp.full((B,), neg_inf),
                                   jnp.arange(U1))
            alpha_t = rows.T                              # [B, U+1]
            return alpha_t, alpha_t

        _, alphas = jax.lax.scan(row_scan,
                                 jnp.full((B, U1), neg_inf),
                                 jnp.arange(T))           # [T, B, U+1]
        alphas = alphas.transpose(1, 0, 2)                # [B, T, U+1]
        bi = jnp.arange(B)
        t_last = jnp.clip(in_len - 1, 0, T - 1)
        u_last = jnp.clip(lab_len, 0, U1 - 1)
        final = alphas[bi, t_last, u_last] + blank_lp[bi, t_last, u_last]
        loss = -final
        if reduction == "mean":
            return loss.mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return forward(f, (input, label, input_lengths, label_lengths),
                   {"blank": blank}, name="rnnt_loss")
