"""Fused convolution + BatchNorm-statistics Pallas kernel.

Motivation (docs/PERF.md round 3): the single-chip ResNet-50 train step is
HBM-bandwidth-bound, and the residual traffic is (a) every conv output
written once and (b) re-read once by the BatchNorm statistics reduction.
This kernel computes the conv AND the per-channel sums (Σy, Σy² in f32)
in one pass: each output tile is produced in VMEM, its statistics are
accumulated on-chip, and the activation is written exactly once — the
stats re-read never touches HBM.  This is the TPU-era analog of the
reference's fused cuDNN conv/BN plumbing
(/root/reference/src/operator/cudnn_batch_norm-inl.h,
cudnn_convolution-inl.h) — except the fusion here is conv+stats (what the
roofline says matters) rather than conv+apply.

Scope: NHWC activations, HWIO weights, groups=1, no conv bias (the
ResNet pattern — conv feeding BN never carries a bias), K×K kernels via
the shifted-matmul decomposition (y = Σ_{dy,dx} shift(x) @ w[dy,dx]),
any stride whose output tiles fit VMEM.  Everything else falls back to
XLA's conv (callers must check `supported(...)`).

The backward is a jax.custom_vjp: d(conv) uses XLA's transposed convs
(they are MXU-optimal already and not bandwidth-critical), and the
gradients that flow into the statistics outputs fold into dy
(dy_total = dy + ds1 + 2·y·ds2) before the transposed convs — exactly
the contraction BN's backward needs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl


_CONV_DN = lax.conv_dimension_numbers(
    (1, 1, 1, 1), (1, 1, 1, 1), ('NHWC', 'HWIO', 'NHWC'))


def _out_size(size, k, s, p):
    return (size + 2 * p - k) // s + 1


def supported(x_shape, w_shape, stride, pad, dtype):
    """Whether the fused kernel handles this conv (else: XLA fallback)."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    n, h, wd, cin = x_shape
    kh, kw, wcin, cout = w_shape
    if wcin != cin:
        return False  # grouped conv
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    if cin < 8:
        return False  # stem conv (Cin=3): MXU-hostile contraction dim
    if cout % 64:
        return False  # tiling wants a lane-aligned Cout
    if stride != (1, 1) and not (kh == kw == 1 and pad == (0, 0) and
                                 h % stride[0] == 0 and wd % stride[1] == 0):
        return False  # strided path: 1x1 via reshape-subsample only
    if n & (n - 1):
        return False  # image-block sizing assumes a power-of-two batch
    ho = _out_size(h, kh, stride[0], pad[0])
    wo = _out_size(wd, kw, stride[1], pad[1])
    if ho < 1 or wo < 1:
        return False
    if kh > 1 and ho < 14:
        return False  # 7x7-spatial KxK tiles crashed Mosaic when written
    if cin * cout > 1024 * 1024:
        return False  # jumbo channel products likewise (not retried)
    # VMEM budget: padded input image + weight tile + f32 accumulator.
    # (Same tile-halving rule as the kernel launcher.)
    tc = min(cout, 256)
    while cout % tc:
        tc //= 2
    nb = _images_per_block(n, ho * wo)
    esize = jnp.dtype(dtype).itemsize
    vmem = (nb * (h + 2 * pad[0]) * (wd + 2 * pad[1]) * cin * esize +
            kh * kw * cin * tc * esize +
            nb * ho * wo * tc * 4 + nb * ho * wo * tc * esize)
    return vmem < 10 * 1024 * 1024


def _images_per_block(n, m_per_image):
    """Batch enough images per grid step that the matmul M dim feeds the
    MXU (>= 512 rows), without blowing VMEM on large images."""
    nb = 1
    while nb < n and nb * m_per_image < 512:
        nb *= 2
    while n % nb:
        nb //= 2
    return max(1, nb)


def _conv_bn_kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, *,
                    kh, kw, sh, sw, ph, pw, ho, wo, out_dtype):
    """One (cout-tile, image-block) grid step.

    Computes the conv for `nb` images against one Cout tile as kh*kw
    shifted matmuls with f32 accumulation, writes the activation tile,
    and accumulates the tile's per-channel Σy / Σy² into the (shared)
    stats blocks.  Grid iterations on TPU run sequentially, so the
    read-modify-write on s1/s2 across the image-block dimension is safe.
    """
    nb, h, wd, cin = x_ref.shape
    tc = y_ref.shape[-1]
    x = x_ref[:]
    if (sh, sw) != (1, 1):
        # 1x1 strided conv: subsample first (Mosaic has no strided
        # slice; a reshape + unit-slice lowers cleanly).
        x = x.reshape(nb, ho, sh, wo, sw, cin)[:, :, 0, :, 0, :]
    elif ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    acc = jnp.zeros((nb * ho * wo, tc), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            window = x if kh == kw == 1 else lax.slice(
                x, (0, dy, dx, 0), (nb, dy + ho, dx + wo, cin))
            acc += jnp.dot(window.reshape(nb * ho * wo, cin),
                           w_ref[dy, dx],
                           preferred_element_type=jnp.float32)
    y_ref[:] = acc.reshape(nb, ho, wo, tc).astype(out_dtype)
    # Statistics epilogue: the tile is still in VMEM/registers — summing
    # here is what saves the HBM re-read.
    part1 = jnp.sum(acc, axis=0, keepdims=True)
    part2 = jnp.sum(acc * acc, axis=0, keepdims=True)
    is_first = pl.program_id(1) == 0

    @pl.when(is_first)
    def _init():
        s1_ref[:] = part1
        s2_ref[:] = part2

    @pl.when(jnp.logical_not(is_first))
    def _accum():
        s1_ref[:] = s1_ref[:] + part1
        s2_ref[:] = s2_ref[:] + part2


def _conv_bn_stats_impl(x, w, stride, pad, interpret=False):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = stride
    ph, pw = pad
    ho, wo = _out_size(h, kh, sh, ph), _out_size(wd, kw, sw, pw)
    tc = min(cout, 256)
    while cout % tc:
        tc //= 2
    nb = _images_per_block(n, ho * wo)
    grid = (cout // tc, n // nb)

    kernel = functools.partial(
        _conv_bn_kernel, kh=kh, kw=kw, sh=sh, sw=sw, ph=ph, pw=pw,
        ho=ho, wo=wo, out_dtype=x.dtype)
    y, s1, s2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, h, wd, cin), lambda c, b: (b, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, tc), lambda c, b: (0, 0, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((nb, ho, wo, tc), lambda c, b: (b, 0, 0, c)),
            pl.BlockSpec((1, tc), lambda c, b: (0, c)),
            pl.BlockSpec((1, tc), lambda c, b: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        interpret=interpret,
    )(x, w)
    return y, s1[0], s2[0]


def _xla_conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=_CONV_DN)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv2d_bn_stats(x, w, stride=(1, 1), pad=(0, 0), interpret=False):
    """Fused NHWC conv + per-channel (Σy, Σy²) in one HBM pass.

    Returns (y, s1, s2) with s1/s2 float32 of shape (Cout,).  Mean and
    (biased) variance follow as s1/m and s2/m − mean², m = N·Ho·Wo —
    the same one-pass formulation ops/nn.py's BatchNorm uses for low
    precision inputs.
    """
    return _conv_bn_stats_impl(x, w, stride, pad, interpret)


def _fwd(x, w, stride, pad, interpret):
    y, s1, s2 = _conv_bn_stats_impl(x, w, stride, pad, interpret)
    return (y, s1, s2), (x, w, y)


def _bwd(stride, pad, interpret, res, grads):
    x, w, y = res
    dy, ds1, ds2 = grads
    # Gradients into the statistics outputs fold into dy:
    #   d/dy [ s1·ds1 + s2·ds2 ] = ds1 + 2·y·ds2   (per channel)
    # (custom_vjp instantiates zero cotangents, so ds1/ds2 are always
    # concrete; BN training always feeds real stats grads anyway.)
    dy_tot = (dy.astype(jnp.float32) + ds1[None, None, None, :] +
              2.0 * y.astype(jnp.float32) * ds2[None, None, None, :])
    dy_tot = dy_tot.astype(y.dtype)
    # XLA's own conv transposes are MXU-optimal and (unlike the forward)
    # not bandwidth-critical here — let vjp derive them.
    _, conv_vjp = jax.vjp(
        lambda xx, ww: _xla_conv(xx, ww, stride, pad), x, w)
    dx, dw = conv_vjp(dy_tot)
    return dx, dw


conv2d_bn_stats.defvjp(_fwd, _bwd)


def reference_conv_bn_stats(x, w, stride=(1, 1), pad=(0, 0)):
    """Unfused oracle: XLA conv, then the stats reduction (reads y)."""
    y = _xla_conv(x, w, stride, pad)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, (0, 1, 2)), jnp.sum(yf * yf, (0, 1, 2))
