"""Operations and least bytes of a network's convolutions and dense
layers, from the layer shapes the plain reference records.

A layer is three products: the forward one, the gradient to its input
and the gradient to its weight, each of as many multiply-adds as the
forward one.  The gradient to the input is not needed, and not counted,
where the input is the data itself.  Nothing recomputed is counted: this
is the work the model needs, whatever program does it, so a share of a
peak worked out from it cannot pass 100 %.

Least bytes of a product: each operand read once and the result written
once, in the configuration's compute type.
"""
import math


def _macs(layer):
    """Multiply-adds of one product of the layer."""
    w, y = layer['w'], layer['y']
    if layer['kind'] == 'conv':
        return math.prod(y) * w[1] * w[2] * w[3]
    if layer['kind'] == 'dense':
        return y[0] * w[0] * w[1]
    raise ValueError('layer kind %r' % (layer['kind'],))


def products(layer):
    """[(flops, elements moved)] for each product the layer needs."""
    flops = 2 * _macs(layer)
    nx, nw, ny = (math.prod(layer[k]) for k in ('x', 'w', 'y'))
    out = [(flops, nx + nw + ny),       # forward: x, w -> y
           (flops, ny + nx + nw)]       # weight gradient: dy, x -> dw
    if layer['needs_dx']:
        out.append((flops, ny + nw + nx))   # input gradient: dy, w -> dx
    return out


def train_flops(layers):
    """Forward and backward floating-point operations of one batch."""
    return sum(f for layer in layers for f, _ in products(layer))


def forward_macs(layers):
    return sum(_macs(layer) for layer in layers)


def roofline_seconds(layers, peak_flops, peak_bytes_per_s, bytes_per_el):
    """The least time one chip could take over one batch's products:
    each product at the larger of its compute time and its memory
    time.  Returns (seconds, seconds of it bound by memory)."""
    total = memory_bound = 0.0
    for layer in layers:
        for flops, elements in products(layer):
            t_c = flops / peak_flops
            t_m = elements * bytes_per_el / peak_bytes_per_s
            total += max(t_c, t_m)
            if t_m > t_c:
                memory_bound += t_m
    return total, memory_bound
