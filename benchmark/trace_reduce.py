"""From a profiler trace to the numbers the per-layer readers take.

`load` reads the .xplane.pb that jax.profiler wrote (with jax alone):
a plane named /device:TPU:<n> is one chip; its line "XLA Ops" holds one
event for each operation the chip ran and "XLA Modules" one for each
execution of a compiled program.  The host plane /host:CPU holds the
benchmark's own spans, written by TraceAnnotation under the prefix
"bench.", on the same clock.  `reduce` works on plain tuples, so the
tests can check it against intervals worked out by hand.

Busy time is the union of the operations' intervals inside the span
bench.window; idle time is the rest of the window.  Every idle gap is
laid against the benchmark's host spans: the part of it during which a
span was open goes to that span, the remainder to "outside".
"""
import glob
import os
import re

DEVICE_PLANE = '/device:TPU:'
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
SPAN_PREFIX = 'bench.'
WINDOW_SPAN = 'window'
OUTSIDE = 'outside'
# operations that only wrap others (a scan's loop holds every step's
# operations inside its own event): not work of their own
WRAPPERS = ('while', 'conditional', 'call')


def load(path):
    """{'devices': {plane: {'ops': [(name, start_s, end_s)], 'modules':
    [...]}}, 'spans': [(name, start_s, end_s)]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = devices.setdefault(plane.name, {'ops': [], 'modules': []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev['ops'] = [(op_name(e.name), e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev['modules'] = [(e.name, e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9)
                                      for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return {'devices': devices, 'spans': spans}


_SERIAL = re.compile(r'[.\-_]\d+$')
_INSTRUCTION = re.compile(
    r'^%?(?P<name>[^\s=]+)\s*=\s*\(?\s*(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]')
_KIND = re.compile(r'kind=k(\w+)')


def op_name(text):
    """A name that survives a recompile.  The trace names a device
    operation by its whole HLO instruction, "%fusion.37 = bf16[256,56,
    56,256]{...} fusion(...), kind=kOutput, ...": keep the instruction's
    name without its serial number (XLA's own category: fusion,
    multiply_reduce_fusion, select-and-scatter, copy-done), a plain
    fusion's kind, and the type and shape of the result."""
    m = _INSTRUCTION.match(text)
    if not m:
        return _SERIAL.sub('', text.lstrip('%').split(' ')[0])
    base = _SERIAL.sub('', m.group('name'))
    if base == 'fusion':
        kind = _KIND.search(text)
        if kind:
            base += '.' + kind.group(1)
    dims = m.group('dims').replace(',', '_')
    return '_'.join(p for p in (base, m.group('dtype'), dims) if p)


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(merged, lo, hi):
    """The gaps of merged intervals inside (lo, hi)."""
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def overlap(a, merged):
    """Seconds of interval a covered by merged intervals."""
    return sum(min(a[1], e) - max(a[0], s) for s, e in merged
               if min(a[1], e) > max(a[0], s))


def reduce(trace):
    """The numbers of one traced window; see the module docstring."""
    windows = [(s, e) for n, s, e in trace['spans'] if n == WINDOW_SPAN]
    if not windows or not trace['devices']:
        return None
    lo, hi = windows[0]
    by_span = {}
    for n, s, e in trace['spans']:
        if n != WINDOW_SPAN:
            by_span.setdefault(n, []).append((s, e))
    by_span = {n: union(clip(v, lo, hi)) for n, v in by_span.items()}
    busy, per_op, idle_by_span, gaps_named, dispatch_gaps = [], {}, {}, [], []
    for plane in sorted(trace['devices']):
        dev = trace['devices'][plane]
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev['ops']
               if min(e, hi) > max(s, lo)
               and n.split('_')[0] not in WRAPPERS]
        merged = union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
        for gap in complement(merged, lo, hi):
            left = gap[1] - gap[0]
            best = (0.0, OUTSIDE)
            for n, spans in by_span.items():
                part = overlap(gap, spans)
                if part > 0:
                    idle_by_span[n] = idle_by_span.get(n, 0.0) + part
                    left -= part
                    best = max(best, (part, n))
            left = max(left, 0.0)
            idle_by_span[OUTSIDE] = idle_by_span.get(OUTSIDE, 0.0) + left
            best = max(best, (left, OUTSIDE))
            gaps_named.append((gap[1] - gap[0], best[1]))
        # idle between consecutive runs of the program that takes most
        # of the device's time: the gap one dispatch leaves to the next
        runs = {}
        for n, s, e in dev['modules']:
            if min(e, hi) > max(s, lo):
                runs.setdefault(_SERIAL.sub('', n.split('(')[0]),
                                []).append((s, e))
        if runs:
            main = sorted(max(runs.values(),
                              key=lambda r: sum(e - s for s, e in r)))
            for (_, e0), (s1, _) in zip(main, main[1:]):
                if s1 > e0:
                    dispatch_gaps.append(
                        (s1 - e0) - overlap((e0, s1), merged))
    n_dev = len(trace['devices'])
    window_s = hi - lo
    busy_s = sum(busy) / n_dev
    return {
        'window_s': window_s, 'busy_s': busy_s, 'devices': n_dev,
        'idle_s': window_s - busy_s,
        'idle_by_span': {n: v / n_dev for n, v in idle_by_span.items()},
        'gaps': sorted(gaps_named, reverse=True)[:50],
        'dispatch_gaps': dispatch_gaps,
        'ops': sorted(((v / n_dev, n) for n, v in per_op.items()),
                      reverse=True)}


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not files:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return files[-1]


def reduce_dir(trace_dir):
    reduced = reduce(load(find_xplane(trace_dir)))
    if reduced is None or reduced['busy_s'] <= 0:
        raise RuntimeError('the trace under %s shows no operation on a '
                           'device inside the window' % trace_dir)
    return reduced


def breakdown(reduced, entry, top=10):
    """The result line's `breakdown`: the device operations that took
    most time, and idle time by the host span it fell in, with the
    longest single gaps."""
    def label(span):
        return ('in_%s_outside_the_benchmark_spans' % entry
                if span == OUTSIDE else 'in_' + span)
    idle = [['sum_' + label(n), s] for n, s in sorted(
        reduced['idle_by_span'].items(), key=lambda kv: -kv[1]) if s > 0]
    longest = {}
    for seconds, span in reduced['gaps']:
        longest.setdefault(span, seconds)
    idle += [['longest_' + label(n), s] for n, s in sorted(
        longest.items(), key=lambda kv: -kv[1])]
    return {'device_ops': [[n, s] for s, n in reduced['ops'][:top]],
            'idle_gaps': idle[:top]}
