"""Mesh-row-striped embedding tables with touched-rows-only updates.

The reference serves "millions of users" recommender workloads with two
mechanisms this package re-expresses TPU-natively:

  * parameter-server big-array striping — `EncodeKey` splits any
    >= 1e6-row array across every server (SURVEY §2.4).  Here the table
    is ONE logical jax array row-sharded over the dp mesh axis via a
    GSPMD constraint (collectives.row_shard_constraint — the same
    single-program pattern as ZeRO-1 in zero.py), so each device
    persistently holds ~1/dp of the rows.
  * row_sparse gradients — `Embedding(sparse_grad=True)` makes the
    backward emit (unique_ids, rows) COO pairs and SGD update only the
    touched rows (kvstore push/pull of row slices).  Here the fused
    train step runs a CAPTURE pass that records each sparse table's
    traced ids, dedups them (`jnp.unique` with a static `size` — the
    bucket ladder below), gathers the touched rows OUTSIDE the
    differentiated region, and re-runs the forward with the lookup
    overridden to `rows[inverse]`.  The vjp of that gather IS the
    segment-sum: the cotangent arriving at `rows` is the per-unique-id
    row-gradient (duplicates pre-summed), shaped (rung, dim) — never a
    dense (vocab, dim) array.  The optimizer then touches only those
    rows (`sparse_row_update`), so per-step update bytes scale with the
    batch's unique ids, not the vocabulary — the sparse analog of
    ZeRO's 1/N state.

Unique-count bucket ladder (zero steady-state recompiles): `jnp.unique`
inside jit needs a static `size`.  Padding every batch to its exact
unique count would compile one program per distinct count; instead the
host counts uniques and rounds UP to a power-of-two rung
(`unique_ladder` / `pick_rung` — the serving bucket-ladder trick), so
any id distribution settles onto a handful of programs.  Padded slots
carry id == vocab: the row gather clips them (masked garbage), and the
update scatter drops them (`mode='drop'` — scatter indices >= vocab are
discarded), so padding is inert end to end.  The rung joins the
compiled-program cache key (exec_cache.embed_plan_key).

Lazy momentum / lazy weight decay (documented semantics): like the
reference's `sgd_update(lazy_update=True)` for row_sparse grads,
momentum decay and weight decay apply ONLY to rows touched this step —
an untouched row's momentum does not decay and its weight does not
shrink.  With momentum=0 and wd=0 the update is BITWISE identical to
the dense path on touched rows (same sgd_update_math call on the same
dtype); with momentum/wd the divergence on rows that go untouched for
k steps is the standard lazy-update semantics every production
recommender uses (fresher rows dominate), and tests pin it by
comparing touched rows exactly and untouched rows for no-change.
"""
import threading

import numpy as np

from ..base import MXNetError


# ---------------------------------------------------------------------------
# unique-count bucket ladder
# ---------------------------------------------------------------------------

MIN_RUNG = 8


def unique_ladder(capacity, min_rung=MIN_RUNG):
    """Rungs a batch's unique-id count may be padded to: powers of two
    from min_rung up to `capacity` (the id-slot count of the batch —
    always included, so the worst case costs pad waste, never a drop)."""
    from .. import exec_cache
    capacity = int(capacity)
    if capacity < 1:
        raise MXNetError('unique_ladder: capacity must be >= 1')
    if capacity <= min_rung:
        return (capacity,)
    return tuple(r for r in exec_cache.batch_ladder(capacity, min_rung))


def pick_rung(ladder, u):
    """Smallest rung covering `u` unique ids (ladder is ascending)."""
    for r in ladder:
        if r >= u:
            return r
    return ladder[-1]


# ---------------------------------------------------------------------------
# traced lookup math
# ---------------------------------------------------------------------------

def dedup_ids(ids_list, rung, vocab):
    """Dedup one sparse table's ids inside the trace.

    ids_list: the traced id arrays of every lookup of this table this
    step (any shape/dtype; clipped to [0, vocab-1] — the op's clip
    semantics).  Returns (uids, invs): uids is (rung,) int32 padded
    with `vocab` (inert under clip-gather / drop-scatter), invs is one
    flat inverse-map per lookup, each value < rung.  `rung` must cover
    the worst-case unique count — callers pass min(host-counted rung,
    total id slots), and the total-slots fallback guarantees coverage
    even when the host could not observe the ids."""
    import jax.numpy as jnp
    flats = [jnp.clip(a.astype(jnp.int32).reshape(-1), 0, vocab - 1)
             for a in ids_list]
    allids = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    uids, inv = jnp.unique(allids, size=rung, fill_value=vocab,
                           return_inverse=True)
    inv = inv.reshape(-1)
    invs, off = [], 0
    for f in flats:
        invs.append(inv[off:off + f.shape[0]])
        off += f.shape[0]
    return uids, invs


def gather_rows(table, uids):
    """Touched-rows gather: (rung, dim) from the (vocab, dim) table.
    Padded uids (== vocab) clip to the last row — garbage that the
    inverse map never references and the update scatter drops."""
    import jax.numpy as jnp
    return jnp.take(table, uids, axis=0, mode='clip')


def sparse_row_update(w, m, uids, d_rows, lr, wd, momentum=0.0,
                      rescale=1.0, clip=None, nesterov=False, mesh=None):
    """Rows-only SGD/NAG update: the dense step's math
    (optimizer.sgd_update_math — ONE definition of the
    rescale/clip/wd/momentum core) applied to the touched row slices,
    scattered back with mode='drop' so ladder padding (uids == vocab)
    is discarded.  Lazy semantics: momentum/wd touch only these rows
    (module docstring).  Returns (new_w, new_m) with new_m is m when
    momentum == 0 (pass-through aliases the donated buffer — no copy,
    no touched bytes).  Under a mesh both outputs are pinned
    row-sharded so the donated table never drifts replicated."""
    from ..optimizer import sgd_update_math
    from .collectives import row_shard_constraint
    w_rows = gather_rows(w, uids)
    m_rows = gather_rows(m, uids) if momentum != 0.0 else None
    acc_rows, nm_rows = sgd_update_math(
        w_rows, d_rows.astype(w.dtype), m_rows, lr, wd,
        momentum=momentum, rescale=rescale, clip=clip, nesterov=nesterov)
    new_w = w.at[uids].set(acc_rows, mode='drop')
    if momentum != 0.0:
        new_m = m.at[uids].set(nm_rows, mode='drop')
    else:
        new_m = m
    if mesh is not None:
        new_w = row_shard_constraint(new_w, mesh)
        new_m = row_shard_constraint(new_m, mesh)
    return new_w, new_m


# ---------------------------------------------------------------------------
# capture / override scopes (the ops/tensor.py Embedding hook)
# ---------------------------------------------------------------------------

_SCOPE = threading.local()


class _CaptureScope:
    """Pass-1 recorder: while active, every Embedding lookup whose
    weight is a watched traced array records its traced ids (and, as a
    trace-time side effect, whether the ids ARE one of the step's
    input arrays — the host uses that source index to count uniques
    per batch).  The lookup itself proceeds densely; pass 1's outputs
    are discarded, so everything downstream of the recorded ids is
    dead code XLA eliminates — the capture costs trace time only."""

    def __init__(self, watch, ins_map=None, on_source=None):
        self.watch = watch          # id(traced table) -> table pos
        self.ins_map = ins_map or {}   # id(traced input) -> input index
        self.on_source = on_source  # host callback(pos, input_index)
        self.records = {}           # pos -> [traced ids, ...]

    def on_embedding(self, attrs, data, weight):
        pos = self.watch.get(id(weight))
        if pos is not None:
            self.records.setdefault(pos, []).append(data)
            if self.on_source is not None:
                self.on_source(pos, self.ins_map.get(id(data)))
        return None                 # fall through to the dense gather


class _Override:
    __slots__ = ('rows', 'invs', 'dim')

    def __init__(self, rows, invs, dim):
        self.rows = rows
        self.invs = list(invs)      # consumed in trace order
        self.dim = dim


class _OverrideScope:
    """Pass-2 rewriter: serves each watched table's lookup as
    rows[inverse] so the differentiated region never touches the
    (vocab, dim) array — its cotangent lands on `rows` as the COO
    row-gradient.  Lookups are matched to capture order positionally
    (both passes trace the same Python, so the order is identical);
    a mismatch means the forward is nondeterministic across traces
    and raises rather than silently mis-wiring gradients."""

    def __init__(self, overrides):
        self.overrides = overrides  # id(traced table) -> _Override

    def on_embedding(self, attrs, data, weight):
        ov = self.overrides.get(id(weight))
        if ov is None:
            return None
        if not ov.invs:
            raise MXNetError(
                'sparse embedding: more lookups of a sparse_grad table '
                'in the gradient pass than the capture pass recorded — '
                'the forward must be trace-deterministic')
        import jax.numpy as jnp
        inv = ov.invs.pop(0)
        out = jnp.take(ov.rows, inv, axis=0, mode='clip')
        return out.reshape(tuple(data.shape) + (ov.dim,))


def _hook(attrs, data, weight):
    stack = getattr(_SCOPE, 'stack', None)
    if not stack:
        return None
    return stack[-1].on_embedding(attrs, data, weight)


class _scope:
    def __init__(self, scope):
        self._scope = scope

    def __enter__(self):
        if not hasattr(_SCOPE, 'stack'):
            _SCOPE.stack = []
        _SCOPE.stack.append(self._scope)
        return self._scope

    def __exit__(self, *exc):
        _SCOPE.stack.pop()
        return False


def capture_scope(watch, ins_map=None, on_source=None):
    return _scope(_CaptureScope(watch, ins_map, on_source))


def override_scope(overrides):
    return _scope(_OverrideScope(overrides))


# bind the hook into the op table (late binding, same pattern as
# block.py -> parameter.py's _lookup_param_substitution)
from ..ops import tensor as _tensor_ops    # noqa: E402
_tensor_ops._embed_hook = _hook


# ---------------------------------------------------------------------------
# host-side plan
# ---------------------------------------------------------------------------

class SparseEmbedPlan:
    """Host-side description of one fused step's sparse tables.

    entries: list of dicts with keys
      pos    — position in the step's parameter list
      name   — parameter name (diagnostics / cache keys)
      vocab  — table rows (input_dim)
      dim    — table cols (output_dim)
      dtype  — np dtype of the table
    `src[pos]` (input index of the ids array, or None) is discovered as
    a trace-time side effect of the first capture pass: when the ids
    fed to the Embedding op ARE one of the step's input arrays, the
    host can count that batch's uniques exactly and pick a tight
    ladder rung; until then (and for derived ids) the rung falls back
    to the table's id-slot capacity — correct, just pad-heavier."""

    def __init__(self, entries):
        self.entries = list(entries)
        self.src = {}      # pos -> input index (host-observed)
        # (pos, batch sig) -> id slots per step.  Slot counts are a
        # property of the BATCH SHAPE (a (256,) id batch has 256
        # slots, a (32,) one 32): keying them by the dispatch's input
        # signature keeps a fact recorded at one shape from
        # under-sizing the rung — and silently truncating uniques —
        # at a larger one.  An unknown (pos, sig) falls back to vocab:
        # pad-heavy for one trace, never wrong.
        self.slots = {}
        self._sig = None   # current dispatch's input-shape signature

    def __bool__(self):
        return bool(self.entries)

    @property
    def positions(self):
        return [e['pos'] for e in self.entries]

    def set_sig(self, sig):
        """Bind the current dispatch's input-shape signature (the
        fused step's `shapes` tuple): note_slots/capacity scope their
        facts to it."""
        self._sig = sig

    def note_source(self, pos, input_index):
        if input_index is not None and pos not in self.src:
            self.src[pos] = input_index

    def note_slots(self, pos, n):
        self.slots[(pos, self._sig)] = int(n)

    def capacity(self, entry):
        """Worst-case unique count of one step AT the bound batch
        signature: the table's id-slot count when known (recorded at
        this shape's first trace), capped at vocab."""
        n = self.slots.get((entry['pos'], self._sig))
        if n is None:
            return int(entry['vocab'])
        return min(int(entry['vocab']), int(n))

    def pick_rungs(self, host_ids, bulk=False):
        """Per-table rung for one dispatch.  host_ids maps input index
        -> host np array (one step's ids; with bulk=True a (K, ...)
        stack whose worst step row picks the rung — every scanned step
        runs the same program, so the rung must cover all K).  Tables
        whose source input is known get cover(unique count); others
        get their capacity."""
        rungs = []
        for e in self.entries:
            cap = self.capacity(e)
            k = self.src.get(e['pos'])
            if k is not None and k in host_ids:
                ids = np.asarray(host_ids[k]).astype(np.int64)
                if bulk:
                    u = max(int(np.unique(row).size)
                            for row in ids.reshape(ids.shape[0], -1))
                else:
                    u = int(np.unique(ids.reshape(-1)).size)
                u = max(1, u)
                rungs.append(min(cap, pick_rung(unique_ladder(cap), u)))
            else:
                rungs.append(cap)
        return tuple(rungs)

    def facts_key(self):
        """exec_cache key of the plan's host-discovered trace facts
        (id source inputs, per-step id-slot counts).  Publishing them
        lets a re-created net/trainer pick steady-state rungs — and so
        hit the cached steady-state program — WITHOUT a discovery
        trace that would otherwise recompile at the capacity rung."""
        return self.key() + ('facts',)

    def key(self, rungs=None):
        from .. import exec_cache
        return exec_cache.embed_plan_key(
            tuple(e['pos'] for e in self.entries),
            tuple(int(e['vocab']) for e in self.entries),
            tuple(int(e['dim']) for e in self.entries),
            rungs)

    # -- accounting --------------------------------------------------------
    def table_bytes(self):
        return sum(int(e['vocab']) * int(e['dim']) *
                   np.dtype(e['dtype']).itemsize for e in self.entries)

    def per_device_table_bytes(self, dp):
        """Persistent per-device table storage under row-striping:
        ceil(vocab/dp) rows per device per table."""
        dp = max(1, int(dp))
        return sum(-(-int(e['vocab']) // dp) * int(e['dim']) *
                   np.dtype(e['dtype']).itemsize for e in self.entries)

    def touched_bytes(self, rungs, momentum=False):
        """Optimizer-touched bytes of one step: per table, read+write
        of `rung` weight rows (and momentum rows when momentum != 0) —
        the quantity the dense path pays at vocab instead of rung."""
        total = 0
        for e, r in zip(self.entries, rungs):
            row = int(e['dim']) * np.dtype(e['dtype']).itemsize
            total += 2 * int(r) * row * (2 if momentum else 1)
        return total

    def dense_equiv_bytes(self, momentum=False):
        """What the dense update would touch: read+write of every
        vocab row (and momentum)."""
        total = 0
        for e in self.entries:
            row = int(e['dim']) * np.dtype(e['dtype']).itemsize
            total += 2 * int(e['vocab']) * row * (2 if momentum else 1)
        return total

    def delta_bytes(self, rungs, steps=1):
        """Expected incremental-CHECKPOINT payload of the plan's tables
        after `steps` commits-worth of touched rows: delta.make_delta
        encodes a sparse table as touched-rows COO (int32 id + one row
        per touched id — the same rows the optimizer wrote), so the
        per-commit checkpoint bytes scale with `rung * steps` (capped
        at vocab: rows re-touched across steps coalesce into one
        entry), not with the table.  The full-commit equivalent is
        table_bytes().  tests/test_delta.py holds the realized ratio
        (test_incremental_commits_of_a_table_move_5x_fewer_bytes)."""
        total = 0
        for e, r in zip(self.entries, rungs):
            touched = min(int(e['vocab']), int(r) * max(1, int(steps)))
            row = int(e['dim']) * np.dtype(e['dtype']).itemsize
            total += touched * (row + np.dtype(np.int32).itemsize)
        return total


def gluon_sparse_plan(params):
    """SparseEmbedPlan over a fused step's ordered Parameter list:
    entries for every 2-D parameter flagged `sparse_grad`
    (gluon.nn.Embedding(sparse_grad=True)).  Returns None when none."""
    entries = []
    for i, p in enumerate(params):
        if not getattr(p, 'sparse_grad', False):
            continue
        if len(p.shape) != 2:
            raise MXNetError(
                'sparse_grad parameter %s must be a 2-D embedding '
                'table, got shape %r' % (p.name, (p.shape,)))
        entries.append({'pos': i, 'name': p.name,
                        'vocab': int(p.shape[0]), 'dim': int(p.shape[1]),
                        'dtype': np.dtype(p.dtype)})
    return SparseEmbedPlan(entries) if entries else None


def find_symbol_tables(symbol, sparse_only=True):
    """Walk a Symbol graph for Embedding applications.  Returns one
    dict per node: weight (arg name), ids_input (the data VARIABLE's
    name, or None when the ids are a derived value), vocab, dim,
    sparse (the node's sparse_grad attr).  Serving's hot-row cache and
    Module's fused sparse plan both key off this."""
    from ..base import parse_attr_value
    out = []
    for node in symbol._topo():
        if node.op is None or getattr(node.op, 'name', '') != 'Embedding':
            continue
        sparse = bool(parse_attr_value(
            node.attrs.get('sparse_grad', False)))
        if sparse_only and not sparse:
            continue
        data_node = node.inputs[0][0]
        w_node = node.inputs[1][0]
        if w_node.op is not None:
            continue                # computed weight: not a table param
        out.append({
            'weight': w_node.name,
            'ids_input': data_node.name if data_node.op is None else None,
            'vocab': int(parse_attr_value(node.attrs['input_dim'])),
            'dim': int(parse_attr_value(node.attrs['output_dim'])),
            'sparse': sparse,
        })
    return out


def row_sharding(mesh):
    """Persistent NamedSharding for a row-striped 2-D table."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P('data', None))
