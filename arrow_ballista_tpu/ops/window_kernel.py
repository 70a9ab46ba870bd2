"""Device window-function kernel (sort + segmented scans + gathers).

TPU-first lowering of :class:`~..exec.window.WindowExec` — a capability
the reference lacks entirely (its distributed planner raises
NotImplemented for WindowAggExec, ``scheduler/src/planner.rs:81-170``):

* ONE multi-key integer ``lax.sort`` orders rows by (pad flag, PARTITION
  BY codes, per-ORDER-BY null flag + order-preserving integer encoding);
  the host pre-encodes every key into integers whose signed order equals
  the SQL order (``window_compiler._order_encode``), so the device sort
  is exact for any numeric/date/dict key in BOTH dtype modes;
* partition / peer boundaries fall out of key-change flags; ranking
  functions are arithmetic over boundary indices; running (default
  RANGE) aggregates are ONE segmented inclusive ``associative_scan``
  with reset-at-boundary (df32-compensated sums in x32, the same 2Sum
  discipline as the aggregate kernels); value functions are clamped
  gathers;
* results return to INPUT row order via an inverse-permutation GATHER
  (scatter serializes on TPU; ``sort_key_val(perm, iota)`` gives the
  inverse as a second sort), and one packed fetch moves every output
  column in a single transfer.

Spec encoding (static per kernel): tuples
  ("row_number",) | ("rank",) | ("dense_rank",) | ("ntile", k)
  | ("agg", fn, arg_slot, pair)        # fn in sum|count|avg|min|max, RANGE
  | ("aggf", fn, arg_slot, a, b, pair) # ROWS frame [i+a, i+b]; None=UNBOUNDED
  | ("val", fn, arg_slot, offset)      # fn in lag|lead|first_value|last_value
arg slots index the (value, validity) array pairs passed after the keys;
``pair`` marks slots whose value is an exact f32 (hi, lo) tuple — x32
integer sum/avg args ride the aggregate path's column_pair discipline so
values above 2^24 don't lose low bits at an f32 cast.
ROWS-framed sums are two gathers on a compensated prefix (global prefix:
both frame bounds live in one segment, so earlier segments subtract out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import kernels as K

_WINDOW_KERNEL_CACHE: dict = {}


def _seg_first(flag, idx):
    """Per sorted row: index of its segment's first row (cummax trick)."""
    return jax.lax.cummax(jnp.where(flag, idx, 0))


def _seg_last(flag, n):
    """Per sorted row: index of its segment's LAST row.  A row is last
    when the next row starts a new segment (or is the final row); in the
    flipped array those become segment firsts."""
    last_marker = jnp.concatenate(
        [flag[1:], jnp.ones((1,), jnp.bool_)]
    )
    fm = jnp.flip(last_marker)
    fidx = jnp.arange(n, dtype=jnp.int32)
    ffirst = jax.lax.cummax(jnp.where(fm, fidx, 0))
    return (n - 1) - jnp.flip(ffirst)


def _change_flag(keys: list):
    """flag[i] = row i differs from row i-1 on ANY key (row 0 starts)."""
    diff = keys[0][1:] != keys[0][:-1]
    for k in keys[1:]:
        diff = jnp.logical_or(diff, k[1:] != k[:-1])
    return jnp.concatenate([jnp.ones((1,), jnp.bool_), diff])


def _seg_scan(flag, elems: list, kinds: list):
    """Segmented inclusive scan resetting at ``flag``.

    kinds per element: "df32" (the element is an (hi, lo) pair summed
    with 2Sum compensation), "sum" (plain add), "min", "max".  Returns
    per-row scanned values in the same structure.
    """
    flat = [flag]
    layout = []
    for kind, e in zip(kinds, elems):
        if kind == "df32":
            layout.append((kind, len(flat)))
            flat.extend(e)
        else:
            layout.append((kind, len(flat)))
            flat.append(e)
    flat_kinds = ["flag"]
    for kind, _ in layout:
        flat_kinds.extend(
            ["df32_hi", "df32_lo"] if kind == "df32" else [kind]
        )

    def combine(a, b):
        fb = b[0]
        out = [jnp.logical_or(a[0], fb)]
        i = 1
        while i < len(flat_kinds):
            kind = flat_kinds[i]
            if kind == "df32_hi":
                s, e = K._two_sum(a[i], b[i])
                hi, lo2 = K._two_sum(s, a[i + 1] + b[i + 1] + e)
                out.append(jnp.where(fb, b[i], hi))
                out.append(jnp.where(fb, b[i + 1], lo2))
                i += 2
                continue
            if kind == "sum":
                merged = a[i] + b[i]
            elif kind == "min":
                merged = jnp.minimum(a[i], b[i])
            else:  # max
                merged = jnp.maximum(a[i], b[i])
            out.append(jnp.where(fb, b[i], merged))
            i += 1
        return tuple(out)

    scanned = jax.lax.associative_scan(combine, tuple(flat))
    outs = []
    for kind, slot in layout:
        if kind == "df32":
            outs.append((scanned[slot], scanned[slot + 1]))
        else:
            outs.append(scanned[slot])
    return outs


def _range_extremum(v, lo, hi, fn, ident, n, max_len):
    """Per-row extremum over [lo_i, hi_i] via a SPARSE TABLE (doubling):
    level k holds the extremum of the size-2^k window starting at each
    row, built with log-depth shifted minimum/maximum folds; the query
    is two gathers (the classic overlapping-windows RMQ decomposition).
    A monotonic deque is inherently sequential — this is the
    gather-friendly device form.  Segment safety: both query windows lie
    inside [lo, hi], which callers clip to the row's segment, so levels
    may freely span segment boundaries without contaminating results.
    ``max_len`` bounds the table depth: finite frames need only
    ceil(log2(frame_len)) levels."""
    ext = jnp.minimum if fn == "min" else jnp.maximum
    levels = [v]
    depth = max(1, int(max_len - 1).bit_length())
    cur = v
    for k in range(1, depth + 1):
        s = 1 << (k - 1)
        if s < n:
            shifted = jnp.concatenate(
                [cur[s:], jnp.full((s,), ident, cur.dtype)]
            )
        else:
            shifted = jnp.full((n,), ident, cur.dtype)
        cur = ext(cur, shifted)
        levels.append(cur)
    table = jnp.stack(levels)  # [depth+1, n]
    length = jnp.maximum(hi - lo + 1, 1)
    kq = jnp.zeros_like(length)
    for k in range(1, depth + 1):
        kq = kq + (length >= (1 << k)).astype(length.dtype)
    size = jnp.left_shift(jnp.ones_like(kq), kq)
    aidx = jnp.clip(lo, 0, n - 1)
    bidx = jnp.clip(hi - size + 1, 0, n - 1)
    flat = table.reshape(-1)
    return ext(flat[kq * n + aidx], flat[kq * n + bidx])


def make_window_kernel(
    specs: tuple,
    n_part_keys: int,
    n_order_keys: int,
    n_args: int,
    mode: str,
):
    """Jitted ``fn(part_keys, order_keys, args) -> packed``.

    ``part_keys``/``order_keys`` are tuples of integer key arrays (the
    pad flag is part_keys[0]); ``args`` is a tuple of (value, validity)
    pairs, where a pair-slot's value is itself an (hi, lo) f32 tuple.
    ``packed`` is an [n_out_rows, n] integer array in INPUT row
    order — float rows bitcast exactly like the aggregate packed fetch.
    Per-spec output layout (host side must mirror):
      ranking/ntile → 1 int row
      agg count     → 1 int row
      agg sum/avg   → x32: hi, lo, cnt  | x64: val, cnt
      agg min/max   → val, cnt
      aggf count(*)/count → 1 int row
      aggf sum/avg  → x32: P_hi@hi, P_lo@hi, P_hi@lo-1, P_lo@lo-1, cnt
                      | x64: P@hi, P@lo-1, cnt   (segment-reset prefixes)
      val fns       → val (arg dtype), ok flag
    """
    cache_key = (specs, n_part_keys, n_order_keys, n_args, mode,
                 jax.default_backend())
    fn = _WINDOW_KERNEL_CACHE.get(cache_key)
    if fn is not None:
        return fn

    fdt = jnp.float64 if mode == "x64" else jnp.float32
    idt = jnp.int64 if mode == "x64" else jnp.int32

    def kernel(part_keys, order_keys, args):
        n = part_keys[0].shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        all_keys = tuple(part_keys) + tuple(order_keys)
        packed = K.packed_multikey_sort(all_keys, iota)
        if packed is not None:
            # pairwise-u64-packed operands: ~half the bytes per bitonic
            # pass (the r05 chip capture's window sort never returned;
            # see kernels.packed_multikey_sort)
            perm, s_all = packed
            s_part = s_all[: len(part_keys)]
        else:
            sorted_ = jax.lax.sort(
                all_keys + (iota,), num_keys=len(all_keys)
            )
            perm = sorted_[-1]
            s_part = sorted_[: len(part_keys)]
            s_all = sorted_[:-1]
        # inverse permutation as a SORT (gather-friendly), not a scatter
        _, inv = jax.lax.sort_key_val(perm, iota)

        idx = jnp.arange(n, dtype=jnp.int32)
        seg_flag = _change_flag(list(s_part))
        peer_flag = _change_flag(list(s_all))
        seg_first = _seg_first(seg_flag, idx)
        peer_last = _seg_last(peer_flag, n)

        s_args = []
        for a in args:
            v, m_ = a
            if isinstance(v, tuple):  # pair slot: (hi, lo) f32 arrays
                s_args.append(((v[0][perm], v[1][perm]), m_[perm]))
            else:
                s_args.append((v[perm], m_[perm]))

        rows: list = []  # (array, is_int) in sorted order pre-inverse

        def emit(arr, is_int):
            rows.append((arr, is_int))

        # lazily-computed shared quantities
        shared: dict = {}

        def get(name):
            if name in shared:
                return shared[name]
            if name == "seg_last":
                v = _seg_last(seg_flag, n)
            elif name == "peer_first":
                v = _seg_first(peer_flag, idx)
            elif name == "peers_cum":
                v = jnp.cumsum(peer_flag.astype(jnp.int32))
            else:
                raise KeyError(name)
            shared[name] = v
            return v

        for spec in specs:
            kind = spec[0]
            if kind == "row_number":
                emit(idx - seg_first + 1, True)
                continue
            if kind == "rank":
                emit(get("peer_first") - seg_first + 1, True)
                continue
            if kind == "dense_rank":
                pc_ = get("peers_cum")
                emit(pc_ - pc_[seg_first] + 1, True)
                continue
            if kind == "ntile":
                k = spec[1]
                seg_last = get("seg_last")
                sizes = seg_last - seg_first + 1
                pos = idx - seg_first
                q, r = sizes // k, sizes % k
                big = r * (q + 1)
                in_big = pos < big
                bucket_big = pos // (q + 1) + 1
                bucket_small = r + (pos - big) // jnp.maximum(q, 1) + 1
                emit(jnp.where(in_big, bucket_big, bucket_small), True)
                continue
            if kind == "agg":
                _, fn_name, slot, is_pair = spec
                if fn_name == "count" and slot is None:
                    # count(*): rows from segment start through last peer
                    cnt = idx - seg_first + 1
                    emit(cnt[peer_last], True)
                    continue
                val, avalid = s_args[slot]
                m = avalid
                cnt_run = _seg_scan(
                    seg_flag, [m.astype(jnp.int32)], ["sum"]
                )[0]
                if fn_name == "count":
                    emit(cnt_run[peer_last], True)
                    continue
                if fn_name in ("sum", "avg"):
                    if mode == "x32":
                        if is_pair:
                            h = jnp.where(m, val[0], 0.0)
                            l = jnp.where(m, val[1], 0.0)
                        else:
                            h = jnp.where(m, val.astype(jnp.float32), 0.0)
                            l = jnp.zeros_like(h)
                        (hi, lo), = _seg_scan(
                            seg_flag, [(h, l)], ["df32"]
                        )
                        emit(hi[peer_last], False)
                        emit(lo[peer_last], False)
                    else:
                        v = jnp.where(m, val.astype(fdt), 0.0)
                        s, = _seg_scan(seg_flag, [v], ["sum"])
                        emit(s[peer_last], False)
                    emit(cnt_run[peer_last], True)
                    continue
                # min / max (numeric; identity = +/- inf in float domain,
                # int idents for exact-int operands)
                if jnp.issubdtype(val.dtype, jnp.integer):
                    info = jnp.iinfo(idt)
                    ident = info.max if fn_name == "min" else info.min
                    v = jnp.where(m, val.astype(idt), ident)
                    is_int = True
                else:
                    ident = jnp.inf if fn_name == "min" else -jnp.inf
                    v = jnp.where(m, val.astype(fdt), ident)
                    is_int = False
                s, = _seg_scan(seg_flag, [v], [fn_name])
                emit(s[peer_last], is_int)
                emit(cnt_run[peer_last], True)
                continue
            if kind == "aggf":
                _, fn_name, slot, fstart, fend, is_pair = spec
                seg_last = get("seg_last")
                lo = (
                    seg_first
                    if fstart is None
                    else jnp.maximum(seg_first, idx + fstart)
                )
                hi = (
                    seg_last
                    if fend is None
                    else jnp.minimum(seg_last, idx + fend)
                )
                empty = hi < lo
                if slot is None:  # count(*)
                    emit(jnp.where(empty, 0, hi - lo + 1), True)
                    continue
                val, avalid = s_args[slot]
                # SEGMENT-RESET prefixes: a global prefix would make the
                # P[hi]-P[lo-1] cancellation scale with the whole-batch
                # magnitude (measured 1e-3 relative on mixed-magnitude
                # partitions); resetting at seg_flag keeps it at frame
                # scale.  lo == seg_first reads 0, not a neighbor's tail.
                hi_g = jnp.clip(hi, 0, n - 1)
                lom1_g = jnp.clip(lo - 1, 0, n - 1)
                lo_open = lo > seg_first  # P[lo-1] is inside the segment
                cp, = _seg_scan(
                    seg_flag, [avalid.astype(jnp.int32)], ["sum"]
                )
                cnt = jnp.where(
                    empty,
                    0,
                    cp[hi_g] - jnp.where(lo_open, cp[lom1_g], 0),
                )
                if fn_name == "count":
                    emit(cnt, True)
                    continue
                if fn_name in ("min", "max"):
                    if jnp.issubdtype(val.dtype, jnp.integer):
                        info = jnp.iinfo(idt)
                        ident = info.max if fn_name == "min" else info.min
                        vv = jnp.where(avalid, val.astype(idt), ident)
                        out_int = True
                    else:
                        ident = jnp.inf if fn_name == "min" else -jnp.inf
                        vv = jnp.where(avalid, val.astype(fdt), ident)
                        out_int = False
                    # finite frames bound the sparse table's depth
                    max_len = (
                        fend - fstart + 1
                        if fstart is not None and fend is not None
                        else n
                    )
                    res = _range_extremum(
                        vv, lo, hi, fn_name, ident, n, max_len
                    )
                    emit(jnp.where(empty, ident, res), out_int)
                    emit(cnt, True)
                    continue
                if mode == "x32":
                    if is_pair:
                        vh = jnp.where(avalid, val[0], 0.0)
                        vl = jnp.where(avalid, val[1], 0.0)
                    else:
                        vh = jnp.where(avalid, val.astype(fdt), 0.0)
                        vl = jnp.zeros_like(vh)
                    (ph, pl), = _seg_scan(
                        seg_flag, [(vh, vl)], ["df32"]
                    )
                    emit(ph[hi_g], False)
                    emit(pl[hi_g], False)
                    emit(
                        jnp.where(lo_open, ph[lom1_g], 0.0), False
                    )
                    emit(
                        jnp.where(lo_open, pl[lom1_g], 0.0), False
                    )
                else:
                    vm = jnp.where(avalid, val.astype(fdt), 0.0)
                    p, = _seg_scan(seg_flag, [vm], ["sum"])
                    emit(p[hi_g], False)
                    emit(jnp.where(lo_open, p[lom1_g], 0.0), False)
                emit(cnt, True)
                continue
            if kind == "val":
                _, fn_name, slot, offset = spec
                val, avalid = s_args[slot]
                seg_last = get("seg_last")
                if fn_name == "first_value":
                    src = seg_first
                    ok = jnp.ones(n, jnp.bool_)
                elif fn_name == "last_value":
                    src = peer_last
                    ok = jnp.ones(n, jnp.bool_)
                elif fn_name == "lag":
                    src = idx - offset
                    ok = jnp.logical_and(src >= seg_first, src <= seg_last)
                else:  # lead
                    src = idx + offset
                    ok = jnp.logical_and(src <= seg_last, src >= seg_first)
                src = jnp.clip(src, 0, n - 1)
                emit(val[src], jnp.issubdtype(val.dtype, jnp.integer))
                emit(
                    jnp.logical_and(ok, avalid[src]).astype(jnp.int32),
                    True,
                )
                continue
            raise AssertionError(f"window spec {spec}")

        packed_rows = []
        for arr, is_int in rows:
            a = arr[inv]  # back to INPUT row order
            if is_int:
                packed_rows.append(a.astype(idt))
            else:
                packed_rows.append(
                    jax.lax.bitcast_convert_type(a.astype(fdt), idt)
                )
        return jnp.stack(packed_rows, axis=0)

    fn = jax.jit(kernel)
    _WINDOW_KERNEL_CACHE[cache_key] = fn
    return fn
