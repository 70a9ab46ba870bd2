"""Multi-chip execution: device mesh, ICI collectives, sharded stages.

The reference scales with one task per partition over executors connected
by gRPC/Flight (SURVEY.md §2.5).  On a TPU pod slice, partitions that live
on the same mesh become SHARDS: a stage runs as ONE ``shard_map``-ped
program over the mesh's data axis, and the cross-partition exchange that
Ballista does via disk+Flight becomes an XLA collective over ICI —
``psum`` for partial-aggregate reduction, ``all_to_all`` for hash
repartition.  Cross-host/cross-pod exchange stays on the Arrow Flight data
plane (flight/, shuffle/).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"


def make_mesh(n_devices: Optional[int] = None, axis: str = DATA_AXIS) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


# ------------------------------------------------------- distributed agg
def make_distributed_agg_step(
    kernel: Callable,
    specs,
    mesh: Mesh,
    capacity: int,
    mode: Optional[str] = None,
):
    """Wrap a fused partial-agg kernel so it runs sharded over the mesh.

    Inputs (seg, valid, *leaf arrays) are sharded on the row axis; each
    device reduces its shard to [capacity] states, then the states reduce
    across the mesh over ICI (psum / pmin / pmax per aggregate) — the
    TPU-native replacement for the reference's map-side shuffle write +
    reduce-side Flight fetch when all shards share a mesh.

    Returns a jitted fn producing fully-reduced (replicated) states.
    """
    from jax import shard_map

    from ..ops import kernels as K

    # the mode must match the one the kernel was BUILT under (pinned by
    # the owning TpuStageExec); the global is only a fallback
    mode = mode or K.precision_mode()

    def reduce_states(states):
        # per-field collective chosen by the kernel's state layout
        # (state_fields): psum for additive fields — including the x32
        # double-float lo term, whose psum error is second-order — and
        # pmin/pmax for extrema
        out = []
        i = 0
        for spec in specs:
            fields = K.state_fields(spec, mode)
            if spec.ord_pair and spec.func in ("min", "max"):
                # lexicographic 64-bit extremum over ICI: reduce hi, then
                # reduce lo among chips tied at the extremal hi (ties
                # carry the identity so they drop out)
                red = (
                    jax.lax.pmin if spec.func == "min" else jax.lax.pmax
                )
                info = jnp.iinfo(states[i].dtype)
                ident = info.max if spec.func == "min" else info.min
                g_hi = red(states[i], DATA_AXIS)
                lo_cand = jnp.where(states[i] == g_hi, states[i + 1], ident)
                g_lo = red(lo_cand, DATA_AXIS)
                out.extend(
                    [g_hi, g_lo, jax.lax.psum(states[i + 2], DATA_AXIS)]
                )
                i += 3
                continue
            for role in fields:
                if role == "min":
                    out.append(jax.lax.pmin(states[i], DATA_AXIS))
                elif role == "max":
                    out.append(jax.lax.pmax(states[i], DATA_AXIS))
                else:
                    out.append(jax.lax.psum(states[i], DATA_AXIS))
                i += 1
        out.append(jax.lax.psum(states[-1], DATA_AXIS))  # presence
        return tuple(out)

    def sharded_step(seg, valid, *arrays):
        local = kernel(seg, valid, *arrays)
        return reduce_states(local)

    # built once: a per-call jit would retrace and recompile every batch
    fn = jax.jit(
        shard_map(
            sharded_step,
            mesh=mesh,
            in_specs=P(DATA_AXIS),  # prefix spec: every arg row-sharded
            out_specs=P(),  # replicated after the cross-chip reduction
            check_vma=False,
        )
    )
    return fn


# ------------------------------------------------- on-device repartition
_EXCHANGE_CACHE: dict = {}


def ici_batch_exchange(mesh: Mesh, n_cols: int, capacity: int):
    """Multi-column hash-repartition exchange over ICI.

    Generalizes :func:`ici_all_to_all_repartition` (single f64 column) to a
    typed multi-column payload (VERDICT.md round-1 item 4): the routing —
    a row's rank within its destination's run (row order kept),
    per-destination staging slots, overflow accounting — is computed
    ONCE from (dest, valid), then every column
    scatters into its own [n_dev, capacity] staging buffer and rides its
    own ``all_to_all``.  Columns may be any device dtype (f32/f64, i32,
    bool, dictionary codes); validity masks travel as ordinary bool
    columns.

    Returns ``fn(dest i32[rows], valid bool[rows], *cols) →
    (*recv_cols [n_dev*capacity], recv_valid bool[n_dev*capacity],
    n_dropped i32)``.  ``n_dropped`` is the global count of valid rows that
    overflowed a (source, destination) bucket — callers MUST re-run with a
    larger capacity (or fall back to the Flight shuffle) when non-zero.
    """
    from jax import shard_map

    n_dev = mesh.devices.size
    # one jitted program per (devices, columns, capacity), reused across
    # plan instances: a fresh jit per exchange re-traced and re-compiled
    # every stage of every query
    cache_key = (tuple(d.id for d in mesh.devices.flat), n_cols, capacity)
    cached = _EXCHANGE_CACHE.get(cache_key)
    if cached is not None:
        return cached

    def local_exchange(dest, valid, *cols):
        # a row's slot in its destination's staging run is its rank among
        # the valid rows bound for that destination, in row order: a
        # running count per destination (the mesh is small), no sort and
        # no gather.  Invalid rows (pad rows among them) take the sentinel
        # destination, rank nowhere and land in the spill column.
        dest_m = jnp.where(valid, dest, n_dev)
        bound = dest_m[:, None] == jnp.arange(n_dev, dtype=dest_m.dtype)
        rank = jnp.cumsum(bound.astype(jnp.int32), axis=0) - 1
        idx_within = jnp.sum(jnp.where(bound, rank, 0), axis=1)
        real = dest_m < n_dev
        ok = real & (idx_within < capacity)
        overflow = real & (idx_within >= capacity)
        n_dropped = jax.lax.psum(
            jnp.sum(overflow.astype(jnp.int32)), DATA_AXIS
        )
        safe_dest = jnp.minimum(dest_m, n_dev - 1)
        slot = jnp.where(ok, idx_within, capacity)

        def route(c, fill_ok=False):
            cs = ok if fill_ok else c
            stage = jnp.zeros((n_dev, capacity + 1), cs.dtype)
            stage = stage.at[safe_dest, slot].set(cs, mode="drop")
            stage = stage[:, :capacity]
            return jax.lax.all_to_all(
                stage, DATA_AXIS, split_axis=0, concat_axis=0, tiled=False
            ).reshape(-1)

        recv_cols = tuple(route(c) for c in cols)
        recv_valid = route(None, fill_ok=True)
        return recv_cols + (recv_valid, n_dropped)

    fn = _EXCHANGE_CACHE[cache_key] = jax.jit(
        shard_map(
            local_exchange,
            mesh=mesh,
            in_specs=(P(DATA_AXIS),) * (2 + n_cols),
            out_specs=(P(DATA_AXIS),) * (n_cols + 1) + (P(),),
            check_vma=False,
        )
    )
    return fn


class ExchangeLayout:
    """The host side of the exchange's bridge, a property of the schema
    alone (no mesh, no capacity): which device columns each field becomes
    (value + validity per field; strings as shared dictionary codes; i64
    as exact lo/hi i32 pairs when the device dtype mode is x32) and the
    string fields' dictionaries.  ``encoders`` hands in dictionaries that
    already exist (field index -> DictEncoder, one for each string field)
    in place of new ones."""

    def __init__(self, schema, encoders: Optional[dict] = None):
        import pyarrow as pa

        from ..ops import kernels as K

        self.schema = schema
        self._x32 = K.precision_mode() == "x32"
        # per-field device layout: "num" (one array), "dict" (codes),
        # "i64pair" (lo/hi split — exchange-exact without device i64)
        self.layout: list[tuple] = []
        for i, f in enumerate(schema):
            t = f.type
            if pa.types.is_string(t) or pa.types.is_large_string(t):
                self.layout.append(("dict", i))
            elif self._x32 and (
                pa.types.is_int64(t)
                or pa.types.is_uint64(t)
                or pa.types.is_date64(t)
                or pa.types.is_timestamp(t)
                # f64 bitcasts through the pair path too: the exchange is
                # pure data movement, so values must survive EXACTLY even
                # though the device has no f64 (narrowing to f32 would
                # silently corrupt pass-through repartition payloads)
                or pa.types.is_float64(t)
            ):
                self.layout.append(("i64pair", i))
            else:
                self.layout.append(("num", i))
        self.encoders = self.new_encoders() if encoders is None else encoders
        if set(self.encoders) != {i for kind, i in self.layout if kind == "dict"}:
            raise ValueError("encoders do not match the schema's string fields")
        self.n_cols = sum(
            2 if kind == "i64pair" else 1 for kind, _ in self.layout
        ) + len(self.layout)  # +1 validity per field

    # ------------------------------------------------------------- host →
    def new_encoders(self) -> dict:
        """Empty dictionaries, one for each string field: the layout's
        own, or one input partition's so that partitions can be flattened
        side by side (``flatten`` on a worker, ``adopt_codes`` at the
        hand-over)."""
        from ..ops.bridge import DictEncoder

        return {i: DictEncoder() for kind, i in self.layout if kind == "dict"}

    def flatten(self, batch, encoders: dict) -> list[np.ndarray]:
        """One RecordBatch of the layout's schema as the exchange's column
        list, string codes against ``encoders``."""
        import pyarrow.compute as pc

        from ..ops.bridge import arrow_to_numpy

        if batch.num_columns != len(self.layout):
            raise ValueError(
                f"batch has {batch.num_columns} columns, "
                f"the layout {len(self.layout)}"
            )
        cols: list[np.ndarray] = []
        for kind, i in self.layout:
            arr = batch.column(i)
            if kind == "dict":
                codes = encoders[i].encode(arr)
                validity = (
                    np.asarray(pc.is_valid(arr))
                    if arr.null_count
                    else np.ones(len(arr), bool)
                )
                cols.append(codes)
            else:
                values, validity = arrow_to_numpy(
                    arr.combine_chunks() if hasattr(arr, "combine_chunks") else arr
                )
                if validity is None:
                    validity = np.ones(len(values), bool)
                if kind == "i64pair":
                    v = (
                        values.view(np.int64)  # f64: exact bitcast
                        if values.dtype == np.float64
                        else values.astype(np.int64)
                    )
                    cols.append((v & 0xFFFFFFFF).astype(np.uint32).view(np.int32))
                    cols.append((v >> 32).astype(np.int32))
                else:
                    if self._x32 and values.dtype == np.float64:
                        values = values.astype(np.float32)
                    cols.append(values)
            cols.append(validity)
        return cols

    def adopt_codes(self, cols: list, encoders: dict) -> None:
        """Rewrite, in ``cols`` (a ``flatten`` against ``encoders``), the
        string codes into the layout's own.  Called partition by partition
        in order it builds the dictionaries that one encoder builds when
        fed the partitions one after the other (``DictEncoder.merge``)."""
        ci = 0
        for kind, i in self.layout:
            if kind == "dict":
                remap = self.encoders[i].merge(encoders[i]).astype(np.int32)
                cols[ci] = remap[cols[ci]]
            ci += 3 if kind == "i64pair" else 2


class BatchExchanger(ExchangeLayout):
    """Schema-aware host bridge around :func:`ici_batch_exchange`.

    Turns RecordBatches into device columns (:class:`ExchangeLayout`),
    runs the on-mesh exchange, and reassembles per-destination
    RecordBatches.  ``share_from`` is an exchanger or a layout with the
    same string fields (a capacity retry; the layout of the input a
    caller flattened before it knew the capacity): its dictionaries, and
    so the columns already flattened against them, stay good.
    """

    def __init__(self, mesh: Mesh, schema, capacity: int, share_from=None):
        super().__init__(
            schema, None if share_from is None else share_from.encoders
        )
        self.mesh = mesh
        self.capacity = capacity
        self._fn = ici_batch_exchange(mesh, self.n_cols, capacity)

    def to_columns(self, batch) -> list[np.ndarray]:
        """Flatten one RecordBatch into the exchange's column list."""
        return self.flatten(batch, self.encoders)

    # ------------------------------------------------------------ exchange
    def exchange(self, dest: np.ndarray, valid: np.ndarray, cols):
        """Run the sharded exchange; returns (recv_cols, recv_valid,
        n_dropped) as host arrays.  The inputs go up padded to
        :func:`exchange_rows`; a pad row is ``valid = False``, which the
        program sends to the sentinel destination and delivers nowhere."""
        rows = exchange_rows(len(dest), self.mesh.devices.size)
        sharded = shard_batch(self.mesh, [dest, valid] + list(cols), rows)
        out = self._fn(*sharded)
        host = [np.asarray(o) for o in out[:-1]]
        return host[:-1], host[-1], int(np.asarray(out[-1]))

    # ------------------------------------------------------------- → host
    def to_batches(self, recv_cols, recv_valid) -> list:
        """Reassemble one RecordBatch per destination device."""
        import pyarrow as pa

        n_dev = self.mesh.devices.size
        per_dev = len(recv_valid) // n_dev
        out = []
        for d in range(n_dev):
            sl = slice(d * per_dev, (d + 1) * per_dev)
            mask = recv_valid[sl]
            arrays = []
            ci = 0
            for kind, i in self.layout:
                f = self.schema.field(i)
                if kind == "i64pair":
                    lo = recv_cols[ci][sl][mask].view(np.uint32).astype(np.int64)
                    hi = recv_cols[ci + 1][sl][mask].astype(np.int64)
                    values = (hi << 32) | lo
                    ci += 2
                else:
                    values = recv_cols[ci][sl][mask]
                    ci += 1
                validity = recv_cols[ci][sl][mask]
                ci += 1
                if kind == "dict":
                    # vectorized decode: the repartition path pushes up to
                    # mesh.exchange_max_rows rows through here
                    arrays.append(
                        self.encoders[i].decode(values, f.type, mask=~validity)
                    )
                else:
                    arrays.append(
                        pa.array(
                            _cast_back(values, f.type),
                            f.type,
                            mask=~validity,
                        )
                    )
            out.append(pa.RecordBatch.from_arrays(arrays, schema=self.schema))
        return out


def _cast_back(values: np.ndarray, t) -> np.ndarray:
    import pyarrow as pa

    if pa.types.is_date32(t):
        return values.astype("datetime64[D]")
    if pa.types.is_date64(t):
        return values.astype("int64").view("datetime64[ms]")
    if pa.types.is_timestamp(t):
        return values.astype("int64").view(f"datetime64[{t.unit}]")
    if pa.types.is_float64(t) and values.dtype == np.int64:
        return values.view(np.float64)  # inverse of the exact pair bitcast
    if pa.types.is_floating(t) and values.dtype == np.float32:
        return values.astype(np.float64)
    return values


def ici_all_to_all_repartition(mesh: Mesh, capacity: int):
    """Build a sharded hash-repartition exchange over ICI.

    Each device holds rows plus a destination-device id per row.  Rows
    route to their destination with a single ``all_to_all`` on a
    [n_dev, capacity] staging buffer (capacity-padded, mask-carrying — the
    static-shape answer to Ballista's variable-size shuffle files).

    Returns fn(values f64[rows], dest i32[rows], valid bool[rows]) →
    (recv_values f64[n_dev*capacity], recv_valid bool[n_dev*capacity],
    n_dropped i32 scalar).  Each device ends holding every row whose
    dest == its index.  ``n_dropped`` is the GLOBAL count of valid rows
    that exceeded a (source, destination) bucket's capacity and were not
    delivered — callers MUST check it and re-run with a larger capacity
    (or fall back to the Flight shuffle) when it is non-zero; silent loss
    would corrupt downstream aggregates.
    """
    from jax import shard_map

    n_dev = mesh.devices.size

    def local_exchange(values, dest, valid):
        # values/dest/valid: this device's shard [rows_local]
        rows = values.shape[0]
        # invalid rows sort to a sentinel destination past every real one,
        # so each real destination's run contains only valid rows and the
        # within-run index is dense
        dest_m = jnp.where(valid, dest, n_dev)
        order = jnp.argsort(dest_m, stable=True)
        values_s = values[order]
        dest_s = dest_m[order]
        # per-destination staging buffer [n_dev, capacity]
        counts = jax.ops.segment_sum(
            jnp.ones(rows, jnp.int32), dest_s, num_segments=n_dev + 1
        )[:n_dev]
        offsets = jnp.cumsum(counts) - counts  # start of each dest run
        safe_dest = jnp.minimum(dest_s, n_dev - 1)
        idx_within = jnp.arange(rows, dtype=jnp.int32) - offsets[safe_dest]
        ok = (
            (dest_s < n_dev) & (idx_within >= 0) & (idx_within < capacity)
        )
        # valid rows that overflowed their bucket: surfaced to the caller
        overflow = (dest_s < n_dev) & (idx_within >= capacity)
        n_dropped = jax.lax.psum(
            jnp.sum(overflow.astype(jnp.int32)), DATA_AXIS
        )
        # rows that don't belong (sentinel dest / over capacity) scatter
        # into a spill column that is sliced away — they can never clobber
        # a real slot
        slot = jnp.where(ok, idx_within, capacity)
        stage_vals = jnp.zeros((n_dev, capacity + 1), values.dtype)
        stage_valid = jnp.zeros((n_dev, capacity + 1), jnp.bool_)
        stage_vals = stage_vals.at[safe_dest, slot].set(values_s, mode="drop")
        stage_valid = stage_valid.at[safe_dest, slot].set(ok, mode="drop")
        stage_vals = stage_vals[:, :capacity]
        stage_valid = stage_valid[:, :capacity]
        # the collective: swap staging rows so device d receives every
        # other device's bucket d — Ballista's shuffle in one ICI op
        recv_vals = jax.lax.all_to_all(
            stage_vals, DATA_AXIS, split_axis=0, concat_axis=0, tiled=False
        )
        recv_valid = jax.lax.all_to_all(
            stage_valid, DATA_AXIS, split_axis=0, concat_axis=0, tiled=False
        )
        return recv_vals.reshape(-1), recv_valid.reshape(-1), n_dropped

    fn = shard_map(
        local_exchange,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def assemble_shards(
    mesh: Mesh, per_dev_chunks: list, n_cols: int
) -> list[jax.Array]:
    """Device-resident chunks → global row-sharded arrays.

    ``per_dev_chunks[d]`` is a list of chunks already placed on device d,
    each chunk a list of ``n_cols`` equal-length 1-D arrays: the gang stage
    uploads ONE chunk per input partition (its batches concatenated on
    host), so a device holds as many chunks as it was dealt partitions.
    Shards must share one length, so each device concatenates ITS chunks
    (one program a column, in partition order) and pads to the longest
    device, on device; the padded per-device arrays then stitch into one
    sharded array per column via ``make_array_from_single_device_arrays``.
    A device dealt no rows holds zeros.  Pad rows are zeros, which the
    kernels' validity column (False-padded) masks out.
    """
    devices = list(mesh.devices.flatten())
    assert len(per_dev_chunks) == len(devices)
    lens = [
        sum(int(ch[0].shape[0]) for ch in chunks) for chunks in per_dev_chunks
    ]
    L = max(max(lens), 1)
    protos = [
        next(ch[c] for chunks in per_dev_chunks for ch in chunks)
        for c in range(n_cols)
    ]
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    out = []
    for c in range(n_cols):
        per_dev = []
        for d, chunks in enumerate(per_dev_chunks):
            pieces = [ch[c] for ch in chunks]
            if not pieces:
                a = jax.device_put(
                    np.zeros(L, dtype=protos[c].dtype), devices[d]
                )
            else:
                a = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
                pad = L - int(a.shape[0])
                if pad:
                    a = jnp.pad(a, (0, pad))
                a = jax.device_put(a, devices[d])
            per_dev.append(a)
        out.append(
            jax.make_array_from_single_device_arrays(
                (L * len(devices),), sharding, per_dev
            )
        )
    return out


def exchange_rows(total: int, n_dev: int) -> int:
    """Rows an exchange's input arrays are padded to: ``total`` in its
    bucket (``kernels.bucket_rows``), a multiple of the mesh so that shards
    are equal.  The program's shapes follow this, never ``total``, so two
    inputs of like size share one compiled exchange."""
    from ..ops import kernels as K

    return -(-K.bucket_rows(total) // n_dev) * n_dev


def shard_batch(
    mesh: Mesh, arrays: Sequence[np.ndarray], rows: Optional[int] = None
) -> list[jax.Array]:
    """Place host arrays onto the mesh sharded along the row axis, padded
    (``kernels._pad``: zeros, so a padded validity column reads False) to
    ``rows``, or to the next multiple of the mesh."""
    from ..ops import kernels as K

    sharding = NamedSharding(mesh, P(DATA_AXIS))
    n_dev = mesh.devices.size
    out = []
    for a in arrays:
        n = rows if rows is not None else -(-len(a) // n_dev) * n_dev
        out.append(jax.device_put(K._pad(a, n), sharding))
    return out
