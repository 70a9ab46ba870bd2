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

import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import trace

DATA_AXIS = "dp"

# the four phases of an exchange call, in the order they run
# (``BatchExchanger.exchange``)
EXCHANGE_PHASES = ("pad", "upload", "program", "fetch")


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
# the exchange program keys a row by ``device * capacity + slot`` as an i32
# (the TPU has no 64-bit compare): a mesh's devices x capacity stay under it
EXCHANGE_MAX_SLOTS = 1 << 31


def exchange_row_words(dtypes) -> int:
    """The 32-bit words a row of columns of ``dtypes`` takes in the
    exchange program's packed form: one a column of 4 bytes or fewer, two
    a column of 8, and one flags word for every 32 bits among the bool
    columns and the routing's own delivered bit."""
    dtypes = [np.dtype(d) for d in dtypes]
    bits = 1 + sum(d == np.bool_ for d in dtypes)
    return sum(
        2 if d.itemsize == 8 else 1 for d in dtypes if d != np.bool_
    ) + -(-bits // 32)


def _uint(itemsize: int):
    return {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[itemsize]


def _pack_rows(cols, ok) -> tuple:
    """The columns and ``ok`` as W ``uint32[rows]`` words a row: a 4-byte
    column is one word (a bitcast), an 8-byte one two (low word first), a
    narrower one a word it is widened into, and every bool a bit of a
    flags word (``ok`` bit 0, then the bool columns in order, 32 to a
    word)."""
    words, bits = [], [ok]
    for c in cols:
        size = c.dtype.itemsize
        if c.dtype == jnp.bool_:
            bits.append(c)
        elif size == 8:
            u = jax.lax.bitcast_convert_type(c, jnp.uint64)
            words += [u.astype(jnp.uint32), (u >> 32).astype(jnp.uint32)]
        else:
            words.append(
                jax.lax.bitcast_convert_type(c, _uint(size)).astype(jnp.uint32)
            )
    for i in range(0, len(bits), 32):
        flags = bits[i].astype(jnp.uint32)
        for k, b in enumerate(bits[i + 1:i + 32], start=1):
            flags = flags | (b.astype(jnp.uint32) << k)
        words.append(flags)
    return tuple(words)


def _unpack_rows(recv, dtypes) -> tuple:
    """:func:`_pack_rows` undone over ``recv`` ``uint32[sources, W,
    capacity]``: each column ``[sources * capacity]`` in its own dtype,
    then ``ok``."""
    def word(i):
        return recv[:, i, :].reshape(-1)

    out, bools, i = [], [], 0
    for d in dtypes:
        size = d.itemsize
        if d == jnp.bool_:
            bools.append(len(out))
            out.append(None)
            continue
        if size == 8:
            u = word(i).astype(jnp.uint64) | (word(i + 1).astype(jnp.uint64) << 32)
            i += 2
        else:
            u = word(i).astype(_uint(size))
            i += 1
        out.append(jax.lax.bitcast_convert_type(u, d))

    def bit(j):
        return (word(i + j // 32) >> (j % 32)) & 1 == 1

    for j, at in enumerate(bools, start=1):
        out[at] = bit(j)
    return tuple(out) + (bit(0),)


def ici_batch_exchange(
    mesh: Mesh, n_cols: int, capacity: int, n_out: Optional[int] = None
):
    """Multi-column hash-repartition exchange over ICI.

    Generalizes :func:`ici_all_to_all_repartition` (single f64 column) to a
    typed multi-column payload: the routing — a row's slot in its (source
    shard, destination device) run, overflow accounting — is computed ONCE
    from (part, valid), and a row then moves ONCE.  The program packs the
    columns into W 32-bit words a row (:func:`_pack_rows`; W =
    :func:`exchange_row_words` of the columns' dtypes: a word a 4-byte
    column, two an 8-byte one, the bools and the delivered bit as bits of
    a flags word), sorts the ``[W, rows]`` word matrix along its rows by
    (destination device, slot) with one sort, slices each device's run
    into a ``[n_dev, W, capacity]`` staging buffer, sends that with one
    ``all_to_all`` and unpacks it into the columns again
    (:func:`_unpack_rows`), bit for bit.  A row that goes nowhere
    (invalid, pad or overflow) sorts past every run.  On the TPU a scatter
    costs a serial step an element it writes (and sorts its indices
    first), so one sort of the packed rows does the work of a scatter a
    column.  The sort takes two operands, the key broadcast to the word
    matrix and the matrix: the TPU compiler's time grows with a sort's
    operands (a key and W separate words took minutes at q3's shapes).
    Columns may be any device dtype (f32/f64, i32, bool, dictionary
    codes); validity masks travel as ordinary bool columns.

    ``part`` is a row's output partition, one of ``n_out`` (default: one a
    device); its destination device is ``part % n_dev``.  The run a source
    sends a device is that device's partitions one after the other, in
    partition order, each partition's rows in row order: a row's slot is
    its partition's offset in the run (this shard's rows of the device's
    lower partitions) plus its rank among its partition's valid rows.  So
    the host, which counted the rows a (source shard, partition), slices
    every output partition out of the received buffers (``BatchExchanger.
    partition_batches``).  With ``part`` < ``n_out`` = ``n_dev`` (a device
    id) a run is the device's rows in row order.

    Returns ``fn(part i32[rows], valid bool[rows], *cols) →
    (*recv_cols [n_dev*capacity], recv_valid bool[n_dev*capacity],
    n_dropped i32)``; a slot no row filled reads zero in every column.
    ``n_dropped`` is the global count of valid rows that overflowed a
    (source, destination) bucket — callers MUST re-run with a larger
    capacity (or fall back to the Flight shuffle) when non-zero.
    """
    from jax import shard_map

    n_dev = mesh.devices.size
    n_out = n_dev if n_out is None else n_out
    # one jitted program per (devices, columns, capacity, partitions),
    # reused across plan instances: a fresh jit per exchange re-traced and
    # re-compiled every stage of every query
    cache_key = (tuple(d.id for d in mesh.devices.flat), n_cols, capacity, n_out)
    cached = _EXCHANGE_CACHE.get(cache_key)
    if cached is not None:
        return cached
    # below[p, q]: partition q shares p's device and comes before it
    p = np.arange(n_out)
    below = (p[None, :] < p[:, None]) & (p[None, :] % n_dev == p[:, None] % n_dev)
    assert n_dev * capacity < EXCHANGE_MAX_SLOTS, (n_dev, capacity)

    def local_exchange(part, valid, *cols):
        # a row's rank among the valid rows of its partition, in row order:
        # a running count a partition (there are few), no sort and no
        # gather.  Invalid rows (pad rows among them) take the sentinel
        # partition and rank nowhere.
        part_m = jnp.where(valid, part, n_out)
        bound = part_m[None, :] == jnp.arange(n_out, dtype=part_m.dtype)[:, None]
        run = jnp.cumsum(bound.astype(jnp.int32), axis=1)
        offset = jnp.sum(jnp.where(below, run[None, :, -1], 0), axis=1)
        idx_within = jnp.sum(
            jnp.where(bound, run - 1 + offset[:, None], 0), axis=0
        )
        real = part_m < n_out
        ok = real & (idx_within < capacity)
        overflow = real & (idx_within >= capacity)
        n_dropped = jax.lax.psum(
            jnp.sum(overflow.astype(jnp.int32)), DATA_AXIS
        )
        safe_dest = jnp.where(real, part_m % n_dev, n_dev - 1).astype(jnp.int32)
        # the rows in (device, slot) order, those not delivered last: the
        # keys of delivered rows are distinct, so every word row of the
        # [W, rows] matrix sorts its delivered rows into the same order.
        # The key is an i32 (EXCHANGE_MAX_SLOTS)
        key = jnp.where(
            ok, safe_dest * capacity + idx_within.astype(jnp.int32), n_dev * capacity
        )
        words = jnp.stack(_pack_rows(cols, ok))
        moved = jax.lax.sort(
            (jnp.broadcast_to(key, words.shape), words),
            dimension=1, num_keys=1, is_stable=False,
        )[1]
        # device d's run starts after the lower devices' rows; its slots
        # past its count are zero
        count = jnp.stack(
            [jnp.sum((ok & (safe_dest == d)).astype(jnp.int32)) for d in range(n_dev)]
        )
        start = jnp.cumsum(count) - count
        rows = part.shape[0]
        room = capacity if n_dev == 1 else rows + capacity
        if room > rows:
            moved = jnp.pad(moved, ((0, 0), (0, room - rows)))
        lane = jax.lax.iota(jnp.int32, capacity)
        stage = jnp.stack([
            jnp.where(
                lane < count[d],
                jax.lax.dynamic_slice(moved, (0, start[d]), (words.shape[0], capacity)),
                jnp.uint32(0),
            )
            for d in range(n_dev)
        ])
        recv = jax.lax.all_to_all(
            stage, DATA_AXIS, split_axis=0, concat_axis=0, tiled=False
        )
        return _unpack_rows(recv, [c.dtype for c in cols]) + (n_dropped,)

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
    runs the on-mesh exchange over ``n_out`` partitions (default: one a
    device), and reassembles the received rows: ``partition_batches``
    slices each output partition's run out of the received buffers (the
    rows a (source shard, partition) are known on the host before the
    call), ``to_batches`` masks every slot with ``recv_valid``, one batch a
    device.  ``share_from`` is an exchanger or a layout with the
    same string fields (a capacity retry; the layout of the input a
    caller flattened before it knew the capacity): its dictionaries, and
    so the columns already flattened against them, stay good.
    """

    def __init__(
        self, mesh: Mesh, schema, capacity: int, share_from=None,
        n_out: Optional[int] = None,
    ):
        super().__init__(
            schema, None if share_from is None else share_from.encoders
        )
        self.mesh = mesh
        self.capacity = capacity
        self.n_out = mesh.devices.size if n_out is None else n_out
        self._fn = ici_batch_exchange(mesh, self.n_cols, capacity, self.n_out)
        import pyarrow as pa

        # the words a row takes in the program (exchange_row_words): the
        # layout's columns, read off an empty batch of the schema
        empty = pa.RecordBatch.from_pylist([], schema=schema)
        self.row_words = exchange_row_words(
            c.dtype for c in self.flatten(empty, self.new_encoders())
        )

    def to_columns(self, batch) -> list[np.ndarray]:
        """Flatten one RecordBatch into the exchange's column list."""
        return self.flatten(batch, self.encoders)

    # ------------------------------------------------------------ exchange
    def exchange(
        self, part: np.ndarray, valid: np.ndarray, cols,
        laps: Optional["PhaseClock"] = None,
    ):
        """Run the sharded exchange; returns (recv_cols, recv_valid,
        n_dropped) as host arrays.  ``part`` is each row's output
        partition.  The inputs go up padded to :func:`exchange_rows`; a pad
        row is ``valid = False``, which the program sends to the sentinel
        partition and delivers nowhere.

        Four phases, one after the other, each ended by a lap of ``laps``
        (a :class:`PhaseClock`; the time since its last read goes to the
        phase) and each a child span of the caller's: ``pad`` every array
        to the padded rows (``exchange.pad``), ``upload`` every array and
        wait until all are on the mesh (``exchange.upload``, attr
        ``bytes``), ``program`` dispatch and wait for its outputs
        (``exchange.program``, attr ``row_words``: the words a row takes
        in the program, :func:`exchange_row_words`), ``fetch`` every
        output to the host (``exchange.fetch``, attr ``bytes``).  The two
        waits keep the copy up out of the program's time and the program
        out of the fetch's; the program cannot start before its inputs are
        there anyway, and the first fetch waited for it before."""
        laps = PhaseClock() if laps is None else laps
        rows = exchange_rows(len(part), self.mesh.devices.size)
        with trace.span("exchange.pad", rows=rows):
            padded = pad_batch(self.mesh, [part, valid] + list(cols), rows)
        laps.lap("pad")
        with trace.span("exchange.upload", bytes=sum(a.nbytes for a in padded)):
            placed = jax.block_until_ready(shard_batch(self.mesh, padded, rows))
        laps.lap("upload")
        with trace.span("exchange.program", row_words=self.row_words):
            out = jax.block_until_ready(self._fn(*placed))
        laps.lap("program")
        with trace.span("exchange.fetch") as fetch_span:
            host = [np.asarray(o) for o in out]
            fetch_span.set_attr("bytes", sum(h.nbytes for h in host))
        laps.lap("fetch")
        return host[:-2], host[-2], int(host[-1])

    # ------------------------------------------------------------- → host
    def partition_batches(self, recv_cols, counts):
        """Yield ``(p, batch)``, one RecordBatch a non-empty output
        partition, devices ascending and then partitions ascending (a
        device's are those with ``p % n_dev`` its index), the rows in the
        order they went up.  ``counts[s, p]`` is the rows of partition
        ``p`` that source shard ``s`` sent (its valid rows: the host's
        bucket count before the call).  No slot is masked: source ``s``'s
        rows of ``p`` lie at ``s * capacity`` plus the rows ``s`` sent of
        the device's lower partitions, in device ``p % n_dev``'s buffer;
        with one device and one source a column is a zero-copy slice."""
        n_dev = self.mesh.devices.size
        counts = np.asarray(counts, np.int64).reshape(n_dev, self.n_out)
        base = np.empty_like(counts)
        for d in range(n_dev):
            mine = counts[:, d::n_dev]
            base[:, d::n_dev] = np.cumsum(mine, axis=1) - mine
        for d in range(n_dev):
            for p in range(d, self.n_out, n_dev):
                n = int(counts[:, p].sum())
                if not n:
                    continue
                starts = (d * n_dev + np.arange(n_dev)) * self.capacity + base[:, p]
                runs = [(int(a), int(k)) for a, k in zip(starts, counts[:, p]) if k]
                yield p, self._runs_batch(recv_cols, runs, n)

    def _runs_batch(self, recv_cols, runs: list, n: int):
        """The received slots ``runs`` ((start, length), in order; ``n``
        rows in all) of every column as one RecordBatch."""
        import pyarrow as pa

        def gather(col):
            if len(runs) == 1:
                a, k = runs[0]
                return col[a:a + k]
            return np.concatenate([col[a:a + k] for a, k in runs])

        arrays = []
        ci = 0
        for kind, i in self.layout:
            f = self.schema.field(i)
            if kind == "i64pair":
                # lo and hi written straight into the halves of one int64
                # buffer (little-endian: lo first), no shifts
                values = np.empty(n, np.int64)
                halves = values.view(np.int32)
                at = 0
                for a, k in runs:
                    halves[2 * at:2 * (at + k):2] = recv_cols[ci][a:a + k]
                    halves[2 * at + 1:2 * (at + k):2] = recv_cols[ci + 1][a:a + k]
                    at += k
                ci += 2
            else:
                values = gather(recv_cols[ci])
                ci += 1
            validity = gather(recv_cols[ci])
            ci += 1
            null = None if validity.all() else ~validity
            if kind == "dict":
                arrays.append(self.encoders[i].decode(values, f.type, mask=null))
            else:
                arrays.append(_to_arrow(values, f.type, null))
        return pa.RecordBatch.from_arrays(arrays, schema=self.schema)

    def to_batches(self, recv_cols, recv_valid) -> list:
        """Reassemble one RecordBatch per destination device, every slot
        masked with ``recv_valid`` (the rows in each device's run order)."""
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


def _to_arrow(values: np.ndarray, t, null: Optional[np.ndarray]):
    """A received column as an Arrow array of type ``t``: where the values
    are ``t``'s own fixed-width bits (date32 as int32, a 64-bit type as the
    rebuilt int64) the buffer is wrapped as it is, with a validity bitmap
    only where ``null`` marks a row; else converted by ``_cast_back``."""
    import pyarrow as pa

    if (
        pa.types.is_primitive(t)
        and not pa.types.is_boolean(t)
        and values.dtype.kind in "iuf"
        and values.dtype.itemsize * 8 == t.bit_width
    ):
        bitmap = None
        if null is not None:
            bitmap = pa.py_buffer(np.packbits(~null, bitorder="little"))
        return pa.Array.from_buffers(
            t, len(values), [bitmap, pa.py_buffer(np.ascontiguousarray(values))],
            null_count=0 if null is None else int(null.sum()),
        )
    return pa.array(_cast_back(values, t), t, mask=null)


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


def pad_batch(
    mesh: Mesh, arrays: Sequence[np.ndarray], rows: Optional[int] = None
) -> list[np.ndarray]:
    """Host arrays padded (``kernels._pad``: zeros, so a padded validity
    column reads False) to ``rows``, or to the next multiple of the mesh;
    an array already that long is itself."""
    from ..ops import kernels as K

    n_dev = mesh.devices.size
    return [
        K._pad(a, rows if rows is not None else -(-len(a) // n_dev) * n_dev)
        for a in arrays
    ]


def shard_batch(
    mesh: Mesh, arrays: Sequence[np.ndarray], rows: Optional[int] = None
) -> list[jax.Array]:
    """Place host arrays onto the mesh sharded along the row axis: every
    array padded first (:func:`pad_batch`), then every one put.  The puts
    are asynchronous: ``jax.block_until_ready`` on the result waits for
    the copies."""
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    return [jax.device_put(a, sharding) for a in pad_batch(mesh, arrays, rows)]


class PhaseClock:
    """Consecutive reads of one clock, each charging the time since the
    read before to a phase of :data:`EXCHANGE_PHASES`: the phases split
    the interval from ``start`` to the last read exactly."""

    __slots__ = ("ns", "last")

    def __init__(self, start: Optional[int] = None):
        self.ns = dict.fromkeys(EXCHANGE_PHASES, 0)
        self.last = time.perf_counter_ns() if start is None else start

    def lap(self, phase: str) -> int:
        """Charge the time since the last read to ``phase``; the read."""
        now = time.perf_counter_ns()
        self.ns[phase] += now - self.last
        self.last = now
        return now
