"""JAX lowering of physical expressions + fused segment-aggregate kernels.

This is the TPU replacement for the reference's per-stage DataFusion
operator pipeline (the hot loop at ``shuffle_writer.rs:214-256`` /
``executor.rs:97-134``): instead of streaming 8K-row batches through
interpreted operators, the eligible stage subtree (filter → project →
partial aggregate) compiles ONCE to a fused XLA kernel and each large
batch is a single device invocation.

TPU-first design rules (see /opt/skills/guides/pallas_guide.md):
* static shapes only — rows are padded to power-of-two buckets, filters are
  boolean masks (multiply, never compact);
* group-by is ``segment_sum`` over host-assigned dense group ids with a
  fixed segment capacity — no device-side hash table, no dynamic growth;
* nulls ride as separate validity masks and fold into the row mask;
* strings never reach the device — host dictionary codes stand in.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from ..errors import ExecutionError
from ..exec import expressions as pe
from .bridge import arrow_to_numpy

# A lowered node evaluates to (value, validity-or-None) in a leaf env.
JaxClosure = Callable[[dict], tuple[jnp.ndarray, Optional[jnp.ndarray]]]


class NotLowerable(Exception):
    """Subtree cannot run on device (string compute, unsupported fn)."""


@dataclass
class LeafSpec:
    """One host-supplied input array of the fused kernel.

    Kinds: "column" (value + validity), "cpu_expr" (host-evaluated value +
    validity), "column_validity" (validity ONLY — count(col) never needs
    the values, so wide i64 key columns don't cross the bridge at all),
    "column_pair" (i64 as an exact f32 (hi, lo) pair in x32 mode — hi/lo
    and validity; 48-bit exact, so big-key sums survive the i32-less
    device), "column_ord_pair" (f64 as an ORDER-preserving (hi, lo) i32
    pair — lexicographic comparisons equal f64 comparisons, so x32
    min/max over f64 columns is bit-EXACT, the q2 decorrelated-equality
    requirement).
    """

    name: str
    kind: str  # "column" | "cpu_expr" | "column_validity" | "column_pair"
    col_index: int = -1
    cpu_expr: Optional[pe.PhysicalExpr] = None


@dataclass
class CompiledExpr:
    closure: JaxClosure
    leaves: dict[str, LeafSpec] = field(default_factory=dict)


# ------------------------------------------------------------- precision
# TPU v5e has no native f64/i64 ALUs: the
# device dtype policy is a MODE, not a constant.
#   "x64" — f64/i64 kernels (CPU platform: exact, matches pyarrow oracles)
#   "x32" — f32/i32 kernels (TPU platform: native dtypes; sums recover
#           ~48-bit effective precision via the double-float compensated
#           segment sum below, so TPC-H aggregates still match oracles
#           at 1e-6)
_PRECISION: dict = {"mode": None}


def set_precision(mode: Optional[str]) -> None:
    """Force the kernel dtype mode ("x64" | "x32") or None to re-resolve."""
    if mode not in (None, "x64", "x32"):
        raise ValueError(f"precision mode {mode!r}")
    _PRECISION["mode"] = mode


def precision_mode() -> str:
    """Resolve the dtype mode, defaulting by platform (CPU→x64, else x32)."""
    if _PRECISION["mode"] is None:
        import jax

        _PRECISION["mode"] = (
            "x64" if jax.default_backend() == "cpu" else "x32"
        )
    return _PRECISION["mode"]


def value_dtype():
    return jnp.float64 if precision_mode() == "x64" else jnp.float32


def index_dtype():
    return jnp.int64 if precision_mode() == "x64" else jnp.int32


def _F():
    return value_dtype()


def _I():
    return index_dtype()


def _pa_to_jnp_dtype(t: pa.DataType):
    if pa.types.is_floating(t) or pa.types.is_decimal(t):
        return _F()
    if pa.types.is_boolean(t):
        return jnp.bool_
    return _I()


class JaxExprCompiler:
    """Lower PhysicalExpr trees to jax closures over a shared leaf env.

    Any subtree that cannot lower (LIKE, string functions, …) but whose
    OUTPUT is device-friendly becomes a ``cpu_expr`` leaf: the engine
    evaluates it with pyarrow per batch and ships the resulting
    numeric/bool array to the device alongside the raw columns.
    """

    def __init__(self, schema: pa.Schema):
        self.schema = schema
        self.leaves: dict[str, LeafSpec] = {}

    def compile(self, expr: pe.PhysicalExpr) -> CompiledExpr:
        closure = self._lower_or_leaf(expr)
        return CompiledExpr(closure, self.leaves)

    # ------------------------------------------------------------ helpers
    def _leaf_column(self, e: pe.Col) -> JaxClosure:
        t = self.schema.field(e.index).type
        # keep in sync with bridge._is_device_friendly — anything accepted
        # here must actually cross the bridge at runtime
        if not (
            pa.types.is_integer(t)
            or pa.types.is_floating(t)
            or pa.types.is_boolean(t)
            or pa.types.is_date(t)
            or pa.types.is_timestamp(t)
        ):
            raise NotLowerable(f"column {e.colname}: type {t}")
        if precision_mode() == "x32" and (
            pa.types.is_timestamp(t) or pa.types.is_date64(t)
        ):
            # ns/ms epoch values overflow i32; keep these on the CPU path
            raise NotLowerable(f"column {e.colname}: {t} needs i64 (x32 mode)")
        name = f"col_{e.index}"
        self.leaves[name] = LeafSpec(name, "column", col_index=e.index)
        vname = f"{name}__valid"

        def run(env: dict):
            return env[name], env[vname]

        return run

    def validity_only(self, e: pe.Col) -> JaxClosure:
        """Leaf that ships ONLY the validity mask of a column (count(col):
        the values are never read, so i32-unrepresentable columns still
        count on device)."""
        name = f"col_{e.index}__validonly"
        self.leaves[name] = LeafSpec(name, "column_validity", col_index=e.index)
        vname = f"{name}__valid"

        def run(env: dict):
            return None, env[vname]

        return run

    def pair_column(self, e: pe.Col) -> JaxClosure:
        """i64 column as an exact f32 (hi, lo) pair (x32 mode): the value
        half of the closure result is a (hi, lo) TUPLE consumed only by
        pair-aware aggregate kernels (KernelAggSpec.pair)."""
        name = f"col_{e.index}__pair"
        self.leaves[name] = LeafSpec(name, "column_pair", col_index=e.index)
        vname = f"{name}__valid"

        def run(env: dict):
            return (env[f"{name}__hi"], env[f"{name}__lo"]), env[vname]

        return run

    def ord_pair_column(self, e: pe.Col) -> JaxClosure:
        """f64 column as an order-preserving (hi, lo) i32 pair (x32
        mode): consumed only by ord_pair min/max kernels, where
        lexicographic integer comparison IS f64 comparison."""
        name = f"col_{e.index}__ordpair"
        self.leaves[name] = LeafSpec(
            name, "column_ord_pair", col_index=e.index
        )
        vname = f"{name}__valid"

        def run(env: dict):
            return (env[f"{name}__ohi"], env[f"{name}__olo"]), env[vname]

        return run

    def _cpu_leaf(self, e: pe.PhysicalExpr) -> JaxClosure:
        out_t = _infer_pa_type(e, self.schema)
        if not (
            pa.types.is_boolean(out_t)
            or pa.types.is_integer(out_t)
            or pa.types.is_floating(out_t)
            or pa.types.is_date(out_t)
        ):
            raise NotLowerable(f"cpu-leaf output type {out_t} for {e}")
        name = f"cpu_{len(self.leaves)}"
        self.leaves[name] = LeafSpec(name, "cpu_expr", cpu_expr=e)
        vname = f"{name}__valid"

        def run(env: dict):
            return env[name], env[vname]

        return run

    def _lower_or_leaf(self, e: pe.PhysicalExpr) -> JaxClosure:
        try:
            return self._lower(e)
        except NotLowerable:
            return self._cpu_leaf(e)

    # ------------------------------------------------------------ lowering
    def _lower(self, e: pe.PhysicalExpr) -> JaxClosure:
        if isinstance(e, pe.Col):
            return self._leaf_column(e)

        if isinstance(e, pe.Lit):
            v = e.value
            if v is None:
                raise NotLowerable("null literal")
            if isinstance(v, bool):
                const = jnp.asarray(v)
            elif isinstance(v, int):
                if precision_mode() == "x32" and not (
                    -(2**31) <= v < 2**31
                ):
                    raise NotLowerable(f"int literal {v} exceeds i32")
                const = jnp.asarray(v, _I())
            elif isinstance(v, float):
                const = jnp.asarray(v, _F())
            else:
                import datetime

                if isinstance(v, datetime.date):
                    const = jnp.asarray(
                        (v - datetime.date(1970, 1, 1)).days, _I()
                    )
                else:
                    raise NotLowerable(f"literal {v!r}")
            return lambda env: (const, None)

        if isinstance(e, pe.Binary):
            op = e.op
            if op in ("AND", "OR"):
                lf, rf = self._lower_or_leaf(e.left), self._lower_or_leaf(e.right)

                def run_bool(env, lf=lf, rf=rf, op=op):
                    lv, lval = lf(env)
                    rv, rval = rf(env)
                    # Kleene: null treated as False for filter masks, which
                    # matches WHERE semantics (null predicate drops the row)
                    lv = lv if lval is None else jnp.logical_and(lv, lval)
                    rv = rv if rval is None else jnp.logical_and(rv, rval)
                    if op == "AND":
                        return jnp.logical_and(lv, rv), None
                    return jnp.logical_or(lv, rv), None

                return run_bool
            lf, rf = self._lower(e.left), self._lower(e.right)
            fns = {
                "=": jnp.equal, "<>": jnp.not_equal, "<": jnp.less,
                "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal,
                "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply,
            }
            if op in fns:
                f = fns[op]

                def run_bin(env, lf=lf, rf=rf, f=f):
                    lv, lval = lf(env)
                    rv, rval = rf(env)
                    lv, rv = _numeric_align(lv, rv)
                    return f(lv, rv), _merge_valid(lval, rval)

                return run_bin
            if op == "/":

                def run_div(env, lf=lf, rf=rf):
                    lv, lval = lf(env)
                    rv, rval = rf(env)
                    if (
                        jnp.issubdtype(lv.dtype, jnp.integer)
                        and jnp.issubdtype(rv.dtype, jnp.integer)
                    ):
                        # SQL / Arrow integer division truncates toward zero
                        # (pc.divide on ints); lax.div matches, floor_divide
                        # and float division do not
                        import jax.lax as lax

                        rv_safe = jnp.where(rv == 0, 1, rv)
                        return lax.div(lv, rv_safe), _merge_valid(lval, rval)
                    return (
                        lv.astype(_F()) / rv.astype(_F()),
                        _merge_valid(lval, rval),
                    )

                return run_div
            if op == "%":

                def run_mod(env, lf=lf, rf=rf):
                    lv, lval = lf(env)
                    rv, rval = rf(env)
                    return jnp.mod(lv, rv), _merge_valid(lval, rval)

                return run_mod
            raise NotLowerable(f"binary op {op}")

        if isinstance(e, pe.Not):
            f = self._lower_or_leaf(e.expr)

            def run_not(env, f=f):
                v, val = f(env)
                v = v if val is None else jnp.logical_and(v, val)
                return jnp.logical_not(v), None

            return run_not

        if isinstance(e, pe.Negative):
            f = self._lower(e.expr)

            def run_neg(env, f=f):
                v, val = f(env)
                return -v, val

            return run_neg

        if isinstance(e, pe.IsNull):
            f = self._lower_or_leaf(e.expr)
            negated = e.negated

            def run_isnull(env, f=f, negated=negated):
                _, val = f(env)
                if val is None:
                    out = jnp.zeros((), jnp.bool_)
                    return (jnp.logical_not(out) if negated else out), None
                return (val if negated else jnp.logical_not(val)), None

            return run_isnull

        if isinstance(e, pe.InList):
            f = self._lower(e.expr)
            items = e.items
            if not all(isinstance(i, (int, float)) or _is_date(i) for i in items):
                raise NotLowerable("IN list with non-numeric items")
            # integer membership must compare in int64: casting an int64 id
            # to f64 loses precision above 2^53 and admits adjacent values
            all_int = all(
                isinstance(i, int) and not isinstance(i, bool) for i in items
            )
            if (
                all_int
                and precision_mode() == "x32"
                and any(not (-(2**31) <= i < 2**31) for i in items)
            ):
                raise NotLowerable("IN list item exceeds i32")
            consts = (
                jnp.asarray(list(items), _I())
                if all_int
                else jnp.asarray([_to_num(i) for i in items], _F())
            )
            negated = e.negated

            def run_in(env, f=f, consts=consts, negated=negated, all_int=all_int):
                v, val = f(env)
                if all_int and jnp.issubdtype(v.dtype, jnp.integer):
                    lhs = v.astype(_I())
                    rhs = consts
                else:
                    lhs = v.astype(_F())
                    rhs = consts.astype(_F())
                m = jnp.any(jnp.equal(lhs[:, None], rhs[None, :]), axis=1)
                if negated:
                    m = jnp.logical_not(m)
                return m, val

            return run_in

        if isinstance(e, pe.Case):
            whens = [
                (self._lower_or_leaf(w), self._lower(t)) for w, t in e.whens
            ]
            else_f = self._lower(e.else_expr) if e.else_expr is not None else None
            out_dtype = _pa_to_jnp_dtype(e.out_type)

            def run_case(env, whens=whens, else_f=else_f, out_dtype=out_dtype):
                # per-row branch selection: both the value AND the validity
                # follow the selected branch (SQL CASE); a no-ELSE CASE is
                # NULL on rows no WHEN matches
                if else_f is not None:
                    acc, ev = else_f(env)
                    acc = acc.astype(out_dtype)
                    acc_val = jnp.asarray(True) if ev is None else ev
                else:
                    acc = jnp.zeros((), out_dtype)
                    acc_val = jnp.asarray(False)
                for wf, tf in reversed(whens):
                    c, cval = wf(env)
                    c = c if cval is None else jnp.logical_and(c, cval)
                    t, tval = tf(env)
                    acc = jnp.where(c, t.astype(out_dtype), acc)
                    tv = jnp.asarray(True) if tval is None else tval
                    acc_val = jnp.where(c, tv, acc_val)
                return acc, acc_val

            return run_case

        if isinstance(e, pe.Cast):
            f = self._lower(e.expr)
            dt = _pa_to_jnp_dtype(e.to_type)

            def run_cast(env, f=f, dt=dt):
                v, val = f(env)
                return v.astype(dt), val

            return run_cast

        if isinstance(e, pe.ScalarFn):
            mapping = {
                "abs": jnp.abs, "sqrt": jnp.sqrt, "exp": jnp.exp, "ln": jnp.log,
                "log10": lambda x: jnp.log10(x), "log2": jnp.log2,
                "ceil": jnp.ceil, "floor": jnp.floor, "sin": jnp.sin,
                "cos": jnp.cos, "tan": jnp.tan, "signum": jnp.sign,
            }
            if e.fname in mapping and len(e.args) == 1:
                f = self._lower(e.args[0])
                fn = mapping[e.fname]

                def run_fn(env, f=f, fn=fn):
                    v, val = f(env)
                    return fn(v.astype(_F())), val

                return run_fn
            if e.fname == "power" and len(e.args) == 2:
                a = self._lower(e.args[0])
                b = self._lower(e.args[1])

                def run_pow(env, a=a, b=b):
                    av, aval = a(env)
                    bv, bval = b(env)
                    return jnp.power(av.astype(_F()), bv.astype(_F())), _merge_valid(aval, bval)

                return run_pow
            if e.fname == "round":
                f = self._lower(e.args[0])

                def run_round(env, f=f):
                    v, val = f(env)
                    return jnp.round(v.astype(_F())), val

                return run_round
            raise NotLowerable(f"scalar fn {e.fname}")

        raise NotLowerable(f"node {type(e).__name__}")


def _merge_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return jnp.logical_and(a, b)


def _numeric_align(lv, rv):
    if lv.dtype == jnp.bool_ or rv.dtype == jnp.bool_:
        return lv, rv
    if jnp.issubdtype(lv.dtype, jnp.floating) or jnp.issubdtype(
        rv.dtype, jnp.floating
    ):
        return lv.astype(_F()), rv.astype(_F())
    return lv.astype(_I()), rv.astype(_I())


def _is_date(v) -> bool:
    import datetime

    return isinstance(v, datetime.date)


def _to_num(v):
    import datetime

    if isinstance(v, datetime.date):
        return float((v - datetime.date(1970, 1, 1)).days)
    return float(v)


def _infer_pa_type(e: pe.PhysicalExpr, schema: pa.Schema) -> pa.DataType:
    empty = pa.RecordBatch.from_arrays(
        [pa.nulls(0, f.type) for f in schema], schema=schema
    )
    v = e.evaluate(empty)
    return v.type


# ---------------------------------------------------------------- env build
def build_env(
    batch: pa.RecordBatch, leaves: dict[str, LeafSpec], n_padded: int,
    trivial_valid: Optional[set] = None,
) -> dict[str, np.ndarray]:
    """Evaluate/extract all leaf arrays for one batch, padded to n_padded.

    Every leaf ALWAYS ships a validity companion (all-true when the batch
    has no nulls) so the fused kernel's positional signature is identical
    across batches — nulls appearing mid-stream must not trigger an XLA
    recompile.  Names of companions that are trivially the row tail mask
    (all-true over live rows, False over padding) are added to
    ``trivial_valid`` when given: the executor substitutes ONE shared
    device-built iota mask for them instead of shipping n_padded host
    bytes per leaf across the bridge.
    """
    import pyarrow.compute as pc

    env: dict[str, np.ndarray] = {}
    for name, spec in leaves.items():
        if spec.kind == "join_col":
            continue  # gathered on device by the join wrapper
        if spec.kind == "cpu_expr":
            arr = spec.cpu_expr.evaluate(batch)
            if isinstance(arr, pa.Scalar):
                arr = pa.array([arr.as_py()] * batch.num_rows, arr.type)
        else:
            arr = batch.column(spec.col_index)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if spec.kind == "column_validity":
            # count(col): ONLY the validity mask crosses — the values are
            # never read, so any column type (strings, decimals, wide
            # i64) counts on device
            if arr.null_count:
                validity = np.asarray(pc.is_valid(arr))
            else:
                validity = np.ones(len(arr), dtype=bool)
                if trivial_valid is not None:
                    trivial_valid.add(f"{name}__valid")
            env[f"{name}__valid"] = _pad(validity, n_padded)
            continue
        values, validity = arrow_to_numpy(arr)
        if validity is None:
            validity = np.ones(len(values), dtype=bool)
            if trivial_valid is not None:
                trivial_valid.add(f"{name}__valid")
        env[f"{name}__valid"] = _pad(validity, n_padded)
        if spec.kind == "column_pair":
            v = values.astype(np.float64)
            if (
                values.dtype.kind in "iu"
                and len(v)
                and np.abs(v).max() >= float(1 << 48)
            ):
                # integer pairs must be EXACT: beyond 48 bits the split
                # loses low bits.  Float pairs are exact at any magnitude
                # (hi carries the exponent) up to f32 range.
                raise ExecutionError(
                    "int64 column exceeds 48-bit pair range in x32 mode"
                )
            if (
                values.dtype.kind == "f"
                and len(v)
                and np.abs(v).max() >= 3e38
            ):
                raise ExecutionError("f64 column exceeds f32 range")
            hi = v.astype(np.float32)
            env[f"{name}__hi"] = _pad(hi, n_padded)
            env[f"{name}__lo"] = _pad(
                (v - hi.astype(np.float64)).astype(np.float32), n_padded
            )
            continue
        if spec.kind == "column_ord_pair":
            from .bridge import split_u64_i32, to_u64_order

            # always encode the f64 VALUE (ints cast exactly below 2^53):
            # consumers decode through order_decode_f64
            ohi, olo = split_u64_i32(to_u64_order(values.astype(np.float64)))
            env[f"{name}__ohi"] = _pad(ohi, n_padded)
            env[f"{name}__olo"] = _pad(olo, n_padded)
            continue
        env[name] = _pad(coerce_host_values(values), n_padded)
    return env


def coerce_host_values(values: np.ndarray) -> np.ndarray:
    """Narrow host arrays to the device dtype mode before transfer.

    x32 mode ships f32/i32 (native TPU dtypes, half the host→HBM bytes).
    64-bit integers that cannot narrow losslessly raise ExecutionError,
    which the stage executor turns into a CPU fallback for the partition.
    """
    if precision_mode() != "x32":
        return values
    if values.dtype == np.float64:
        return values.astype(np.float32)
    if values.dtype in (np.dtype(np.int64), np.dtype(np.uint64)):
        if len(values) and (
            values.max(initial=0) > np.iinfo(np.int32).max
            or values.min(initial=0) < np.iinfo(np.int32).min
        ):
            raise ExecutionError("int64 column exceeds i32 range in x32 mode")
        return values.astype(np.int32)
    return values


def flat_arg_names(leaves: dict[str, LeafSpec]) -> list[str]:
    """Positional arg order of the fused kernel, per leaf kind."""
    out = []
    for n, spec in leaves.items():
        if spec.kind == "column_validity":
            out.append(f"{n}__valid")
        elif spec.kind == "column_pair":
            out.extend([f"{n}__hi", f"{n}__lo", f"{n}__valid"])
        elif spec.kind == "column_ord_pair":
            out.extend([f"{n}__ohi", f"{n}__olo", f"{n}__valid"])
        else:
            out.extend([n, f"{n}__valid"])
    return out


def make_join_kernel(
    inner_fn, flat_names: list[str], join_slots: dict[str, int],
    n_build: int, dense: bool = False, build_rows: Optional[int] = None,
):
    """Wrap a fused aggregate kernel with an on-device PK-FK probe join.

    ``join_slots`` maps flat arg NAMES that come from the build side to
    their index in the build-column arrays.  The wrapped signature is::

        fn(seg, valid, *probe_args, pkey, pkey_valid,
           bkeys, *bvals, *bvalids)               # sorted-probe form
        fn(seg, valid, *probe_args, pkey, pkey_valid,
           table, kmin, *bvals, *bvalids)         # dense form

    where ``probe_args`` are the per-batch arrays for NON-join flat names
    (in order) and ``pkey`` is this batch's probe join key.  Sorted form:
    build arrays are [m]-sized, SORTED by key (unique keys), probed by
    searchsorted + gather.  Dense form (key span fits the slot cap):
    ``table`` is a [span] array holding row_index+1 at slot key-kmin
    (0 = no such key), probed with ONE gather — searchsorted's log2(m)
    sequential gather passes dominated device time on the chip
    (round 5, star join).  Either way non-matching probe rows
    fold into the global row mask (inner join), so shapes stay static
    and the joined relation is never materialized.

    ``build_rows`` (the build side's padded row count, which ``inner_fn``
    was built at as its capacity): the stage's groups ARE the build rows,
    so the segment id of a row is the build row it matched, worked out
    here; the program takes no ``seg`` (``join_aggregate(valid, ...)``) and
    returns the states, then the count of valid probe rows that matched.
    A row that matched nothing gets the id ``build_rows``, out of range:
    the reduction drops it.
    """
    n_probe = sum(1 for n in flat_names if n not in join_slots)

    def probe(args):
        """(matched build row, match mask, the inner kernel's args)."""
        probe_args = args[:n_probe]
        if dense:
            pkey, pkey_valid, tbl, kmin = args[n_probe:n_probe + 4]
            bvals = args[n_probe + 4:n_probe + 4 + n_build]
            bvalids = args[n_probe + 4 + n_build:]
            span = tbl.shape[0]
            # i64 probe arithmetic: i32 pkey - i32 kmin can overflow
            rel = pkey.astype(jnp.int64) - kmin.astype(jnp.int64)
            inb = jnp.logical_and(rel >= 0, rel < span)
            slot = tbl[jnp.clip(rel, 0, span - 1).astype(jnp.int32)]
            match = jnp.logical_and(
                jnp.logical_and(inb, slot > 0), pkey_valid
            )
            idx = jnp.maximum(slot - 1, 0).astype(jnp.int32)
        else:
            pkey, pkey_valid, bkeys = args[n_probe:n_probe + 3]
            bvals = args[n_probe + 3:n_probe + 3 + n_build]
            bvalids = args[n_probe + 3 + n_build:]
            m = bkeys.shape[0]
            idx = jnp.clip(
                jnp.searchsorted(bkeys, pkey), 0, max(m - 1, 0)
            ).astype(jnp.int32)
            match = jnp.logical_and(bkeys[idx] == pkey, pkey_valid)
        full = []
        it = iter(probe_args)
        for name in flat_names:
            j = join_slots.get(name)
            if j is None:
                full.append(next(it))
            elif name.endswith("__valid"):
                full.append(jnp.logical_and(bvalids[j][idx], match))
            else:
                full.append(bvals[j][idx])
        return idx, match, full

    if build_rows is not None:

        def join_aggregate(valid, *args):
            idx, match, full = probe(args)
            hit = jnp.logical_and(valid, match)
            seg_ids = jnp.where(hit, idx, jnp.int32(build_rows))
            states = inner_fn(seg_ids, hit, *full)
            return tuple(states) + (jnp.sum(hit.astype(jnp.int32)),)

        return join_aggregate

    def fn(seg_ids, valid, *args):
        _, match, full = probe(args)
        return inner_fn(seg_ids, jnp.logical_and(valid, match), *full)

    return fn


def _pad(x: np.ndarray, n: int, fill=0) -> np.ndarray:
    """``x`` padded to ``n`` rows with ``fill``: the one way rows are padded
    for the device (batches, join build sides, exchange inputs).  What the
    pad rows hold never reaches an answer: each caller ships a validity
    column padded False, or a fill no real row can match."""
    if len(x) == n:
        return x
    # zeros come as untouched pages; only a fill has to be written
    out = np.full(n, fill, dtype=x.dtype) if fill else np.zeros(n, dtype=x.dtype)
    out[: len(x)] = x
    return out


def bucket_rows(n: int, floor: int = 1024) -> int:
    """The one rule for a row count the data decides (rows of a batch, of
    a join's build side, of an exchange, group slots fetched): the next
    power of two, not below ``floor``.  A jitted program's shapes then take
    ~log2(max rows) values and two data sets of like size share every
    program; padding costs under 2x the rows."""
    return max(floor, 1 << (max(int(n), 1) - 1).bit_length())


# ------------------------------------------------------------- fused kernel
@dataclass(frozen=True)
class KernelAggSpec:
    func: str  # sum | count | avg | min | max | count_star
    has_arg: bool
    # x32 only: the arg closure yields an exact f32 (hi, lo) pair for an
    # i64 column; the kernel sums both halves and recombines error-free
    pair: bool = False
    # min/max over integer/date args stay in INTEGER dtype end-to-end —
    # casting to f32 rounds above 2^24, and a min/max that comes back
    # sub-ulp wrong breaks decorrelated equality predicates (q2)
    int_minmax: bool = False
    # x32 only: min/max over an f64 COLUMN rides an order-preserving
    # (hi, lo) i32 pair — lexicographic integer min/max IS f64 min/max,
    # so the extremum is bit-exact without f64 device dtypes
    ord_pair: bool = False


def state_fields(spec: KernelAggSpec, mode: str) -> tuple[str, ...]:
    """Per-aggregate kernel-state layout: field roles in output order.

    Roles drive merging: "add" → +, "min"/"max" → elementwise extremum.
    In x32 mode sums carry a double-float (hi, lo) pair so f32 device math
    retains ~48 effective mantissa bits; host materialization adds the pair
    in f64.
    """
    if spec.func in ("count", "count_star"):
        return ("add",)
    if spec.func in ("sum", "avg"):
        return ("add", "add", "add") if mode == "x32" else ("add", "add")
    if spec.func == "min":
        if spec.ord_pair:
            return ("omin_hi", "omin_lo", "add")
        return ("min", "add")
    if spec.func == "max":
        if spec.ord_pair:
            return ("omax_hi", "omax_lo", "add")
        return ("max", "add")
    raise ExecutionError(f"kernel agg {spec.func}")


def _two_sum(a, b):
    """Knuth 2Sum: s = fl(a+b) plus the EXACT rounding error e (no FMA)."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _two_product_f32(a, b):
    """Dekker two-product: p = fl(a*b) plus the EXACT rounding error e
    (Veltkamp split; no FMA assumed — XLA contracting into FMA only
    makes the error term more accurate)."""
    p = a * b
    c = jnp.asarray(4097.0, jnp.float32)  # 2^12 + 1 splits f32 mantissas
    ac = a * c
    a_hi = ac - (ac - a)
    a_lo = a - a_hi
    bc = b * c
    b_hi = bc - (bc - b)
    b_lo = b - b_hi
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


def square_pair_closure(pair_closure: JaxClosure) -> JaxClosure:
    """x² as a double-float pair from a double-float x (variance family,
    x32): x = hi+lo exactly, so x² = hi² + 2·hi·lo + lo² — hi² splits
    error-free via Dekker, the cross/low terms fold into the error word
    (their own rounding sits at ~2^-48 of x²)."""

    def run(env: dict):
        (hi, lo), valid = pair_closure(env)
        p, e = _two_product_f32(hi, hi)
        e = e + jnp.asarray(2.0, jnp.float32) * hi * lo + lo * lo
        return (p, e), valid

    return run


def square_closure(closure: JaxClosure) -> JaxClosure:
    """x² in the value dtype (variance family, x64 mode)."""

    def run(env: dict):
        v, valid = closure(env)
        v = v.astype(_F())
        return v * v, valid

    return run


def _lex_merge(a_hi, a_lo, b_hi, b_lo, is_min: bool):
    """Lexicographic (hi, lo) extremum merge — the order-pair encoding of
    f64 makes this identical to an f64 min/max."""
    if is_min:
        better_b = jnp.logical_or(
            b_hi < a_hi, jnp.logical_and(b_hi == a_hi, b_lo < a_lo)
        )
    else:
        better_b = jnp.logical_or(
            b_hi > a_hi, jnp.logical_and(b_hi == a_hi, b_lo > a_lo)
        )
    return jnp.where(better_b, b_hi, a_hi), jnp.where(better_b, b_lo, a_lo)


# ------------------------------------------------------- algorithm choice
# The segment reduction has two device strategies:
#   "matmul"  — blocked one-hot einsum on the MXU.  TPU scatter serializes
#               (measured: the round-2 q1 kernel spent ~2.4s in blocked
#               scatter-adds); a [block, cap] one-hot matmul with
#               precision=HIGHEST runs the same reduction as dense MXU
#               work.  FLOPs scale with capacity, so it applies while
#               capacity <= _MATMUL_MAX_CAP.
#   "scatter" — jax.ops.segment_sum.  Exact choice on CPU (XLA:CPU lowers
#               scatter to a tight loop) and the fallback for very high
#               cardinality on TPU.
# Tests force a strategy via set_agg_algorithm to exercise the matmul path
# on the CPU-mesh CI host.
_AGG_ALGO: dict = {"force": None}
# matmul FLOP bounds (chip, round 5): the MXU one-hot einsum beats the
# other reducers while capacity <= 8192 and rows x capacity <= 2^36.
_MATMUL_MAX_CAP = 8192
_MATMUL_MAX_ELEMS = 1 << 36
# Per-block MXU accumulation error grows ~sqrt(block)*eps relative to the
# block sum; 16K-row blocks measured 9e-8 relative error on q1-scale data
# (6M rows), an order inside the 1e-6 oracle tolerance.
_MATMUL_BLOCK = 1 << 14


def set_agg_algorithm(algo: Optional[str]) -> None:
    """Force the device segment-reduction strategy (tests) or None=auto."""
    if algo not in (None, "matmul", "scatter", "sort"):
        raise ValueError(f"agg algorithm {algo!r}")
    _AGG_ALGO["force"] = algo


def segment_algo(capacity: int, n_rows: Optional[int] = None) -> str:
    """Strategy for one kernel trace (n_rows static at trace time).

    TPU: matmul (MXU one-hot einsum) while rows x capacity stays inside
    the FLOP bound, else scatter (block-compensated ``segment_sum``).
    CPU: scatter (XLA:CPU lowers it to a tight loop).

    The sort + segmented-scan reducer is never CHOSEN: on XLA:TPU its
    1-D ``associative_scan`` does not compile in usable time at stage
    batch sizes (v5e, PR 21: >17 min at 4M rows x 1M capacity, and q3's
    477 s "device time" was this compile).  It stays reachable through
    ``set_agg_algorithm("sort")`` and the variance family's
    ``force_sort``, which need its per-combine compensation.
    """
    if _AGG_ALGO["force"] is not None:
        return _AGG_ALGO["force"]
    if jax.default_backend() == "cpu":
        return "scatter"
    if capacity > _MATMUL_MAX_CAP or (
        n_rows is not None and n_rows * capacity > _MATMUL_MAX_ELEMS
    ):
        return "scatter"
    return "matmul"


def algo_cache_token() -> tuple:
    """Part of any compiled-kernel cache key: the strategy inputs that are
    NOT visible in the kernel signature (forced algorithm, backend,
    the matmul bounds)."""
    return (
        _AGG_ALGO["force"],
        jax.default_backend(),
        _MATMUL_MAX_CAP,
        _MATMUL_MAX_ELEMS,
    )


def _blocked_onehot_agg(V, seg_ids, capacity, n_sum_cols):
    """Segment-reduce all aggregate columns in ONE one-hot einsum.

    V: [n, S+C] f32 — S masked value columns then C 0/1 count columns.
    Returns (hi [cap, S], lo [cap, S], counts [cap, C] int).

    Rows reshape into [nb, block] blocks; a single batched einsum
    ``onehot[nb, block, cap] x V[nb, block, S+C] -> partials[nb, cap, S+C]``
    puts the whole reduction on the MXU (precision=HIGHEST keeps f32
    products exact — default bf16 inputs measured 5.5e-6 relative error,
    30x past the oracle tolerance).  Value partials then combine across
    blocks in a pairwise 2Sum tree for a double-float (hi, lo) total;
    count partials are exact integers (block <= 2^22 < 2^24) and sum
    exactly in i32/i64.
    """
    n = V.shape[0]
    block = _MATMUL_BLOCK
    nb = max(1, -(-n // block))
    nb = 1 << (nb - 1).bit_length()  # pow2 block count for the pair tree
    n2 = nb * block
    if n2 != n:
        V = jnp.pad(V, ((0, n2 - n), (0, 0)))
        seg_ids = jnp.pad(seg_ids, (0, n2 - n))
    oh = jax.nn.one_hot(
        seg_ids.reshape(nb, block), capacity, dtype=jnp.float32
    )
    partials = jnp.einsum(
        "abc,abk->ack",
        oh,
        V.reshape(nb, block, V.shape[1]),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [nb, cap, S+C]
    counts = partials[:, :, n_sum_cols:].astype(_I()).sum(axis=0)
    hi = partials[:, :, :n_sum_cols]
    lo = jnp.zeros_like(hi)
    while hi.shape[0] > 1:  # unrolled at trace: static shapes, log depth
        s, e = _two_sum(hi[0::2], hi[1::2])
        hi, lo = s, lo[0::2] + lo[1::2] + e
    return hi[0], lo[0], counts


def _segment_sum_df32(v, seg_ids, capacity, block_cap: int = 4096):
    """Double-float compensated segment sum for f32 device math.

    f32 scatter-add over millions of rows accumulates ~sqrt(n)·eps ≈ 1e-4
    relative error — two orders past the 1e-6 oracle tolerance.  Instead:

    * rows split into 512-row blocks; per-block f32 scatter partials see at
      most 512 sequential adds per segment (≲ sqrt(512)·eps ≈ 1.4e-6 of
      one block's contribution, and per-block errors are independent so
      they shrink by another sqrt(n_blocks) in the total);
    * block partials combine in a pairwise double-float TREE — each level
      a vectorized 2Sum whose error term is captured EXACTLY into the lo
      word — giving a (hi, lo) pair with ~48-bit effective mantissa.

    Everything is vectorized (vmapped scatter + log2(n/block) tree levels);
    there is no O(n) scan, so device utilization stays high.  Rows pad up
    to a power-of-two block count (zeros aggregate into segment 0 with
    weight 0), so any row count works — mesh shards are NOT pow2-bucketed.

    Block sizing: relative error ≈ block·eps/sqrt(n) (per-block scatter
    error, independent across blocks), so block grows with n — keeping the
    [n/block, capacity] partial buffer small — while staying well inside
    the 1e-6 oracle tolerance at every scale.
    """
    n = v.shape[0]
    if jax.default_backend() == "cpu":
        block = int(max(256, min(block_cap, n // 64)))
    elif capacity <= (1 << 16):
        # TPU scatter cost grows with block COUNT (each vmapped block is
        # its own serialized scatter), but compensation quality shrinks as
        # blocks grow: nb <= 64 bounds the vmap cost while worst-case
        # skew (a whole segment inside one 8K block) stays ~5e-6 — this
        # path only runs at capacity > 8192, where typical rows/segment
        # per block are far smaller
        block = int(max(8192, -(-n // 64)))
    else:
        # very high cardinality: the [nb, capacity] partial buffer is the
        # constraint (64 x 2M x 4B = 512MB per column) — nb <= 8 keeps it
        # ~64MB; rows/segment are tiny here, so precision holds
        block = int(max(1 << 16, -(-n // 8)))
    nb = -(-n // block)
    nb = 1 << (nb - 1).bit_length()  # pow2 block count for the pair tree
    n2 = nb * block
    if n2 != n:
        v = jnp.pad(v, (0, n2 - n))
        seg_ids = jnp.pad(seg_ids, (0, n2 - n))
    vb = v.reshape(nb, block)
    sb = seg_ids.reshape(nb, block)
    hi = jax.vmap(
        lambda vv, ss: jax.ops.segment_sum(vv, ss, num_segments=capacity)
    )(vb, sb)
    lo = jnp.zeros_like(hi)
    while hi.shape[0] > 1:  # unrolled at trace: static shapes, log depth
        s, e = _two_sum(hi[0::2], hi[1::2])
        hi, lo = s, lo[0::2] + lo[1::2] + e
    return hi[0], lo[0]


def _sorted_segment_agg(seg_key, capacity: int, kinds: list, cols: list):
    """Sort-based segmented reduction: the TPU-native high-cardinality path.

    TPU scatter serializes (one element per cycle-ish), so at capacity
    beyond the matmul bound the scatter path costs ~rows/45M seconds PER
    COLUMN.  Sorting rows by group id once and running one segmented
    ``lax.associative_scan`` over ALL columns costs one XLA sort plus a
    handful of HBM passes, independent of capacity, amortized across every
    aggregate in the stage — and segment boundaries come from
    ``searchsorted`` (exact row counts, no reduction at all).

    seg_key: [n] i32 group ids with base-mask-failing rows set to
    ``capacity`` (they sort to the end, past every extracted boundary).
    kinds: per logical column, one of
      "df32" — double-float compensated sum; col is an (hi, lo) pair of
               f32 arrays (normalize leaves via ``_two_sum`` first).
               Errors stay RELATIVE TO THE SEGMENT (the scan resets at
               boundaries), unlike global-prefix schemes.
      "f64"  — plain f64 sum (x64 mode)
      "i32"  — exact integer count sum
      ("min", ident) / ("max", ident) — extremum (any dtype; masked rows
               AND empty segments carry the identity, matching the
               scatter path so cross-batch state merges stay correct)
    cols: matching arrays, gathered through the sort permutation here.

    Returns (per-kind segment totals [capacity], presence counts
    [capacity]); empty segments yield 0 for sums/counts and the identity
    for min/max.
    """
    n = seg_key.shape[0]
    if n < (1 << 31):
        # one u64 operand instead of (key, iota): seg_key is
        # non-negative and <= capacity (< 2^22 at the ceiling), so
        # key<<31|iota fits 53 bits and unsigned order == (key, iota)
        # lex order.  Measured (KERNELBENCH sort_operands): the
        # single-operand sort runs ~4.6x faster than the two-operand
        # form at equal rows.
        packed = (
            seg_key.astype(jnp.uint64) << jnp.uint64(31)
        ) | jnp.arange(n, dtype=jnp.uint64)
        (sp,) = jax.lax.sort((packed,), num_keys=1)
        s2 = (sp >> jnp.uint64(31)).astype(jnp.int32)
        perm = (sp & jnp.uint64(0x7FFFFFFF)).astype(jnp.int32)
    else:  # pragma: no cover - >2^31 rows per batch never happens
        s2, perm = jax.lax.sort_key_val(
            seg_key, jnp.arange(n, dtype=jnp.int32)
        )
    outs, presence, _ = _scan_segments(s2, perm, capacity, kinds, cols)
    return outs, presence


def _scan_segments(s2, perm, capacity: int, kinds: list, cols: list):
    """Segmented reduction over PRE-SORTED segment ids.

    ``s2``: [n] non-decreasing segment ids; rows excluded from every
    segment carry a sentinel >= capacity and sit at the end.  ``perm`` is
    the permutation that sorted the original rows into ``s2`` order;
    ``cols`` are in ORIGINAL row order and are gathered through ``perm``
    here.  Shared by :func:`_sorted_segment_agg` (which sorts host gids)
    and the keyed path (which sorts raw key codes and derives gids from
    key-change boundaries on device).  Returns (outs, presence, bounds).
    """
    n = s2.shape[0]
    flag = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), s2[1:] != s2[:-1]]
    )

    elems = [flag]
    slots = []  # per logical col: (kind, ident, slot index or (slot, slot))
    for kind, col in zip(kinds, cols):
        ident = None
        if isinstance(kind, tuple):
            kind, ident = kind
        if kind in ("df32", "omin", "omax"):
            hi, lo = col
            slots.append((kind, ident, (len(elems), len(elems) + 1)))
            elems.append(hi[perm])
            elems.append(lo[perm])
        else:
            slots.append((kind, ident, len(elems)))
            elems.append(col[perm])

    flat_kinds = ["flag"]
    for kind, _, _ in slots:
        if kind == "df32":
            flat_kinds.extend(["df32_hi", "df32_lo"])
        elif kind in ("omin", "omax"):
            flat_kinds.extend([f"{kind}_hi", f"{kind}_lo"])
        else:
            flat_kinds.append(kind)

    def combine(a, b):
        fa, fb = a[0], b[0]
        out = [jnp.logical_or(fa, fb)]
        i = 1
        while i < len(flat_kinds):
            kind = flat_kinds[i]
            if kind == "df32_hi":
                s, e = _two_sum(a[i], b[i])
                hi, lo2 = _two_sum(s, a[i + 1] + b[i + 1] + e)
                out.append(jnp.where(fb, b[i], hi))
                out.append(jnp.where(fb, b[i + 1], lo2))
                i += 2
                continue
            if kind in ("omin_hi", "omax_hi"):
                hi, lo = _lex_merge(
                    a[i], a[i + 1], b[i], b[i + 1], kind == "omin_hi"
                )
                out.append(jnp.where(fb, b[i], hi))
                out.append(jnp.where(fb, b[i + 1], lo))
                i += 2
                continue
            if kind in ("f64", "i32"):
                merged = a[i] + b[i]
            elif kind == "min":
                merged = jnp.minimum(a[i], b[i])
            else:  # max
                merged = jnp.maximum(a[i], b[i])
            out.append(jnp.where(fb, b[i], merged))
            i += 1
        return tuple(out)

    scanned = jax.lax.associative_scan(combine, tuple(elems))

    bounds = jnp.searchsorted(
        s2, jnp.arange(capacity + 1, dtype=jnp.int32), side="left"
    )
    presence = jnp.diff(bounds)
    last = jnp.clip(bounds[1:] - 1, 0, max(n - 1, 0))
    occupied = presence > 0

    outs = []
    for kind, ident, slot in slots:
        if kind == "df32":
            hi = jnp.where(occupied, scanned[slot[0]][last], 0.0)
            lo = jnp.where(occupied, scanned[slot[1]][last], 0.0)
            outs.append((hi, lo))
        elif kind in ("omin", "omax"):
            hi_s = scanned[slot[0]][last]
            lo_s = scanned[slot[1]][last]
            empty = jnp.asarray(ident, hi_s.dtype)
            outs.append(
                (
                    jnp.where(occupied, hi_s, empty),
                    jnp.where(occupied, lo_s, empty),
                )
            )
        else:
            v = scanned[slot][last]
            empty = (
                jnp.zeros((), v.dtype)
                if ident is None
                else jnp.asarray(ident, v.dtype)
            )
            outs.append(jnp.where(occupied, v, empty))
    return outs, presence, bounds


def make_partial_agg_kernel(
    filter_closure: Optional[JaxClosure],
    arg_closures: list[Optional[JaxClosure]],
    specs: list[KernelAggSpec],
    capacity: int,
    flat_names: list[str],
    force_sort: bool = False,
):
    """Build the fused filter→project→segment-aggregate device function.

    Returns ``fn(seg_ids, valid, *leaf_arrays) -> (states..., presence)``
    where every output is a [capacity] array.  Per-agg state layout is
    :func:`state_fields` — x64: sum/avg → (sum, n), x32: (sum_hi, sum_lo,
    n) double-float; min/max → (value, n); count/count_star → (n,).
    ``presence`` counts mask-passing rows per group: groups whose presence
    is 0 are dropped on host (their rows were all filtered out).

    Strategy (:func:`segment_algo`): on TPU at moderate capacity every
    sum/count reduces in ONE blocked one-hot einsum on the MXU (scatter
    serializes on TPU); min/max stay on ``segment_min/max``.  On CPU (and
    very high cardinality) everything stays scatter-based.
    """
    mode = precision_mode()

    def fn(seg_ids, valid, *arrays):
        env = dict(zip(flat_names, arrays))
        mask = valid
        if filter_closure is not None:
            pred, pvalid = filter_closure(env)
            if pvalid is not None:
                pred = jnp.logical_and(pred, pvalid)
            mask = jnp.logical_and(mask, pred)
        maskf = mask

        # strategy is static per trace: jit re-traces per row-count shape,
        # so the rows x capacity bound sees the actual batch size.
        # force_sort (variance family, x32): the scatter/matmul pair sums
        # compensate only across BLOCKS — in-block f32 rounding leaves
        # ~eps32·sqrt(block) relative error, which the Σx²−(Σx)²/n
        # cancellation amplifies by the conditioning number.  The sorted
        # scan 2Sums at EVERY combine (~2^-45 relative), keeping raw
        # moments usable.
        if force_sort and mode == "x32":
            algo = "sort"
        else:
            algo = segment_algo(capacity, int(seg_ids.shape[0]))
        if algo == "matmul" and mode == "x32":
            return _fn_matmul(env, seg_ids, maskf)
        if algo == "sort":
            return _fn_sorted(env, seg_ids, maskf)

        outs = []
        for spec, closure in zip(specs, arg_closures):
            if spec.func == "count_star":
                outs.append(
                    jax.ops.segment_sum(
                        maskf.astype(_I()), seg_ids, num_segments=capacity
                    )
                )
                continue
            val, avalid = closure(env)
            m = maskf if avalid is None else jnp.logical_and(maskf, avalid)
            n = jax.ops.segment_sum(m.astype(_I()), seg_ids, num_segments=capacity)
            if spec.func == "count":
                outs.append(n)
                continue
            if spec.func in ("sum", "avg"):
                if spec.pair:  # x32 i64 pair: sum halves, recombine exactly
                    vhi, vlo = val
                    z = jnp.zeros((), jnp.float32)
                    a_hi, a_lo = _segment_sum_df32(
                        jnp.where(m, vhi, z), seg_ids, capacity
                    )
                    b_hi, b_lo = _segment_sum_df32(
                        jnp.where(m, vlo, z), seg_ids, capacity
                    )
                    s, e = _two_sum(a_hi, b_hi)
                    outs.append(s)
                    outs.append(a_lo + b_lo + e)
                    outs.append(n)
                    continue
                v = jnp.where(m, val.astype(_F()), jnp.zeros((), _F()))
                if mode == "x32":
                    hi, lo = _segment_sum_df32(v, seg_ids, capacity)
                    outs.append(hi)
                    outs.append(lo)
                else:
                    outs.append(
                        jax.ops.segment_sum(v, seg_ids, num_segments=capacity)
                    )
                outs.append(n)
                continue
            if spec.func in ("min", "max") and spec.ord_pair:
                outs.extend(
                    _ord_segment_extremum(spec, val, m, seg_ids, capacity)
                )
                outs.append(n)
                continue
            if spec.func in ("min", "max"):
                v, ident = _minmax_operand(spec, val)
                red = (
                    jax.ops.segment_min
                    if spec.func == "min"
                    else jax.ops.segment_max
                )
                outs.append(
                    red(jnp.where(m, v, ident), seg_ids, num_segments=capacity)
                )
                outs.append(n)
                continue
            raise ExecutionError(f"kernel agg {spec.func}")
        presence = jax.ops.segment_sum(
            maskf.astype(_I()), seg_ids, num_segments=capacity
        )
        return tuple(outs) + (presence,)

    def _fn_sorted(env, seg_ids, maskf):
        """High-cardinality path: one sort, one segmented scan, no scatter.

        Base-mask-failing rows get the sentinel key ``capacity`` and sort
        past every boundary; presence comes free from the boundary counts.
        Per-argument validity folds into the columns (0 / identity), and
        count columns dedupe by validity like the matmul path.
        """
        key = jnp.where(maskf, seg_ids, jnp.asarray(capacity, seg_ids.dtype))
        kinds, cols, plan = _build_scan_plan(
            env, maskf, specs, arg_closures, mode
        )
        totals, presence = _sorted_segment_agg(key, capacity, kinds, cols)
        return tuple(_emit_scan_outs(plan, totals, presence)) + (presence,)

    def _fn_matmul(env, seg_ids, maskf):
        """x32 MXU path: one einsum reduces all sums AND all counts.

        Value columns are masked f32; count columns are 0/1 masks carried
        as f32 (per-block partials are exact integers, combined in i32).
        Count columns dedupe by mask identity — aggregates over the same
        argument validity share one column.
        """
        sum_cols: list = []  # masked f32 value columns
        cnt_cols: list = []  # f32 0/1 mask columns (deduped)
        # dedupe count columns by the VALIDITY tracer: leaf closures return
        # the shared env[...__valid] object, so sum(x)/avg(x)/count(x) over
        # the same column share one mask column (the base-mask sentinel
        # covers count_star and all-valid args)
        cnt_index: dict = {}

        def cnt_col(m, avalid=None):
            key = "base" if avalid is None else id(avalid)
            j = cnt_index.get(key)
            if j is None:
                j = len(cnt_cols)
                cnt_index[key] = j
                cnt_cols.append(m.astype(jnp.float32))
            return j

        plan: list = []  # per spec: ("sumlike"|"count", indices...) emit plan
        minmax: list = []  # (out_slot_builder) computed via segment_min/max
        for spec, closure in zip(specs, arg_closures):
            if spec.func == "count_star":
                plan.append(("count", cnt_col(maskf)))
                continue
            val, avalid = closure(env)
            m = maskf if avalid is None else jnp.logical_and(maskf, avalid)
            nj = cnt_col(m, avalid)
            if spec.func == "count":
                plan.append(("count", nj))
            elif spec.func in ("sum", "avg") and spec.pair:
                vhi, vlo = val
                z = jnp.zeros((), jnp.float32)
                sj1 = len(sum_cols)
                sum_cols.append(jnp.where(m, vhi, z))
                sj2 = len(sum_cols)
                sum_cols.append(jnp.where(m, vlo, z))
                plan.append(("sumpair", sj1, sj2, nj))
            elif spec.func in ("sum", "avg"):
                sj = len(sum_cols)
                sum_cols.append(
                    jnp.where(m, val.astype(jnp.float32), jnp.zeros((), jnp.float32))
                )
                plan.append(("sum", sj, nj))
            elif spec.func in ("min", "max") and spec.ord_pair:
                plan.append(("ominmax", len(minmax), nj))
                minmax.append(
                    _ord_segment_extremum(spec, val, m, seg_ids, capacity)
                )
            elif spec.func in ("min", "max"):
                v, ident = _minmax_operand(spec, val)
                red = (
                    jax.ops.segment_min
                    if spec.func == "min"
                    else jax.ops.segment_max
                )
                plan.append(("minmax", len(minmax), nj))
                minmax.append(
                    red(jnp.where(m, v, ident), seg_ids, num_segments=capacity)
                )
            else:
                raise ExecutionError(f"kernel agg {spec.func}")
        presence_j = cnt_col(maskf)

        V = jnp.stack(sum_cols + cnt_cols, axis=1)
        hi, lo, counts = _blocked_onehot_agg(
            V, seg_ids, capacity, len(sum_cols)
        )
        outs = []
        for entry in plan:
            if entry[0] == "count":
                outs.append(counts[:, entry[1]])
            elif entry[0] == "sumpair":
                s, e = _two_sum(hi[:, entry[1]], hi[:, entry[2]])
                outs.append(s)
                outs.append(lo[:, entry[1]] + lo[:, entry[2]] + e)
                outs.append(counts[:, entry[3]])
            elif entry[0] == "sum":
                outs.append(hi[:, entry[1]])
                outs.append(lo[:, entry[1]])
                outs.append(counts[:, entry[2]])
            elif entry[0] == "ominmax":
                ohi, olo = minmax[entry[1]]
                outs.append(ohi)
                outs.append(olo)
                outs.append(counts[:, entry[2]])
            else:  # minmax
                outs.append(minmax[entry[1]])
                outs.append(counts[:, entry[2]])
        return tuple(outs) + (counts[:, presence_j],)

    return fn


def _build_scan_plan(env, maskf, specs, arg_closures, mode):
    """Column/plan construction shared by the sort-based reductions.

    Evaluates every aggregate argument closure against ``env``, folds the
    base mask + per-argument validity into masked SCAN-FORM columns, and
    returns ``(kinds, cols, plan)``:

    * ``kinds``/``cols`` — per logical column, the scan element kind and
      array(s) as documented on :func:`_sorted_segment_agg` (min/max
      identities are PYTHON scalars so kinds stays hashable for kernel
      cache keys);
    * ``plan`` — per aggregate spec, the static emission recipe consumed
      by :func:`_emit_scan_outs`.

    Count columns dedupe by argument-validity identity (like the matmul
    path); a ``None`` count index means "use presence" (base mask).
    """
    kinds: list = []
    cols: list = []
    cnt_index: dict = {}  # validity id -> logical col index (None=base)

    def cnt_col(m, avalid=None):
        if avalid is None:
            return None  # base-mask count == presence (boundary diff)
        k = id(avalid)
        j = cnt_index.get(k)
        if j is None:
            j = len(kinds)
            cnt_index[k] = j
            kinds.append("i32")
            cols.append(m.astype(_I()))
        return j

    plan: list = []
    for spec, closure in zip(specs, arg_closures):
        if spec.func == "count_star":
            plan.append(("count", None))
            continue
        val, avalid = closure(env)
        m = maskf if avalid is None else jnp.logical_and(maskf, avalid)
        nj = cnt_col(m, avalid)
        if spec.func == "count":
            plan.append(("count", nj))
            continue
        if spec.func in ("sum", "avg"):
            if mode == "x32":
                if spec.pair:
                    vhi, vlo = val
                    z = jnp.zeros((), jnp.float32)
                    h, l = _two_sum(
                        jnp.where(m, vhi, z), jnp.where(m, vlo, z)
                    )
                else:
                    h = jnp.where(
                        m, val.astype(jnp.float32), jnp.zeros((), jnp.float32)
                    )
                    l = jnp.zeros_like(h)
                plan.append(("sum32", len(kinds), nj))
                kinds.append("df32")
                cols.append((h, l))
            else:
                v = jnp.where(m, val.astype(_F()), jnp.zeros((), _F()))
                plan.append(("sum64", len(kinds), nj))
                kinds.append("f64")
                cols.append(v)
            continue
        if spec.func in ("min", "max") and spec.ord_pair:
            vhi, vlo = val
            info = jnp.iinfo(jnp.int32)
            ident = int(info.max if spec.func == "min" else info.min)
            plan.append(("ominmax", len(kinds), nj))
            kinds.append((f"o{spec.func}", ident))
            cols.append(
                (jnp.where(m, vhi, ident), jnp.where(m, vlo, ident))
            )
            continue
        if spec.func in ("min", "max"):
            v, ident = _minmax_operand(spec, val)
            # identity as a PYTHON scalar: kinds must stay hashable for
            # kernel cache keys, and tracers have no .item() under jit
            if spec.int_minmax:
                info = jnp.iinfo(_I())
                ident_py = int(
                    info.max if spec.func == "min" else info.min
                )
            else:
                ident_py = float("inf" if spec.func == "min" else "-inf")
            plan.append(("minmax", len(kinds), nj))
            kinds.append((spec.func, ident_py))
            cols.append(jnp.where(m, v, ident))
            continue
        raise ExecutionError(f"kernel agg {spec.func}")
    return kinds, cols, plan


def _emit_scan_outs(plan, totals, presence) -> list:
    """Expand scan totals into the kernel's per-spec state-field order."""
    outs: list = []
    for entry in plan:
        if entry[0] == "count":
            outs.append(presence if entry[1] is None else totals[entry[1]])
        elif entry[0] in ("sum32", "ominmax"):
            hi, lo = totals[entry[1]]
            outs.append(hi)
            outs.append(lo)
            outs.append(presence if entry[2] is None else totals[entry[2]])
        else:  # sum64 / minmax
            outs.append(totals[entry[1]])
            outs.append(presence if entry[2] is None else totals[entry[2]])
    return outs


# --------------------------------------------------------- keyed aggregate
# Device-KEYED aggregation: the host never assigns group ids at all.  Raw
# per-key dictionary/identity CODES ship to the device; one multi-key
# ``lax.sort`` orders the rows, group ids fall out of key-change
# boundaries (cumsum of change flags), and the packed fetch returns the
# unique key codes alongside the states.  This replaces the host
# hash-probe/factorize encode (``ops/groups.py``) on the high-cardinality
# path — 44% of q3 SF10 wall in round 3 — with one astype per key
# per batch.  Counterpart of the reference's per-batch hash repartition
# loop (``shuffle_writer.rs:214-256``), redesigned sort-first for a
# scatter-hostile device.


def make_keyed_prep_kernel(
    filter_closure: Optional[JaxClosure],
    arg_closures: list[Optional[JaxClosure]],
    specs: list[KernelAggSpec],
    flat_names: list[str],
    holder: dict,
    extra_names: tuple = (),
    key_kinds: Optional[tuple] = None,
):
    """Per-batch half of the keyed aggregation.

    ``fn(keys, valid, *leaf_arrays) -> (mask, *keys, *flat_cols,
    *extras)``: runs the fused filter (and, wrapped in
    :func:`make_join_kernel`, the device join) and emits masked
    scan-form columns that BUFFER in HBM until the final sort.  ``keys``
    is a tuple of per-key code arrays and passes through untouched (it
    rides the ``seg_ids`` slot so the join wrapper composes unchanged);
    with ``key_kinds`` set, each entry is instead the operand tuple
    :func:`device_encode_keys` expects and the group-code derivation
    runs INSIDE this dispatch — the raw key column crosses the bridge
    once and the host never encodes at all.
    ``extra_names`` are env arrays buffered RAW for post-sort passes
    (device median / count_distinct / corr).  ``holder`` captures the
    static ``kinds``/``plan`` during the first trace for the finish
    kernel.
    """
    mode = precision_mode()

    def fn(keys, valid, *arrays):
        if key_kinds is not None:
            keys = device_encode_keys(key_kinds, keys)
        env = dict(zip(flat_names, arrays))
        mask = valid
        if filter_closure is not None:
            pred, pvalid = filter_closure(env)
            if pvalid is not None:
                pred = jnp.logical_and(pred, pvalid)
            mask = jnp.logical_and(mask, pred)
        kinds, cols, plan = _build_scan_plan(
            env, mask, specs, arg_closures, mode
        )
        holder["kinds"] = tuple(kinds)
        holder["plan"] = tuple(plan)
        flat: list = []
        for kind, col in zip(kinds, cols):
            if _is_pair_kind(kind):
                flat.extend(col)
            else:
                flat.append(col)
        extras = tuple(env[nm] for nm in extra_names)
        return (mask,) + tuple(keys) + tuple(flat) + extras

    return fn


def _is_pair_kind(kind) -> bool:
    """Scan-plan kinds whose column is an (hi, lo) ARRAY PAIR: df32
    compensated sums and order-pair extrema.  Pair columns must flatten
    into two buffer slots (the multi-batch path concatenates and pads
    per slot) and re-pair inside the finish kernel."""
    return kind == "df32" or (
        isinstance(kind, tuple) and kind[0] in ("omin", "omax")
    )


_KEYED_MEDIAN_CACHE: dict = {}


def keyed_median_kernel(n_keys: int, capacity: int):
    """Per-group sorted-argument pass: exact median AND distinct count
    (cached per key count/capacity).

    ``fn(mask, keys, vhi, vlo, vvalid) -> packed [6, capacity]``: ONE
    multi-key sort by (masked-last, *group keys, arg-null-last, value
    order-pair) places each group's valid values ascending; group
    boundaries come from a doubled segment id (gid*2 + null_flag) so the
    VALID-value count per group needs no scatter; the two middle values
    gather per group (decode/average on host) and distinct values count
    as run-starts via one cumsum.  Output rows: hi@lo_idx, lo@lo_idx,
    hi@hi_idx, lo@hi_idx, valid_count, distinct_count.
    """
    key = (n_keys, capacity)
    fn = _KEYED_MEDIAN_CACHE.get(key)
    if fn is not None:
        return fn

    def median_fn(mask, keys, vhi, vlo, vvalid):
        n = mask.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        inv = jnp.logical_not(mask).astype(jnp.int32)
        argnull = jnp.logical_not(vvalid).astype(jnp.int32)
        # vlo MUST be a sort key too: values whose hi words collide
        # (within ~1.2e-7 relative) otherwise stay unordered, gathering
        # the wrong middle element and overcounting distinct run-starts
        kfields = (inv,) + tuple(keys) + (argnull, vhi, vlo)
        packed = packed_multikey_sort(kfields, iota)
        if packed is not None:
            _, skeys = packed
        else:
            sorted_ = jax.lax.sort(
                kfields + (iota,), num_keys=4 + n_keys
            )
            skeys = sorted_[:-1]
        sinv = skeys[0]
        sk = skeys[1:1 + n_keys]
        snull = skeys[1 + n_keys]
        shi = skeys[2 + n_keys]
        slo = skeys[3 + n_keys]
        valid = sinv == 0
        diff = sk[0][1:] != sk[0][:-1]
        for k in sk[1:]:
            diff = jnp.logical_or(diff, k[1:] != k[:-1])
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_), diff])
        flag = jnp.logical_and(first, valid)
        gid = jnp.cumsum(flag.astype(jnp.int32)) - 1
        # doubled id: even slot = valid-arg rows, odd = null-arg rows;
        # masked rows park past every boundary
        big = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
        s2 = jnp.where(valid, gid * 2 + snull, big)
        bounds = jnp.searchsorted(
            s2, jnp.arange(2 * capacity + 1, dtype=jnp.int32), side="left"
        )
        start = bounds[0::2][:capacity]
        end_valid = bounds[1::2]
        cnt = end_valid - start
        lo_idx = jnp.clip(start + (cnt - 1) // 2, 0, max(n - 1, 0))
        hi_idx = jnp.clip(start + cnt // 2, 0, max(n - 1, 0))
        # distinct count: value-run starts among each group's valid rows
        vdiff = jnp.logical_or(shi[1:] != shi[:-1], slo[1:] != slo[:-1])
        runfirst = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), jnp.logical_or(diff, vdiff)]
        )
        dflag = jnp.logical_and(
            jnp.logical_and(runfirst, valid), snull == 0
        )
        cum0 = jnp.concatenate(
            [
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum(dflag.astype(jnp.int32)),
            ]
        )
        distinct = cum0[end_valid] - cum0[start]
        idt = jnp.int32 if precision_mode() == "x32" else jnp.int64
        rows = [
            shi[lo_idx].astype(idt),
            slo[lo_idx].astype(idt),
            shi[hi_idx].astype(idt),
            slo[hi_idx].astype(idt),
            cnt.astype(idt),
            distinct.astype(idt),
        ]
        return jnp.stack(rows, axis=0)

    fn = jax.jit(median_fn)
    _KEYED_MEDIAN_CACHE[key] = fn
    return fn


_KEYED_SORT_CACHE: dict = {}


def packed_multikey_sort(keys: tuple, iota):
    """Lexicographic multi-key sort with PAIRWISE-u64-PACKED operands.

    ``keys`` are i32 arrays (most-significant first); ``iota`` is the i32
    row index riding as the final tiebreaker.  Each u64 word carries two
    sign-biased 32-bit fields, so unsigned u64 lex order over
    ceil((k+1)/2) words equals i32 tuple order over k+1 operands —
    halving (or better) the bytes every bitonic pass moves.  Measured
    (KERNELBENCH sort_operands): u64x1 sorts ~4.6x faster than i32x2 and
    ~9x faster than i32x5 at equal rows.

    Returns ``(perm, sorted_keys)`` or None when a key isn't i32 (x64
    identity codes) — callers keep the plain operand form then.
    """
    import jax

    n = iota.shape[0]
    if n >= (1 << 31) or any(k.dtype != jnp.int32 for k in keys):
        return None
    fields = [
        # bias in SIGNED i64 first (no uint wraparound subtleties), then
        # reinterpret: result is always in [0, 2^32)
        (k.astype(jnp.int64) + jnp.int64(1 << 31)).astype(jnp.uint64)
        for k in keys
    ]
    fields.append(iota.astype(jnp.uint64))  # non-negative: bias-free
    if len(fields) % 2:
        # a constant low half never affects order
        fields.append(jnp.zeros((), jnp.uint64))
    words = []
    for j in range(0, len(fields), 2):
        hi, lo = fields[j], fields[j + 1]
        words.append((hi << jnp.uint64(32)) | (lo & jnp.uint64(0xFFFFFFFF)))
    sorted_words = jax.lax.sort(tuple(words), num_keys=len(words))
    out_fields = []
    for w in sorted_words:
        out_fields.append((w >> jnp.uint64(32)).astype(jnp.int64))
        out_fields.append((w & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64))
    sorted_keys = tuple(
        (f - jnp.int64(1 << 31)).astype(jnp.int32)
        for f in out_fields[: len(keys)]
    )
    perm = out_fields[len(keys)].astype(jnp.int32)
    return perm, sorted_keys


# ------------------------------------------------------ device key encode
# Device twin of the host group-key encoders (bridge.make_key_encoder /
# device_key_encoder): the raw key column crosses the bridge ONCE as
# (values, validity) and the jitted kernel derives the group code
# bit-identically to the host encoder, so the keyed route pays no host
# encode at all — the same host/device bit-identity pattern
# make_partition_id_kernel proved for shuffle partition ids.  Kinds:
#   "code"  — host-encoded codes pass through (dict/string handoff)
#   "ident" — int/date32 identity codes: value + 1, null -> 0
#             (bridge.IdentityKeyEncoder), computed in the shipped
#             integer dtype (i32 when the host precheck narrowed)
#   "bool"  — null -> 0, False -> 1, True -> 2 (bridge.BoolKeyEncoder)
#   "f32"/"f64" — the RAW bit pattern as a signed integer, null -> a
#             reserved NaN pattern (bridge.FloatKeyEncoder).  Pure
#             bit-pattern grouping matches the CPU hash aggregate
#             exactly (dictionary_encode distinguishes -0.0 from +0.0
#             and NaN payloads from each other — measured, and the
#             oracle identity contract follows IT, not IEEE equality);
#             a host precheck falls back when data contains the one
#             reserved payload
FLOAT32_NULL_BITS = 0xFFC00001 - (1 << 32)  # as signed i32
FLOAT64_NULL_BITS = 0xFFF8000000000001 - (1 << 64)  # as signed i64


def device_encode_key(kind: str, vals, valid):
    """Traceable group-code derivation for ONE key column (see the kind
    table above).  ``vals``/``valid`` are the padded device arrays; pad
    rows carry valid=False and encode to the null code — they are masked
    out of every segment downstream, so their code value never matters.
    """
    if kind == "ident":
        one = jnp.asarray(1, vals.dtype)
        zero = jnp.zeros((), vals.dtype)
        return jnp.where(valid, vals + one, zero)
    if kind == "bool":
        v = vals.astype(jnp.int32) + jnp.int32(1)
        return jnp.where(valid, v, jnp.zeros((), jnp.int32))
    if kind in ("f32", "f64"):
        idt = jnp.int32 if kind == "f32" else jnp.int64
        null = jnp.asarray(
            FLOAT32_NULL_BITS if kind == "f32" else FLOAT64_NULL_BITS,
            idt,
        )
        bits = jax.lax.bitcast_convert_type(vals, idt)
        return jnp.where(valid, bits, null)
    raise ExecutionError(f"device key-encode kind {kind}")


def device_encode_keys(kinds: tuple, keys: tuple) -> tuple:
    """Per-key codes from mixed operands: ``keys[k]`` is ``(codes,)`` for
    kind "code" (host dictionary handoff) or ``(values, validity)`` for
    a device-encoded kind."""
    out = []
    for kind, ops in zip(kinds, keys):
        if kind == "code":
            out.append(ops[0])
        else:
            out.append(device_encode_key(kind, *ops))
    return tuple(out)


_KEY_ENCODE_CACHE: dict = {}


def make_key_encode_kernel(kinds: tuple):
    """Jitted standalone ``fn(keys) -> code arrays`` (parity tests; the
    production path traces :func:`device_encode_keys` INSIDE the fused
    keyed prep kernel so encode shares the batch's single dispatch)."""
    fn = _KEY_ENCODE_CACHE.get(kinds)
    if fn is None:
        fn = jax.jit(lambda keys: device_encode_keys(kinds, keys))
        _KEY_ENCODE_CACHE[kinds] = fn
    return fn


def keyed_sort_body(n_keys: int):
    """Traceable phase-1 body (see :func:`keyed_sort_kernel`): returned
    uncompiled so the fused keyed runner can inline encode→sort into one
    jitted dispatch."""
    return _keyed_sort_fn(n_keys)


def keyed_sort_kernel(n_keys: int):
    """Phase 1 of the keyed aggregation (cached per key count).

    ``fn(mask, *keys) -> (s2, perm, *sorted_keys, n_groups)``: one
    multi-key sort with the inverted mask as the MAJOR key (masked rows
    sink past every boundary), then group ids from key-change boundaries.
    ``s2`` is non-decreasing with masked rows at INT32_MAX, exactly the
    contract :func:`_scan_segments` wants; ``n_groups`` is the only value
    the host fetches before building the capacity-sized finish kernel.
    """
    fn = _KEYED_SORT_CACHE.get(n_keys)
    if fn is not None:
        return fn
    fn = jax.jit(_keyed_sort_fn(n_keys))
    _KEYED_SORT_CACHE[n_keys] = fn
    return fn


def _keyed_sort_fn(n_keys: int):
    def sort_fn(mask, *keys):
        n = mask.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)
        inv = jnp.logical_not(mask).astype(jnp.int32)
        if n_keys == 1 and keys[0].dtype == jnp.int32 and n < (1 << 31):
            # Single-OPERAND packed sort (trace-time specialization —
            # dtype and shape are static): bit 63 carries the inverted
            # mask (masked rows sink), bits 62..31 the sign-biased key,
            # bits 30..0 the row index, so ONE uint64 array rides the
            # bitonic passes instead of three i32 operands.  Measured
            # (KERNELBENCH sort_operands family): the u64x1 form sorts
            # ~4.6x faster than i32x2 and ~9x faster than i32x5 at 1e5
            # rows on the CPU backend — and every sort-based device
            # path was the r05 chip capture's loss center.
            biased = (
                keys[0].astype(jnp.int64) + jnp.int64(1 << 31)
            ).astype(jnp.uint64)
            packed = (
                (inv.astype(jnp.uint64) << jnp.uint64(63))
                | (biased << jnp.uint64(31))
                | iota.astype(jnp.uint64)
            )
            (sp,) = jax.lax.sort((packed,), num_keys=1)
            perm = (sp & jnp.uint64(0x7FFFFFFF)).astype(jnp.int32)
            k0 = (
                ((sp >> jnp.uint64(31)) & jnp.uint64(0xFFFFFFFF)).astype(
                    jnp.int64
                )
                - jnp.int64(1 << 31)
            ).astype(jnp.int32)
            valid = (sp >> jnp.uint64(63)) == jnp.uint64(0)
            sk = (k0,)
        else:
            packed2 = packed_multikey_sort((inv,) + tuple(keys), iota)
            if packed2 is not None:
                # multi-key form: pairwise-u64 words (see
                # packed_multikey_sort) — 2 words vs 3-5 operands
                perm, skeys = packed2
                sk = skeys[1:]
                valid = skeys[0] == 0
            else:
                sorted_ = jax.lax.sort(
                    (inv, *keys, iota), num_keys=1 + n_keys
                )
                sk = sorted_[1:1 + n_keys]
                perm = sorted_[-1]
                valid = sorted_[0] == 0
        diff = sk[0][1:] != sk[0][:-1]
        for k in sk[1:]:
            diff = jnp.logical_or(diff, k[1:] != k[:-1])
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_), diff])
        flag = jnp.logical_and(first, valid)
        gid = jnp.cumsum(flag.astype(jnp.int32)) - 1
        sentinel = jnp.asarray(np.iinfo(np.int32).max, jnp.int32)
        s2 = jnp.where(valid, gid, sentinel)
        n_groups = jnp.sum(flag.astype(jnp.int32))
        return (s2, perm) + tuple(sk) + (n_groups,)

    return sort_fn


_KEYED_FINISH_CACHE: dict = {}


def keyed_finish_kernel(
    kinds: tuple,
    plan: tuple,
    specs: list[KernelAggSpec],
    n_keys: int,
    capacity: int,
    mode: str,
):
    """Phase 2: gather + segmented scan + key extraction + pack, one jit.

    ``fn(s2, perm, sk, flat_cols) -> packed [n_state_fields + 1 + n_keys,
    capacity]`` integer array (floats bitcast like
    :func:`pack_for_fetch`): per-spec state fields, presence, then the
    unique key CODES gathered at each segment's first sorted row — so one
    fetch returns both the states and the group keys.
    """
    cache_key = (kinds, plan, tuple(specs), n_keys, capacity, mode)
    fn = _KEYED_FINISH_CACHE.get(cache_key)
    if fn is not None:
        return fn
    flags = [f for spec in specs for f in state_is_int(spec, mode)] + [True]

    def finish_fn(s2, perm, sk, flat):
        cols: list = []
        i = 0
        for kind in kinds:
            if _is_pair_kind(kind):
                cols.append((flat[i], flat[i + 1]))
                i += 2
            else:
                cols.append(flat[i])
                i += 1
        totals, presence, bounds = _scan_segments(
            s2, perm, capacity, list(kinds), cols
        )
        outs = _emit_scan_outs(list(plan), totals, presence) + [presence]
        n = s2.shape[0]
        starts = jnp.clip(bounds[:-1], 0, max(n - 1, 0))
        occupied = presence > 0
        fdt = jnp.float64 if mode == "x64" else jnp.float32
        idt = jnp.int64 if mode == "x64" else jnp.int32
        rows = [
            a.astype(idt)
            if is_int
            else jax.lax.bitcast_convert_type(a.astype(fdt), idt)
            for a, is_int in zip(outs, flags)
        ]
        for k in sk:
            rows.append(
                jnp.where(occupied, k[starts], jnp.zeros((), k.dtype)).astype(
                    idt
                )
            )
        return jnp.stack(rows, axis=0)

    fn = jax.jit(finish_fn)
    _KEYED_FINISH_CACHE[cache_key] = fn
    return fn


_KEYED_CORR_CACHE: dict = {}


def keyed_corr_kernel(capacity: int, mode: str):
    """Per-group Pearson correlation moments, PER-GROUP centered.

    Reuses the keyed path's phase-1 sort (``s2``/``perm``): pass 1 scans
    per-group Σx, Σy, n over pairwise-valid rows (null or NaN in either
    argument drops the row from every sum, pandas semantics); the
    per-group means gather back to rows; pass 2 scans the CENTERED
    products Σx'y', Σx'², Σy'².  Centering by each group's own mean is
    strictly stronger conditioning than the CPU operator's global-mean
    centering — the center constant need not be exact, it only has to
    kill the magnitude.

    x32: ``fn(s2, perm, xhi, xlo, xvalid, yhi, ylo, yvalid)``; x64:
    ``fn(s2, perm, x, xvalid, y, yvalid)``.  Returns packed integer rows
    [Σxy(hi,lo) Σxx(hi,lo) Σyy(hi,lo) n] (x32) / [Σxy Σxx Σyy n] (x64);
    the host finalizes Σxy/√(Σxx·Σyy).
    """
    key = (capacity, mode)
    fn = _KEYED_CORR_CACHE.get(key)
    if fn is not None:
        return fn

    if mode == "x32":

        def corr_fn(s2, perm, xhi, xlo, xvalid, yhi, ylo, yvalid):
            m = jnp.logical_and(xvalid, yvalid)
            m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(xhi)))
            m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(yhi)))
            z = jnp.zeros((), jnp.float32)
            kinds1 = ["df32", "df32", "i32"]
            cols1 = [
                (jnp.where(m, xhi, z), jnp.where(m, xlo, z)),
                (jnp.where(m, yhi, z), jnp.where(m, ylo, z)),
                m.astype(jnp.int32),
            ]
            (sx, sy, n_pair), _pres, _b = _scan_segments(
                s2, perm, capacity, kinds1, cols1
            )
            nf = jnp.maximum(n_pair, 1).astype(jnp.float32)
            mx = (sx[0] + sx[1]) / nf
            my = (sy[0] + sy[1]) / nf
            gid = jnp.clip(s2, 0, capacity - 1)
            # centered values in sorted-row order: gather means per row
            mxr = mx[gid]
            myr = my[gid]
            # perm-gathered (sorted) argument rows
            xs_hi, xs_lo = xhi[perm], xlo[perm]
            ys_hi, ys_lo = yhi[perm], ylo[perm]
            ms = m[perm]
            xc = (xs_hi - mxr) + xs_lo
            yc = (ys_hi - myr) + ys_lo
            kinds2 = ["df32", "df32", "df32"]
            zero = jnp.zeros_like(xc)
            cols2 = [
                (jnp.where(ms, xc * yc, z), zero),
                (jnp.where(ms, xc * xc, z), zero),
                (jnp.where(ms, yc * yc, z), zero),
            ]
            # cols are already in SORTED order: identity perm for pass 2
            iota = jnp.arange(s2.shape[0], dtype=jnp.int32)
            (sxy, sxx, syy), _p2, _b2 = _scan_segments(
                s2, iota, capacity, kinds2, cols2
            )
            idt = jnp.int32
            rows = [
                jax.lax.bitcast_convert_type(sxy[0], idt),
                jax.lax.bitcast_convert_type(sxy[1], idt),
                jax.lax.bitcast_convert_type(sxx[0], idt),
                jax.lax.bitcast_convert_type(sxx[1], idt),
                jax.lax.bitcast_convert_type(syy[0], idt),
                jax.lax.bitcast_convert_type(syy[1], idt),
                n_pair.astype(idt),
            ]
            return jnp.stack(rows, axis=0)

    else:

        def corr_fn(s2, perm, x, xvalid, y, yvalid):
            m = jnp.logical_and(xvalid, yvalid)
            m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(x)))
            m = jnp.logical_and(m, jnp.logical_not(jnp.isnan(y)))
            z = jnp.zeros((), jnp.float64)
            kinds1 = ["f64", "f64", "i32"]
            cols1 = [
                jnp.where(m, x, z),
                jnp.where(m, y, z),
                m.astype(jnp.int64),
            ]
            (sx, sy, n_pair), _pres, _b = _scan_segments(
                s2, perm, capacity, kinds1, cols1
            )
            nf = jnp.maximum(n_pair, 1).astype(jnp.float64)
            mx = sx / nf
            my = sy / nf
            gid = jnp.clip(s2, 0, capacity - 1)
            xs, ys, ms = x[perm], y[perm], m[perm]
            xc = xs - mx[gid]
            yc = ys - my[gid]
            iota = jnp.arange(s2.shape[0], dtype=jnp.int32)
            (sxy, sxx, syy), _p2, _b2 = _scan_segments(
                s2, iota, capacity, ["f64", "f64", "f64"],
                [
                    jnp.where(ms, xc * yc, z),
                    jnp.where(ms, xc * xc, z),
                    jnp.where(ms, yc * yc, z),
                ],
            )
            idt = jnp.int64
            rows = [
                jax.lax.bitcast_convert_type(sxy, idt),
                jax.lax.bitcast_convert_type(sxx, idt),
                jax.lax.bitcast_convert_type(syy, idt),
                n_pair.astype(idt),
            ]
            return jnp.stack(rows, axis=0)

    fn = jax.jit(corr_fn)
    _KEYED_CORR_CACHE[key] = fn
    return fn


def merge_keyed_host(
    specs: list[KernelAggSpec],
    mode: str,
    per_dev: list,
) -> tuple[list[np.ndarray], list[np.ndarray], int]:
    """Merge per-shard keyed results BY KEY on host (numpy, vectorized).

    ``per_dev``: list of (states, key_cols, n_groups) as returned by
    :func:`unpack_keyed_host` (+ group count).  The merge is
    [total distinct]-sized — the O(rows) work stayed on the shards; an
    ICI tree-merge is a future optimization.  Returns (merged states
    incl. trailing presence, merged key code arrays, n_groups).
    """
    live = [(s, k, n) for s, k, n in per_dev if n > 0]
    if not live:
        empty = [np.zeros(0, dtype=np.int64) for _ in per_dev[0][0]]
        return empty, [np.zeros(0, np.int64) for _ in per_dev[0][1]], 0
    n_keys = len(live[0][1])
    keys = [
        np.concatenate([k[j][:n] for _s, k, n in live])
        for j in range(n_keys)
    ]
    states = [
        np.concatenate([s[i][:n] for s, _k, n in live])
        for i in range(len(live[0][0]))
    ]
    order = np.lexsort(tuple(reversed(keys)))
    keys = [k[order] for k in keys]
    states = [s[order] for s in states]
    n_rows = len(keys[0])
    newflag = np.ones(n_rows, dtype=bool)
    for k in keys:
        nf = np.empty(n_rows, dtype=bool)
        nf[0] = True
        nf[1:] = k[1:] != k[:-1]
        if k is keys[0]:
            newflag = nf
        else:
            newflag |= nf
    starts = np.flatnonzero(newflag)
    out_keys = [k[starts] for k in keys]

    def _reduceat(a, how):
        if how == "sum":
            return np.add.reduceat(a.astype(np.float64), starts)
        if how == "isum":
            return np.add.reduceat(a.astype(np.int64), starts)
        if how == "min":
            return np.minimum.reduceat(a, starts)
        return np.maximum.reduceat(a, starts)

    def _lex_reduceat(hi, lo, how):
        # lexicographic (hi, lo) i32 extremum via ONE biased u64 key —
        # bridge.join_u64 owns the bias/pack convention (and its
        # docstring owns the i64-wrap warning)
        from .bridge import join_u64

        m = _reduceat(join_u64(hi, lo), how)
        return (
            (m >> np.uint64(32)).astype(np.int64) - (1 << 31),
            (m & np.uint64(0xFFFFFFFF)).astype(np.int64) - (1 << 31),
        )

    out: list[np.ndarray] = []
    i = 0
    for spec in specs:
        if spec.func in ("sum", "avg") and mode == "x32":
            # recombine the pair in f64; compensation already happened
            # on-device — the per-group cross-shard sum is tiny
            v = states[i].astype(np.float64) + states[i + 1].astype(
                np.float64
            )
            out.append(_reduceat(v, "sum"))
            out.append(np.zeros(len(starts)))  # lo absorbed into hi
            out.append(_reduceat(states[i + 2], "isum"))
            i += 3
            continue
        if spec.ord_pair and spec.func in ("min", "max"):
            hi, lo = _lex_reduceat(
                states[i], states[i + 1], spec.func
            )
            out.extend([hi, lo, _reduceat(states[i + 2], "isum")])
            i += 3
            continue
        for role in state_fields(spec, mode):
            if role == "min":
                out.append(_reduceat(states[i], "min"))
            elif role == "max":
                out.append(_reduceat(states[i], "max"))
            else:  # additive
                is_int = states[i].dtype.kind in "iu"
                out.append(
                    _reduceat(states[i], "isum" if is_int else "sum")
                )
            i += 1
    out.append(_reduceat(states[-1], "isum"))  # presence
    return out, out_keys, len(starts)


def unpack_keyed_host(
    specs: list[KernelAggSpec], packed: np.ndarray, mode: str, n_keys: int
) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Host inverse of :func:`keyed_finish_kernel`'s pack: (state arrays
    incl. trailing presence, per-key unique code arrays as int64)."""
    flags = [f for spec in specs for f in state_is_int(spec, mode)] + [True]
    fdt = np.float64 if mode == "x64" else np.float32
    states = [
        row if is_int else row.view(fdt)
        for row, is_int in zip(packed[: len(flags)], flags)
    ]
    keys = [
        packed[len(flags) + k].astype(np.int64) for k in range(n_keys)
    ]
    return states, keys


def _ord_segment_extremum(spec, val, m, seg_ids, capacity):
    """Exact segment extremum over an order-pair operand: reduce hi, then
    reduce lo among the rows tied at the extremal hi (two segment
    reductions = one lexicographic 64-bit extremum)."""
    vhi, vlo = val
    info = jnp.iinfo(jnp.int32)
    if spec.func == "min":
        red, ident = jax.ops.segment_min, info.max
    else:
        red, ident = jax.ops.segment_max, info.min
    hi_m = jnp.where(m, vhi, ident)
    seg_hi = red(hi_m, seg_ids, num_segments=capacity)
    tie = jnp.logical_and(m, hi_m == seg_hi[seg_ids])
    lo_m = jnp.where(tie, vlo, ident)
    seg_lo = red(lo_m, seg_ids, num_segments=capacity)
    return [seg_hi, seg_lo]


def _minmax_operand(spec: KernelAggSpec, val):
    """(operand, identity) for a min/max reduction, dtype-preserving for
    the integer path (exactness) and float for the rest."""
    if spec.int_minmax:
        v = val.astype(_I())
        info = jnp.iinfo(_I())
        ident = jnp.asarray(
            info.max if spec.func == "min" else info.min, _I()
        )
        return v, ident
    v = val.astype(_F())
    ident = jnp.asarray(
        jnp.inf if spec.func == "min" else -jnp.inf, _F()
    )
    return v, ident


def _pad_ident(role: str, dtype):
    """Growth-padding identity per state field, dtype-aware (integer
    min/max states must not pad with float inf)."""
    if role in ("min", "omin_hi", "omin_lo"):
        return (
            jnp.iinfo(dtype).max
            if jnp.issubdtype(dtype, jnp.integer)
            else jnp.inf
        )
    if role in ("max", "omax_hi", "omax_lo"):
        return (
            jnp.iinfo(dtype).min
            if jnp.issubdtype(dtype, jnp.integer)
            else -jnp.inf
        )
    return 0


def pad_states(
    specs: list[KernelAggSpec],
    acc: Optional[tuple],
    new_cap: int,
    mode: str,
):
    """Grow accumulated [old_cap] states to [new_cap] (adaptive segment
    capacity): additive fields pad with 0, extrema with their identity.
    Existing group ids stay valid — the host encoder assigns them
    monotonically."""
    if acc is None:
        return None
    out = []
    i = 0
    old_cap = acc[0].shape[0]
    grow = new_cap - old_cap
    for spec in specs:
        for role in state_fields(spec, mode):
            ident = _pad_ident(role, acc[i].dtype)
            out.append(
                jnp.pad(acc[i], (0, grow), constant_values=ident)
            )
            i += 1
    out.append(jnp.pad(acc[-1], (0, grow)))  # presence
    return tuple(out)


def state_is_int(spec: KernelAggSpec, mode: str) -> tuple[bool, ...]:
    """Which state fields are integer (counts) vs float, in layout order."""
    if spec.func in ("count", "count_star"):
        return (True,)
    if spec.func in ("sum", "avg"):
        return (False, False, True) if mode == "x32" else (False, True)
    if spec.ord_pair:
        return (True, True, True)  # (hi, lo, n) — all integer
    return (spec.int_minmax, True)  # min/max: (value, n)


# Packed-fetch plumbing: the device→host fetch is the stage's sync, and
# packing the whole state tuple into ONE array makes materialization a
# single transfer instead of one per state field.
# The pack travels in the INTEGER domain (floats bitcast to i32/i64):
# int→float bitcasts produce denormal bit patterns that the TPU flushes
# to zero during multi-row relayout — measured: a [2, 1] stack of
# bitcast counts came back all-zero — while integer copies are exact.
_PACK_CACHE: dict = {}


def pack_states(
    specs: list[KernelAggSpec], states: tuple, mode: str,
    keep: Optional[int] = None,
):
    """Traceable body of :func:`pack_for_fetch`: stack every state field
    (floats bitcast to the integer domain) into one [n_fields, keep]
    array.  Usable inside a larger jit (the fused single-dispatch runner
    packs in the same trace as the kernels) or via the jitted wrapper."""
    cap = states[0].shape[-1]
    if keep is None or keep > cap:
        keep = cap
    flags = [
        f for spec in specs for f in state_is_int(spec, mode)
    ] + [True]  # presence
    fdt = jnp.float64 if mode == "x64" else jnp.float32
    idt = jnp.int64 if mode == "x64" else jnp.int32
    rows = [
        a[:keep].astype(idt)
        if is_int
        else jax.lax.bitcast_convert_type(a[:keep].astype(fdt), idt)
        for a, is_int in zip(states, flags)
    ]
    return jnp.stack(rows, axis=0)


def pack_for_fetch(
    specs: list[KernelAggSpec], acc: tuple, mode: str,
    keep: Optional[int] = None,
):
    """Device-side: concat all state fields into one [n_fields, keep] array.

    ``keep`` (static per trace; callers bucket it to a power of two so
    retraces stay bounded) slices the fetch to the slots that hold real
    groups — capacity grows in 4x steps, so fetching all of it moves up
    to 4x more bytes than the group table ever assigned, and fetch
    bandwidth is the scarce resource at high cardinality."""
    cap = acc[0].shape[-1]
    if keep is None or keep > cap:
        keep = cap
    key = (tuple(specs), mode, cap, keep)
    fn = _PACK_CACHE.get(key)
    if fn is None:
        fn = jax.jit(
            lambda states: pack_states(specs, states, mode, keep)
        )
        _PACK_CACHE[key] = fn
    return fn(acc)


def unpack_host(
    specs: list[KernelAggSpec], packed: np.ndarray, mode: str
) -> list[np.ndarray]:
    """Host-side inverse of :func:`pack_for_fetch` (numpy, no device)."""
    flags = [f for spec in specs for f in state_is_int(spec, mode)] + [True]
    fdt = np.float64 if mode == "x64" else np.float32
    out = []
    for row, is_int in zip(packed, flags):
        out.append(row if is_int else row.view(fdt))
    return out


def combine_states(
    specs: list[KernelAggSpec],
    acc: Optional[tuple],
    new: tuple,
    mode: Optional[str] = None,
) -> tuple:
    """Merge per-batch kernel outputs (device-side, cheap elementwise).

    In x32 mode sum/avg states are double-float (hi, lo) pairs merged with
    an error-free 2Sum so cross-batch accumulation keeps ~f64 precision.
    ``mode`` must be the mode the kernel was BUILT under (the owning
    TpuStageExec pins it); the global is only a fallback.
    """
    if acc is None:
        return new
    mode = mode or precision_mode()
    out = []
    i = 0
    for spec in specs:
        fields = state_fields(spec, mode)
        if spec.func in ("sum", "avg") and mode == "x32":
            s, e = _two_sum(acc[i], new[i])
            out.append(s)
            out.append(acc[i + 1] + new[i + 1] + e)
            out.append(acc[i + 2] + new[i + 2])
            i += 3
            continue
        if spec.ord_pair and spec.func in ("min", "max"):
            hi, lo = _lex_merge(
                acc[i], acc[i + 1], new[i], new[i + 1],
                spec.func == "min",
            )
            out.append(hi)
            out.append(lo)
            out.append(acc[i + 2] + new[i + 2])
            i += 3
            continue
        for role in fields:
            if role == "min":
                out.append(jnp.minimum(acc[i], new[i]))
            elif role == "max":
                out.append(jnp.maximum(acc[i], new[i]))
            else:
                out.append(acc[i] + new[i])
            i += 1
    out.append(acc[-1] + new[-1])  # presence
    return tuple(out)


# --------------------------------------------------- shuffle hash partition
# Device twin of exec.operators.hash_partition_indices: the SAME 64-bit
# multiply/xorshift/combine hash, built from uint32 limb arithmetic so it
# runs in x32 mode on accelerators without native 64-bit ALUs.  Map and
# reduce sides of a join must co-partition, so assignments have to match
# the host/native partitioner bit-for-bit (property-tested in
# tests/test_shuffle_writer.py).

_HASH_MUL = (0x9E3779B9, 0x7F4A7C15)  # (hi, lo) of the host multiplier
_NULL_HASH = (0xA5A5A5A5, 0xDEADBEEF)  # (hi, lo) of the host null hash
# the n <= 2^16 gate keeps every intermediate of the final 64-bit mod
# inside uint32: (n-1)^2 + (n-1) < 2^32
PID_MAX_PARTITIONS = 1 << 16


def _mul64_limbs(ahi, alo, bhi, blo):
    """Low 64 bits of a 64x64 product over (hi, lo) uint32 limbs —
    16-bit half-products so nothing needs a widening multiply."""
    mask16 = jnp.uint32(0xFFFF)
    a0, a1 = alo & mask16, alo >> 16
    b0, b1 = blo & mask16, blo >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    mid = (p00 >> 16) + (p01 & mask16) + (p10 & mask16)
    lo = (mid << 16) | (p00 & mask16)
    hi = a1 * b1 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    hi = hi + alo * bhi + ahi * blo  # uint32 wrap == mod 2^32
    return hi, lo


def _add64_limbs(ahi, alo, bhi, blo):
    lo = alo + blo
    carry = (lo < alo).astype(jnp.uint32)
    return ahi + bhi + carry, lo


_PID_KERNEL_CACHE: dict = {}


def partition_id_hash(args, n_out: int):
    """Traceable body of the partition-id kernel: flattened
    ``(hi, lo, is_null)`` limb triples -> int32 partition ids.

    Per column: ``hv = (x * 0x9E3779B97F4A7C15) mod 2^64``,
    ``hv ^= hv >> 32`` (both limbs uint32: the xorshift is one limb
    xor), nulls replaced by the host's constant; columns combine as
    ``h = h * 31 + hv``; the result is ``h mod n_out`` with the 64-bit
    mod folded through ``2^32 mod n``.  Usable inside a larger jit (the
    whole-stage fused runner derives the shuffle pid column in the same
    trace as the agg kernels) or via the jitted wrapper below.
    """
    n_cols = len(args) // 3
    mul_hi = jnp.uint32(_HASH_MUL[0])
    mul_lo = jnp.uint32(_HASH_MUL[1])
    null_hi = jnp.uint32(_NULL_HASH[0])
    null_lo = jnp.uint32(_NULL_HASH[1])
    m = jnp.uint32(n_out)
    pow32_mod = jnp.uint32((1 << 32) % n_out)
    hhi = jnp.zeros_like(args[0])
    hlo = jnp.zeros_like(args[0])
    for c in range(n_cols):
        vhi, vlo, is_null = args[3 * c : 3 * c + 3]
        phi, plo = _mul64_limbs(vhi, vlo, mul_hi, mul_lo)
        plo = plo ^ phi  # hv ^= hv >> 32
        phi = jnp.where(is_null, null_hi, phi)
        plo = jnp.where(is_null, null_lo, plo)
        thi, tlo = _mul64_limbs(hhi, hlo, jnp.uint32(0), jnp.uint32(31))
        hhi, hlo = _add64_limbs(thi, tlo, phi, plo)
    return (((hhi % m) * pow32_mod + (hlo % m)) % m).astype(jnp.int32)


def make_partition_id_kernel(n_cols: int, n_out: int):
    """Jitted ``(hi, lo, is_null) x n_cols -> int32 partition ids``
    (see :func:`partition_id_hash` for the hash definition)."""
    key = (n_cols, n_out)
    cached = _PID_KERNEL_CACHE.get(key)
    if cached is not None:
        return cached

    def kernel(*args):
        return partition_id_hash(args, n_out)

    cached = jax.jit(kernel)
    _PID_KERNEL_CACHE[key] = cached
    return cached


def _pid_limbs(v: pa.Array) -> Optional[tuple]:
    """(hi, lo, is_null) uint32/bool limb arrays for one key column —
    the exact value prep of hash_partition_indices, or None when the
    column type has no device hash (strings hash FNV over bytes on
    host)."""
    import pyarrow.compute as pc

    t = v.type
    if not (
        pa.types.is_integer(t)
        or pa.types.is_floating(t)
        or pa.types.is_boolean(t)
        or pa.types.is_date(t)
        or pa.types.is_timestamp(t)
    ):
        return None
    is_null = (
        np.asarray(pc.is_null(v))
        if v.null_count
        else np.zeros(len(v), dtype=bool)
    )
    if pa.types.is_date32(t):
        v = v.cast(pa.int32())
    elif pa.types.is_date64(t) or pa.types.is_timestamp(t):
        v = v.cast(pa.int64())
    elif pa.types.is_boolean(t):
        v = v.cast(pa.int8())
    if v.null_count:
        v = v.fill_null(0)
    x = np.asarray(v)
    if x.dtype.kind == "f":
        x = (
            x.view(np.uint64)
            if x.dtype == np.float64
            else x.astype(np.float64).view(np.uint64)
        )
    else:
        x = x.astype(np.int64).view(np.uint64)
    hi = (x >> np.uint64(32)).astype(np.uint32)
    lo = (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo, is_null


def pid_limb_args(columns, pad_to: int) -> Optional[list]:
    """Flattened ``(hi, lo, is_null)`` limb arrays, padded to ``pad_to``,
    for a list of arrow key columns — or None when any column has no
    device hash.  Host prep for :func:`partition_id_hash` inside a
    larger trace (the whole-stage fused runner derives the shuffle pid
    lane in the same dispatch as the agg kernels)."""
    args: list = []
    for col in columns:
        limbs = _pid_limbs(col)
        if limbs is None:
            return None
        for a in limbs:
            args.append(_pad(a, pad_to))
    return args or None


def device_partition_ids(
    batch: pa.RecordBatch, exprs, n: int
) -> Optional[np.ndarray]:
    """Partition ids for ``batch`` through the jitted device hash, or
    None when a key isn't device-hashable (non-column expression, string
    key, n past PID_MAX_PARTITIONS) — the caller falls back to the host
    partitioner.  Rows pad to power-of-two buckets so distinct XLA
    shapes stay logarithmic in batch size."""
    if n <= 0 or n > PID_MAX_PARTITIONS or batch.num_rows == 0:
        return None
    flat = []
    for e in exprs:
        if not isinstance(e, pe.Col) or not (0 <= e.index < batch.num_columns):
            return None
        limbs = _pid_limbs(batch.column(e.index))
        if limbs is None:
            return None
        flat.append(limbs)
    if not flat:
        return None
    n_rows = batch.num_rows
    bucket = bucket_rows(n_rows, floor=256)
    args = []
    for hi, lo, is_null in flat:
        args.append(_pad(hi, bucket))
        args.append(_pad(lo, bucket))
        args.append(_pad(is_null, bucket))
    kernel = make_partition_id_kernel(len(flat), n)
    out = np.asarray(kernel(*args))[:n_rows]
    return out.astype(np.int64)
