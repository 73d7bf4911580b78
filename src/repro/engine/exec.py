"""Vectorized, jit-able execution of Query objects over Columnar batches.

Every operator is shape-stable (masked-row semantics), so a full query —
and, via core/physical.py, a *chain* of queries plus Python expectations —
compiles to a single XLA program.  The reference group-by uses a sort +
segment-scatter formulation (radix-style grouping adapted to TPU-friendly
dense ops: sort, cumsum, scatter-add are all well-supported lax
primitives).  When the route carries a group domain (shard statistics
bound every key, engine/route.py), the group-by instead reduces over a
static slot axis with masked reductions: no sort, no gather, no scatter,
and an output of one row per slot.  Joins compile
to a shape-stable first-match gather: the right side is sorted once
(valid rows first), probe keys binary-search into it, and misses either
invalidate the row (inner) or zero-fill the gathered columns (left) — no
data-dependent shapes anywhere, so joined queries still jit to one
program.

The Pallas kernel in kernels/fused_filter_agg IS wired in: when the
planner's eligibility pass (engine/route.py) stamps a ``RouteDecision``
with ``engine_path == "kernel"``, the scan→filter→agg pipeline of an
aggregation query executes as one fused kernel pass (filter evaluated
in-kernel for native column-vs-literal predicates, as a mask feed
otherwise) and the grouped output is re-assembled to match the jnp
path's layout byte-for-byte.  Queries without a route — or routed
``"jnp"`` because dtypes/statistics cannot prove kernel exactness — run
the pure-jnp operators below, which remain the reference semantics.
"""
from __future__ import annotations

import functools
from collections import Counter
from dataclasses import replace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.columnar import Columnar
from repro.engine.query import Agg, Query
from repro.engine.route import RouteDecision, native_filter_of
from repro.runtime.phases import named

def apply_filter(rel: Columnar, query: Query) -> Columnar:
    if query.filter_expr is None:
        return rel
    keep = query.filter_expr.evaluate(rel.columns)
    return rel.mask_where(keep.astype(bool))


def apply_projection(rel: Columnar, query: Query) -> Columnar:
    if not query.projections:
        return rel
    out = {alias: expr.evaluate(rel.columns) for alias, expr in query.projections}
    return Columnar(out, rel.valid)


# --------------------------------------------------------------------- joins
def _combined_relation(
    query: Query, rel: Columnar, joined: Optional[Dict[str, Columnar]]
) -> Tuple[Columnar, Optional[List[str]]]:
    """Gather all join sources onto the FROM relation.

    The combined relation carries every column twice-addressable: under its
    qualified name (``qualifier.col``) always, and under its plain name
    when exactly one source owns that name — so expressions written either
    way evaluate against the same dict with no rewriting.  Returns the
    combined relation plus the *display* column list (plain-if-unique,
    qualified otherwise, in source order) used to resolve ``SELECT *``.

    Single-table queries with no alias and no dotted references pass
    through untouched (display ``None``) — the common path pays nothing.
    """
    dotted = any("." in c for c in query.referenced_columns())
    if not query.joins and query.source_alias is None and not dotted:
        return rel, None

    sources: List[Tuple[str, Columnar]] = [(query.source_alias or query.source, rel)]
    for j in query.joins:
        if not joined or j.table not in joined:
            raise KeyError(
                f"join table {j.table!r} was not provided to execute_query; "
                f"have {sorted(joined or {})}"
            )
        sources.append((j.qualifier, joined[j.table]))

    owners: Counter = Counter()
    for _, srel in sources:
        owners.update(srel.columns.keys())

    q0, rel0 = sources[0]
    combined: Dict[str, jax.Array] = {}
    display: List[str] = []
    for n, a in rel0.columns.items():
        combined[f"{q0}.{n}"] = a
        if owners[n] == 1:
            combined[n] = a
        display.append(n if owners[n] == 1 else f"{q0}.{n}")
    valid = rel0.valid

    for j, (jq, jrel) in zip(query.joins, sources[1:]):
        gathered, found = _first_match_gather(
            j, jq, combined, valid, jrel, sql=query.raw_sql
        )
        for n, g in gathered.items():
            combined[f"{jq}.{n}"] = g
            if owners[n] == 1:
                combined[n] = g
            display.append(n if owners[n] == 1 else f"{jq}.{n}")
        if j.how == "inner":
            valid = found
        # left join: validity unchanged, misses were zero-filled

    return Columnar(combined, valid), display


def _first_match_gather(join, jq, combined, valid, jrel, *, sql=None):
    """Probe the accumulated left side into one joined relation.

    Right side is sorted by key with invalid rows pushed to the tail
    (double stable argsort), probe keys ``searchsorted`` into it, and
    duplicate right keys resolve deterministically to the first matching
    row in storage order.  Returns (gathered right columns, found mask);
    misses are zero-filled so even non-compact outputs are deterministic.
    """
    def _orient(lref, rref):
        rtail = rref.split(".")[-1]
        rq = rref.split(".")[0] if "." in rref else None
        if rq is not None and rq != jq:
            return None
        if lref in combined and rtail in jrel.columns:
            return combined[lref], jrel.columns[rtail]
        return None

    pair = _orient(join.left_on, join.right_on) or _orient(join.right_on, join.left_on)
    if pair is None:
        raise KeyError(
            f"cannot resolve JOIN {join.table} ON {join.left_on} = "
            f"{join.right_on}: left side has {sorted(combined)}, "
            f"{join.qualifier!r} has {sorted(jrel.columns)}"
        )
    left_keys, right_keys = pair
    for side, arr in (("left", left_keys), ("right", right_keys)):
        if arr.dtype.kind not in ("i", "u", "b"):
            raise TypeError(
                f"join key on the {side} side of {join.left_on} = "
                f"{join.right_on} must be integer/bool, got {arr.dtype} "
                "(fix: cast the join key to int32 upstream — T401 flags "
                "this statically)"
            )

    cap_r = jrel.capacity
    if cap_r == 0:  # statically-empty right side: nothing ever matches
        found = jnp.zeros(valid.shape, bool)
        gathered = {
            n: jnp.zeros(valid.shape, a.dtype) for n, a in jrel.columns.items()
        }
        return gathered, found

    rk32 = right_keys.astype(jnp.int32)
    perm = jnp.argsort(rk32, stable=True)
    perm = perm[jnp.argsort((~jrel.valid[perm]).astype(jnp.int32), stable=True)]
    sorted_valid = jrel.valid[perm]
    # invalid tail carries the max sentinel; a *valid* key equal to the
    # sentinel still wins because searchsorted("left") lands on it first
    sorted_keys = jnp.where(sorted_valid, rk32[perm], jnp.iinfo(jnp.int32).max)

    lk32 = left_keys.astype(jnp.int32)
    idx = jnp.minimum(jnp.searchsorted(sorted_keys, lk32, side="left"), cap_r - 1)
    found = (sorted_keys[idx] == lk32) & sorted_valid[idx] & valid
    src = perm[idx]
    gathered = {
        n: jnp.where(found, a[src], jnp.zeros((), a.dtype))
        for n, a in jrel.columns.items()
    }
    return gathered, found


def _normalize_group_keys(rel: Columnar, query: Query) -> Tuple[Columnar, Query]:
    """Materialize qualified group keys under their output names.

    ``GROUP BY t.loc`` groups out as column ``loc`` (see
    Query.group_key_output_names); the plain single-table path is
    untouched."""
    out_names = query.group_key_output_names()
    if list(query.group_keys) == out_names:
        return rel, query
    new_cols = {
        out: rel.column(k)
        for k, out in zip(query.group_keys, out_names)
        if out != k
    }
    return rel.with_columns(new_cols), replace(query, group_keys=tuple(out_names))


def _lex_sort_perm(rel: Columnar, keys) -> jax.Array:
    """Permutation grouping equal key tuples, valid rows first.

    Lexicographic order via repeated *stable* argsort from least- to
    most-significant key; validity is the most significant key.  Avoids
    packing keys into one word (no x64 requirement, no range limits).
    """
    perm = jnp.arange(rel.capacity)
    for k in reversed(keys):
        kcol = rel.column(k)
        if kcol.dtype.kind not in ("i", "u", "b"):
            raise TypeError(f"group key {k!r} must be integer/bool, got {kcol.dtype}")
        order = jnp.argsort(kcol[perm].astype(jnp.int32), stable=True)
        perm = perm[order]
    order = jnp.argsort((~rel.valid[perm]).astype(jnp.int32), stable=True)
    return perm[order]


def apply_groupby(rel: Columnar, query: Query, *, capacity: Optional[int] = None) -> Columnar:
    """Sort-based grouping with static output capacity.

    Output relation has ``capacity`` rows (default: input capacity); rows
    beyond the number of distinct groups are invalid.  All ops are
    shape-stable → fully jit/fusion compatible.
    """
    cap = capacity or rel.capacity
    order = _lex_sort_perm(rel, query.group_keys)
    sorted_valid = rel.valid[order]
    if query.group_keys:
        diff = jnp.zeros((rel.capacity,), bool)
        for k in query.group_keys:
            kcol = rel.column(k)[order]
            diff = diff | jnp.concatenate(
                [jnp.ones((1,), bool), kcol[1:] != kcol[:-1]]
            )
        is_new = diff & sorted_valid
    else:
        # global aggregation: one group, opened by the first (valid) row
        is_new = sorted_valid & (jnp.arange(rel.capacity) == 0)
    seg_id = jnp.cumsum(is_new.astype(jnp.int32)) - 1  # -1 for invalid prefix
    seg_id = jnp.where(sorted_valid, seg_id, cap)  # route invalid to overflow slot
    seg_id = jnp.minimum(seg_id, cap)  # overflow slot is dropped

    out_cols: Dict[str, jax.Array] = {}
    # representative group-key columns
    for k in query.group_keys:
        src = rel.column(k)[order]
        out = jnp.zeros((cap + 1,), dtype=src.dtype).at[seg_id].set(src)
        out_cols[k] = out[:cap]

    counts = jnp.zeros((cap + 1,), jnp.int32).at[seg_id].add(
        sorted_valid.astype(jnp.int32)
    )[:cap]

    for agg in query.aggregates:
        out_cols[agg.name] = _apply_one_agg(rel, agg, order, seg_id, sorted_valid, counts, cap)

    group_valid = counts > 0
    return Columnar(out_cols, group_valid)


def _apply_one_agg(rel, agg: Agg, order, seg_id, sorted_valid, counts, cap):
    if agg.fn == "count":
        return counts
    vals = agg.expr.evaluate(rel.columns)[order]
    if agg.fn in ("sum", "mean"):
        # f32 accum for floats, i32 for ints (x64 is disabled jax-wide)
        acc_dtype = vals.dtype if vals.dtype.kind == "f" else jnp.int32
        zeroed = jnp.where(sorted_valid, vals.astype(acc_dtype), 0)
        total = jnp.zeros((cap + 1,), acc_dtype).at[seg_id].add(zeroed)[:cap]
        if agg.fn == "sum":
            return total
        return total.astype(jnp.float32) / jnp.maximum(counts, 1).astype(jnp.float32)
    if agg.fn == "min":
        big = _extreme(vals.dtype, +1)
        masked = jnp.where(sorted_valid, vals, big)
        return jnp.full((cap + 1,), big, vals.dtype).at[seg_id].min(masked)[:cap]
    if agg.fn == "max":
        small = _extreme(vals.dtype, -1)
        masked = jnp.where(sorted_valid, vals, small)
        return jnp.full((cap + 1,), small, vals.dtype).at[seg_id].max(masked)[:cap]
    raise ValueError(f"unsupported aggregate {agg.fn!r}")


def _extreme(dtype, sign: int):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(sign * jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if sign > 0 else info.min, dtype)


# --------------------------------------------------------------- kernel path
def _kernel_filter_agg(rel: Columnar, query: Query, route: RouteDecision) -> Columnar:
    """Filter + group + aggregate through kernels/fused_filter_agg.

    One kernel pass per distinct value column (counts ride along free);
    the grouped output is re-assembled into the jnp path's layout —
    present groups first in ascending key order, absent slots zeroed —
    so compacted results are byte-identical to apply_groupby's whenever
    the route's exactness guards hold (integer sums below 2**24).
    """
    from repro.kernels.fused_filter_agg import fused_filter_agg

    key_name = query.group_keys[0]
    out_key = query.group_key_output_names()[0]
    key_col = rel.column(key_name)
    # validity folds into the key stream: invalid rows carry key -1,
    # which matches no group lane inside the kernel
    keys_slot = jnp.where(
        rel.valid, key_col.astype(jnp.int32) - route.key_offset, jnp.int32(-1)
    )
    G = route.num_groups

    native = native_filter_of(query.filter_expr) if route.native_filter else None
    if native is not None:
        fcol, op, thr = native
        filt = rel.column(fcol).astype(jnp.float32)
    elif query.filter_expr is not None:
        # non-native predicate: evaluate to a mask and feed it as the
        # filter column — still one fused XLA program end to end
        filt = query.filter_expr.evaluate(rel.columns).astype(jnp.float32)
        op, thr = "ge", 0.5
    else:
        filt, op, thr = jnp.ones((rel.capacity,), jnp.float32), "ge", 0.0

    value_cols: Dict[str, jax.Array] = {}
    for agg in query.aggregates:
        if agg.fn != "count":
            value_cols.setdefault(agg.expr.args[0], rel.column(agg.expr.args[0]))

    sums_by_col: Dict[str, jax.Array] = {}
    counts_f = None
    if not value_cols:  # COUNT(*)-only (or bare GROUP BY): one zero-value pass
        _, counts_f = fused_filter_agg(
            keys_slot, jnp.zeros((rel.capacity,), jnp.float32), filt,
            op=op, threshold=thr, num_groups=G,
        )
    for cname, vals in value_cols.items():
        sums_f, counts_f = fused_filter_agg(
            keys_slot, vals, filt,
            op=op, threshold=thr, num_groups=G,
        )
        sums_by_col[cname] = sums_f

    counts = counts_f.astype(jnp.int32)
    # slot index == key - offset, so ascending slot == ascending key
    out_cols: Dict[str, jax.Array] = {
        out_key: (jnp.arange(G, dtype=jnp.int32) + route.key_offset).astype(
            key_col.dtype
        )
    }
    for agg in query.aggregates:
        if agg.fn == "count":
            out_cols[agg.name] = counts
            continue
        sums = sums_by_col[agg.expr.args[0]]
        if agg.fn == "sum":
            vdtype = rel.column(agg.expr.args[0]).dtype
            out_cols[agg.name] = sums.astype(
                vdtype if vdtype.kind == "f" else jnp.int32
            )
        else:  # mean
            out_cols[agg.name] = sums / jnp.maximum(counts, 1).astype(jnp.float32)
    return _present_first(counts > 0, out_cols)


def _present_first(present: jax.Array, slot_cols: Dict[str, jax.Array]) -> Columnar:
    """Slot-indexed group outputs in apply_groupby's layout: present
    groups first, in ascending slot order, absent slots zeroed — so the
    compacted result equals the sort path's."""
    order = jnp.argsort((~present).astype(jnp.int32), stable=True)
    present_s = present[order]
    out = {
        name: jnp.where(present_s, c[order], jnp.zeros((), c.dtype))
        for name, c in slot_cols.items()
    }
    return Columnar(out, present_s)


# ---------------------------------------------------------- dense group-by
def _dense_groupby(rel: Columnar, query: Query, route: RouteDecision) -> Columnar:
    """Group over the static slot axis of ``route.group_domain`` (one
    ``(offset, size)`` per key, ``route.dense_groups`` slots) with masked
    reductions.

    A row's slot is ``sum((key_i - offset_i) * stride_i)``, the first key
    most significant, so ascending slot is ascending lexicographic key
    order.  Each aggregate is one reduction over a ``[G, N]`` compare and
    select that XLA fuses into the reduce: nothing of that shape is
    written out, and nothing is sorted, gathered or scattered.  Output
    capacity is G, in the sort path's layout (:func:`_present_first`);
    float sums differ from it only in the order of addition.
    """
    domain, G = route.group_domain, route.dense_groups
    sizes = [size for _, size in domain]
    slot = jnp.zeros((rel.capacity,), jnp.int32)
    live = rel.valid
    for k, (offset, size) in zip(query.group_keys, domain):
        kcol = rel.column(k)
        if kcol.dtype.kind not in ("i", "u", "b"):
            raise TypeError(f"group key {k!r} must be integer/bool, got {kcol.dtype}")
        digit = kcol.astype(jnp.int32) - offset
        # keys outside the statistics' bounds belong to no slot
        live = live & (digit >= 0) & (digit < size)
        slot = slot * size + digit
    hit = jnp.where(live, slot, -1)[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]

    counts = jnp.sum(hit, axis=1, dtype=jnp.int32)
    # each key's value per slot: the slot's digit in the mixed radix
    digits = np.unravel_index(np.arange(G), sizes) if sizes else ()
    out_cols: Dict[str, jax.Array] = {
        k: jnp.asarray(d + offset, jnp.int32).astype(rel.column(k).dtype)
        for k, d, (offset, _) in zip(query.group_keys, digits, domain)
    }
    for agg in query.aggregates:
        out_cols[agg.name] = _dense_agg(rel, agg, hit, counts)
    return _present_first(counts > 0, out_cols)


def _dense_agg(rel: Columnar, agg: Agg, hit: jax.Array, counts: jax.Array) -> jax.Array:
    """One aggregate per slot; dtypes and fills as :func:`_apply_one_agg`."""
    if agg.fn == "count":
        return counts
    vals = agg.expr.evaluate(rel.columns)[None, :]
    if agg.fn in ("sum", "mean"):
        acc_dtype = vals.dtype if vals.dtype.kind == "f" else jnp.int32
        total = jnp.sum(jnp.where(hit, vals.astype(acc_dtype), 0), axis=1)
        if agg.fn == "sum":
            return total
        return total.astype(jnp.float32) / jnp.maximum(counts, 1).astype(jnp.float32)
    if agg.fn == "min":
        return jnp.min(jnp.where(hit, vals, _extreme(vals.dtype, +1)), axis=1)
    if agg.fn == "max":
        return jnp.max(jnp.where(hit, vals, _extreme(vals.dtype, -1)), axis=1)
    raise ValueError(f"unsupported aggregate {agg.fn!r}")


def apply_sort(rel: Columnar, query: Query) -> Columnar:
    if not query.order_by:
        return rel
    # stable multi-key sort: apply keys in reverse significance order,
    # then one final stable pass pushing invalid rows to the end
    perm = jnp.arange(rel.capacity)
    for column, desc in reversed(query.order_by):
        # after aggregation a qualified group key surfaces under its
        # unqualified tail (group_key_output_names) — resolve the same way
        if column not in rel.columns and "." in column:
            tail = column.split(".")[-1]
            if tail in rel.columns:
                column = tail
        vals = rel.column(column)[perm]
        if vals.dtype.kind == "b":
            vals = vals.astype(jnp.int32)
        order = jnp.argsort(-vals if desc else vals, stable=True)
        perm = perm[order]
    order = jnp.argsort((~rel.valid[perm]).astype(jnp.int32), stable=True)
    perm = perm[order]
    return Columnar({k: v[perm] for k, v in rel.columns.items()}, rel.valid[perm])


def apply_limit(rel: Columnar, query: Query) -> Columnar:
    if query.limit is None or query.limit >= rel.capacity:
        return rel
    n = query.limit
    return Columnar({k: v[:n] for k, v in rel.columns.items()}, rel.valid[:n])


def execute_query(
    query: Query,
    rel: Columnar,
    *,
    group_capacity: Optional[int] = None,
    joined: Optional[Dict[str, Columnar]] = None,
    route: Optional[RouteDecision] = None,
) -> Columnar:
    """Interpret a Query over a Columnar. Pure function of its inputs.

    ``joined`` maps each JOIN table name to its relation; ``route`` is an
    optional engine/route.py decision — ``"kernel"`` sends the
    filter+group+agg pipeline through the fused Pallas kernel, a jnp
    route with a ``group_domain`` groups without a sort, anything else
    (including no route at all) runs the reference jnp operators.
    """
    rel, display = _combined_relation(query, rel, joined)
    if route is not None and route.engine_path == "kernel" and query.is_aggregation:
        rel = _kernel_filter_agg(rel, query, route)
        if query.projections:
            rel = apply_projection(rel, query)
    else:
        rel = apply_filter(rel, query)
        if query.is_aggregation:
            grel, gquery = _normalize_group_keys(rel, query)
            if route is not None and route.group_domain is not None:
                rel = _dense_groupby(grel, gquery, route)
            else:
                rel = apply_groupby(grel, gquery, capacity=group_capacity)
            if query.projections:
                rel = apply_projection(rel, query)
        else:
            if query.projections:
                rel = apply_projection(rel, query)
            elif display is not None:
                rel = rel.select(display)  # SELECT * over joined sources
    rel = apply_sort(rel, query)
    rel = apply_limit(rel, query)
    return rel


def group_path(query: Query, route: Optional[RouteDecision]) -> str:
    """Which group-by :func:`execute_query` runs for ``query`` under
    ``route``: ``"kernel"``, ``"dense"``, ``"sort"``, or ``""`` for a
    statement with no aggregation."""
    if not query.is_aggregation:
        return ""
    if route is not None and route.engine_path == "kernel":
        return "kernel"
    if route is not None and route.group_domain is not None:
        return "dense"
    return "sort"


def program_name(query: Query) -> str:
    """The name of a query's compiled program (``jit_<name>`` in a device
    trace): its tables, group keys, aggregate functions and filtered
    columns.  Literals are left out, so statements that differ only in
    them share one name."""
    parts = [query.source, *(j.table for j in query.joins)]
    if query.group_keys:
        parts += ["by", *query.group_keys]
    parts += [agg.fn for agg in query.aggregates]
    if query.filter_expr is not None:
        parts += ["where", *sorted(set(query.filter_expr.referenced_columns()))]
    return "_".join(parts)


@functools.lru_cache(maxsize=512)
def _compiled_for(
    query: Query, group_capacity: Optional[int], route: Optional[RouteDecision]
) -> Callable:
    def run(
        rel: Columnar, joined: Optional[Dict[str, Columnar]] = None
    ) -> Columnar:
        return execute_query(
            query, rel, group_capacity=group_capacity, joined=joined, route=route
        )

    return jax.jit(named(run, program_name(query)))


def compile_query(
    query: Query,
    *,
    group_capacity: Optional[int] = None,
    route: Optional[RouteDecision] = None,
) -> Callable[..., Columnar]:
    """Return the jit-compiled executable for a query (cached — this cache
    is the engine-level face of the runtime's warm-container cache).

    The executable takes ``(rel, joined=None)``; being a ``jax.jit``
    function, its ``lower(...)`` gives the program it runs."""
    return _compiled_for(query, group_capacity, route)
