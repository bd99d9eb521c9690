"""Analyzer: resolution and normalization rewrites.

The (much slimmer) analog of ``catalyst/analysis/Analyzer.scala``.  Columns
bind by name directly against child schemas, so "resolution" is validation
plus these structural rewrites:

* ``ResolveAggregates``: `groupBy().agg(expr)` accepts arbitrary expressions
  mixing aggregate functions and scalars (``sum(x) + 1``); they are split
  into a Project over a pure Aggregate (Spark plans this shape inside
  ``HashAggregateExec`` result expressions).
* ``RewriteDistinctAggregates``: single-column distinct aggregates expand to
  a two-level aggregation (restriction of
  ``optimizer/RewriteDistinctAggregates.scala``).
* ``ResolveRelations``: table names → catalog plans.
* eager schema validation for early, readable AnalysisException errors.
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, List, Sequence, Tuple

from .. import types as T
from ..aggregates import AggregateFunction, Count, CountDistinct, CountStar, Sum
from ..expressions import (
    Alias, And, AnalysisException, Col, EQ, Expression, Literal,
)
from .logical import Aggregate, Distinct, Filter, Join, Limit, LogicalPlan, Project, Sample, Sort, SortOrder, SubqueryAlias, UnresolvedRelation

def fresh_name(prefix: str, basis: str, index: int) -> str:
    """DETERMINISTIC generated names: derived from the expression text and
    slot position, never a global counter — identical queries must produce
    byte-identical plans so the executor's jit cache can hit."""
    return f"__{prefix}_{index}_{basis}"


def split_aggregate_expr(e: Expression, slots: List[Tuple[AggregateFunction, str]],
                         ) -> Expression:
    """Replace AggregateFunction subtrees with Col refs to buffer slots;
    returns the residual scalar expression."""
    if isinstance(e, AggregateFunction):
        for f, n in slots:
            if f is e:
                return Col(n)
        name = fresh_name("agg", repr(e), len(slots))
        slots.append((e, name))
        return Col(name)
    from .window import WindowExpression, WindowSpec
    if isinstance(e, WindowExpression):
        # windows over aggregates (SUM(SUM(x)) OVER ...): the window
        # function's ARGUMENTS slot-ify like any other post-agg expression;
        # the window itself computes over the aggregated rows.  PARTITION/
        # ORDER must reference grouping keys (plain columns survive; key
        # EXPRESSIONS in a window spec are not substituted yet).
        f2 = e.func.map_children(lambda c: split_aggregate_expr(c, slots))
        p2 = [split_aggregate_expr(p, slots) for p in e.spec.partition_by]
        o2 = [type(o)(split_aggregate_expr(o.child, slots), o.ascending,
                      o.nulls_first) for o in e.spec.order_by]
        return WindowExpression(
            f2, WindowSpec(p2, o2, e.spec.frame, e.spec.frame_type))
    return e.map_children(lambda c: split_aggregate_expr(c, slots))


def substitute_grouping_keys(e: Expression,
                             keys: Sequence[Expression]) -> Expression:
    """Occurrences of a grouping EXPRESSION above the Aggregate become
    references to its output column: `GROUP BY substr(c,1,5)` with
    `SELECT substr(c,1,5)` must read the key column — the input column no
    longer exists above the Aggregate.  Matching is structural via repr
    (expression reprs are canonical)."""
    for k in keys:
        if not isinstance(k, Col) and repr(e) == repr(k):
            return Col(k.name)
    return e.map_children(lambda c: substitute_grouping_keys(c, keys))


def contains_aggregate(e: Expression) -> bool:
    if isinstance(e, AggregateFunction):
        return True
    return any(contains_aggregate(c) for c in e.children)


def build_aggregate(keys: Sequence[Expression], agg_exprs: Sequence[Expression],
                    child: LogicalPlan) -> LogicalPlan:
    """Construct Aggregate (+ wrapping Project if needed) from user exprs.

    Grouping keys are also available in output; each agg output expression
    may reference keys and aggregate functions arbitrarily.
    """
    slots: List[Tuple[AggregateFunction, str]] = []
    out_exprs: List[Expression] = []
    key_out: List[Expression] = []
    key_names = []
    for k in keys:
        key_out.append(Col(k.name))
        key_names.append(k.name)

    needs_project = False
    for e in agg_exprs:
        name = e.name
        residual = split_aggregate_expr(e, slots)
        residual = substitute_grouping_keys(residual, keys)
        if isinstance(residual, Col) and not isinstance(e, Alias) \
                and residual.name not in key_names:
            # plain aggregate: rename slot to the pretty name
            for i, (f, n) in enumerate(slots):
                if n == residual.name:
                    slots[i] = (f, name)
                    residual = Col(name)
                    break
        out_exprs.append(Alias(residual, name) if not (
            isinstance(residual, Col) and residual.name == name) else residual)
        if not (isinstance(residual, Col)):
            needs_project = True

    agg = Aggregate(list(keys), slots, child)
    if needs_project or any(isinstance(e, Alias) for e in out_exprs):
        return Project(key_out + out_exprs, agg)
    return agg


def rewrite_distinct_aggregates(plan: Aggregate) -> LogicalPlan:
    """Expand single distinct-column aggregates into two-level aggregation."""
    distinct_slots = [(f, n) for f, n in plan.aggs
                      if getattr(f, "is_distinct", False)]
    if not distinct_slots:
        return plan
    regular = [(f, n) for f, n in plan.aggs
               if not getattr(f, "is_distinct", False)]
    from ..aggregates import Max, Min
    mergeable = (Sum, Count, CountStar, Min, Max)
    for f, _n in regular:
        if not isinstance(f, mergeable):
            raise AnalysisException(
                f"mixing DISTINCT aggregates with {f!r} is not supported: "
                "only sum/count/min/max merge through the two-level "
                "expansion (rewrite avg as sum/count)")
    inputs = {repr(f.children[0]) for f, _ in distinct_slots}
    if len(inputs) > 1:
        raise AnalysisException(
            "multiple different DISTINCT columns in one aggregate are not "
            "yet supported")
    dcol = distinct_slots[0][0].children[0]
    dname = fresh_name("distinct", repr(dcol), 0)
    # level 1: group by keys + distinct column (dedup); regular aggregates
    # evaluate per fine group and MERGE at level 2 (sum-of-sums,
    # min-of-mins — `RewriteDistinctAggregates.scala` without the Expand)
    inner_keys = list(plan.keys) + [Alias(dcol, dname)]
    inner = Aggregate(inner_keys, list(regular), plan.child)
    # level 2: group by keys, aggregate the deduped column
    outer_slots = []
    for f, n in distinct_slots:
        base = Count if isinstance(f, CountDistinct) else Sum
        outer_slots.append((base(Col(dname)), n))
    for f, n in regular:
        merge = Sum if isinstance(f, (Sum, Count, CountStar)) \
            else (Min if isinstance(f, Min) else Max)
        outer_slots.append((merge(Col(n)), n))
    outer_keys = [Col(k.name) for k in plan.keys]
    return Aggregate(outer_keys, outer_slots, inner)


def _rewrite_distinct(node: LogicalPlan) -> LogicalPlan:
    return rewrite_distinct_aggregates(node) \
        if isinstance(node, Aggregate) else node


def _set_substitution(node, s_idx, aggregate=None):
    """The rewrite of one grouping set's expressions: grouping() and
    grouping_id() as the set's literals, an absent key as a typed NULL.  An
    aggregate goes to ``aggregate``; where that is None it stays as it is,
    since its ARGUMENTS see the child's rows (the reference's Expand nulls
    the key copies, never the aggregate inputs: SUM(k) over ROLLUP(k)
    totals k), and a present key is left to ``build_aggregate``.  With
    ``aggregate`` a present key becomes its output column."""
    from ..expressions import GroupingCall
    child_schema = node.children[0].schema()
    key_reprs = [repr(k) for k in node.keys]
    present = set(s_idx)
    # grouping_id bitmask: bit i set when key i is AGGREGATED away
    gid = 0
    for i in range(len(node.keys)):
        if i not in present:
            gid |= 1 << (len(node.keys) - 1 - i)

    def subst(e: Expression) -> Expression:
        if isinstance(e, GroupingCall):
            if not e.children:
                return Literal(gid)
            r = repr(e.children[0])
            if r not in key_reprs:
                raise AnalysisException(
                    f"grouping() argument {e.children[0]!r} is not "
                    "a grouping key")
            return Literal(0 if key_reprs.index(r) in present else 1)
        if isinstance(e, AggregateFunction):
            return e if aggregate is None else aggregate(e)
        r = repr(e)
        if r in key_reprs:
            i = key_reprs.index(r)
            if i not in present:
                return Literal(None, node.keys[i].data_type(child_schema))
            if aggregate is not None:
                return Col(node.keys[i].name)
        return e.map_children(subst)

    return subst


def _select_of_set(node, subst) -> List[Expression]:
    """The select list of one grouping set, each item under its own name."""
    sel = []
    for e in node.select_list:
        if isinstance(e, Alias):
            sel.append(Alias(subst(e.children[0]), e.name))
        else:
            ne = subst(e)
            sel.append(ne if ne.name == e.name else Alias(ne, e.name))
    return sel


def _grouping_sets_from_finest(node, ordinal: int):
    """Grouping sets whose aggregates all decompose (SUM, COUNT, MIN, MAX,
    AVG as a sum and a count; none DISTINCT) as ONE aggregation of the
    child by the finest set, each coarser set re-aggregating the buffers of
    the smallest listed set that contains it (SUM of sums, SUM of counts,
    MIN of mins, MAX of maxes).  Where no listed set holds every key, an
    aggregation by all of them is the base.  Each aggregation is a
    ``Shared`` node: the lanes compute it once a statement, however many
    arms read it.  None where the rewrite does not apply."""
    from ..aggregates import Avg, Max, Min
    from ..expressions import Coalesce, Div
    from .logical import Filter as LFilter, Shared, Union as LUnion
    from .window import contains_window
    child = node.children[0]
    cs = child.schema()
    exprs = list(node.select_list) + (
        [node.having] if node.having is not None else [])
    names = [k.name for k in node.keys]
    if any(contains_window(e) for e in exprs) or len(set(names)) < len(names):
        return None
    found: Dict[str, AggregateFunction] = {}

    def collect(e: Expression) -> None:
        if isinstance(e, AggregateFunction):
            found.setdefault(repr(e), e)
        for c in () if isinstance(e, AggregateFunction) else e.children:
            collect(c)

    for e in exprs:
        collect(e)
    merge = {Sum: Sum, Count: Sum, CountStar: Sum, Min: Min, Max: Max}
    for f in found.values():
        if type(f) not in merge and not (
                type(f) is Avg and not isinstance(
                    f.children[0].data_type(cs), T.DecimalType)):
            return None
    bufs: Dict[str, Tuple[AggregateFunction, str]] = {}

    def buffer(fn: AggregateFunction) -> Col:
        if repr(fn) not in bufs:
            bufs[repr(fn)] = (fn, fresh_name("gs", repr(fn), len(bufs)))
        return Col(bufs[repr(fn)][1])

    residual: Dict[str, Expression] = {}
    for r, f in found.items():
        if type(f) is Avg:
            x = f.children[0]
            residual[r] = Div(buffer(Sum(x)), buffer(Count(x)))
        elif type(f) in (Count, CountStar):
            # a keyless set over no rows sums no counts: COUNT is 0 there
            residual[r] = Coalesce(buffer(f), Literal(0, T.int64))
        else:
            residual[r] = buffer(f)
    merges = [(merge[type(fn)](Col(n)), n) for fn, n in bufs.values()]
    sets = [frozenset(s) for s in node.sets]
    every = frozenset(i for s in sets for i in s)

    def from_child(arm=None):
        keys = [node.keys[i] for i in sorted(every)]
        return Shared(Aggregate(keys, list(bufs.values()), child),
                      f"gs{ordinal}.{'base' if arm is None else arm[0]}",
                      arm)

    made: Dict[int, LogicalPlan] = {}
    base = None if every in sets else from_child()
    for i in sorted(range(len(sets)), key=lambda i: (-len(sets[i]), i)):
        arm_keys = tuple(names[k] for k in node.sets[i])
        if base is None:
            made[i] = base = from_child((i, arm_keys, False))
            continue
        finer = [j for j in made if sets[i] < sets[j]]
        src = made[min(finer, key=lambda j: (len(sets[j]), j))] \
            if finer else base
        keys = [Col(names[k]) for k in sorted(sets[i])]
        made[i] = Shared(Aggregate(keys, merges, src), f"gs{ordinal}.{i}",
                         (i, arm_keys, True))
    outs = []
    for i, s_idx in enumerate(node.sets):
        subst = _set_substitution(node, s_idx, lambda f: residual[repr(f)])
        sel = _select_of_set(node, subst)
        src = made[i]
        if node.having is not None:
            # HAVING reads the keys, the aggregates and the select list's
            # names: a name resolves to its item's expression
            visible = set(src.schema().names)
            defined = {e.name: e.children[0] for e in sel
                       if isinstance(e, Alias)}

            def item(e, visible=visible, defined=defined):
                if isinstance(e, Col) and e.name not in visible \
                        and e.name in defined:
                    return defined[e.name]
                return e.map_children(item)
            src = LFilter(item(subst(node.having)), src)
        outs.append(Project(sel, src))
    return outs[0] if len(outs) == 1 else LUnion(outs)


class _JoinSideRename(Project):
    """Marker Project inserted by join disambiguation: renames overlapping
    columns to their qualified names while passing other qualifiers through."""


def qualifier_map(plan: LogicalPlan) -> Dict[str, str]:
    """``alias.column`` → ``column`` visible from a plan subtree.

    The slim analog of Catalyst attribute qualifiers: a SubqueryAlias
    qualifies its output; schema-preserving nodes pass qualifiers through;
    Join unions both sides; Project/Aggregate reset the scope.
    """
    if isinstance(plan, _JoinSideRename):
        inner = qualifier_map(plan.children[0])
        visible = set(plan.schema().names)
        return {q: n for q, n in inner.items() if n in visible}
    if isinstance(plan, SubqueryAlias):
        return {f"{plan.alias}.{n}": n for n in plan.schema().names}
    if isinstance(plan, (Filter, Sort, Limit, Distinct, Sample)):
        return qualifier_map(plan.children[0])
    if isinstance(plan, Join):
        left = qualifier_map(plan.children[0])
        right = qualifier_map(plan.children[1])
        merged = dict(left)
        merged.update(right)
        return merged
    return {}


class Analyzer:
    def __init__(self, catalog=None):
        self.catalog = catalog

    def analyze(self, plan: LogicalPlan) -> LogicalPlan:
        plan = self._resolve_relations(plan)
        plan = plan.transform_up(self._resolve_functions)
        from .subquery import rewrite_subqueries

        def resolve_sub(p: LogicalPlan) -> LogicalPlan:
            # nested subquery plans need relation AND function resolution
            # (they are invisible to the outer transform_up passes)
            p = self._resolve_relations(p)
            return p.transform_up(self._resolve_functions)

        plan = rewrite_subqueries(plan, resolve_sub)
        plan = plan.transform_up(self._disambiguate_joins)
        plan = plan.transform_up(self._expand_stars)
        plan = plan.transform_up(self._resolve_qualified)
        # set-op replacement needs fully-resolved sides (stars expanded,
        # qualified refs bound) to build the all-column join condition
        plan = plan.transform_up(self._replace_set_ops)
        plan = plan.transform_up(self._rewrite_node)
        plan = plan.transform_up(self._rewrite_explode)
        plan = plan.transform_up(functools.partial(
            self._rewrite_grouping_sets, ordinal=itertools.count()))
        plan = plan.transform_up(self._rewrite_sliding_window)
        self._validate(plan)
        return plan

    @staticmethod
    def _rewrite_sliding_window(node: LogicalPlan) -> LogicalPlan:
        """Sliding window() grouping keys (slide < duration) expand each
        event into its duration/slide windows BELOW the aggregate (the
        reference's Expand in TimeWindowing): static expansion factor
        r = duration // slide, so shapes stay compile-time constant."""
        from ..expressions import (
            Add, Alias, Cast, Col, Literal, MakeArray, Sub, TimeWindow,
        )
        from .logical import Explode
        if not isinstance(node, Aggregate):
            return node

        def base(k):
            return k.children[0] if isinstance(k, Alias) else k

        sliding = [k for k in node.keys
                   if isinstance(base(k), TimeWindow)
                   and base(k).is_sliding]
        if not sliding:
            return node
        specs = {(base(k).duration_us, base(k).slide_us,
                  repr(base(k).children[0])) for k in sliding}
        if len(specs) > 1:
            raise AnalysisException(
                "one sliding window spec per aggregation is supported")
        tw = base(sliding[0])
        d, s_us = tw.duration_us, tw.slide_us
        r = d // s_us
        ts = tw.children[0]
        # i-th containing window start = floor(ts / slide) * slide - i*slide
        last = Cast(TimeWindow(ts, s_us), T.int64)
        starts = [Sub(last, Literal(i * s_us)) for i in range(r)]
        tmp = "__win_start"
        child = node.children[0]
        pre = [Col(n) for n in child.schema().names]
        expansion = Explode(pre, MakeArray(*starts), tmp, False, "pos",
                            child, insert_at=len(pre))
        new_keys = []
        for k in node.keys:
            b = base(k)
            if isinstance(b, TimeWindow) and b.is_sliding:
                if b.field == "start":
                    e = Cast(Col(tmp), T.timestamp)
                else:
                    e = Cast(Add(Col(tmp), Literal(d)), T.timestamp)
                new_keys.append(Alias(e, k.name))
            else:
                new_keys.append(k)
        return Aggregate(new_keys, node.aggs, expansion)

    @staticmethod
    def _rewrite_grouping_sets(node: LogicalPlan, ordinal) -> LogicalPlan:
        """GroupingSets → UNION ALL of one arm per grouping set: absent keys
        project as typed NULLs, grouping()/grouping_id() calls become
        per-arm literals (Expand-free ROLLUP/CUBE).  Where every aggregate
        decomposes, the arms share one aggregation of the child
        (``_grouping_sets_from_finest``); else each arm aggregates the
        child by itself.  ``ordinal`` counts the statement's rewrites."""
        from .logical import GroupingSets, Filter as LFilter, Union as LUnion
        if not isinstance(node, GroupingSets):
            return node
        shared = _grouping_sets_from_finest(node, next(ordinal))
        if shared is not None:
            return shared
        branches = []
        for s_idx in node.sets:
            subst = _set_substitution(node, s_idx)
            sel = _select_of_set(node, subst)
            keys_subset = [node.keys[i] for i in s_idx]
            # the analyzer's distinct rewrite ran before this rule
            branch = build_aggregate(keys_subset, sel, node.children[0]) \
                .transform_up(_rewrite_distinct)
            # the aggregate also outputs its keys; keep ONLY the select list
            want = [e.name for e in node.select_list]
            if branch.schema().names != want:
                branch = Project([Col(n) for n in want], branch)
            if node.having is not None:
                hv = subst(node.having)
                slots = []
                resid = split_aggregate_expr(hv, slots)
                if slots:
                    # HAVING with aggregates: re-aggregate per branch with
                    # extra slots, filter, then project the select list
                    sel_h = sel + [Alias(f, n) for f, n in slots]
                    b2 = build_aggregate(keys_subset, sel_h,
                                         node.children[0]) \
                        .transform_up(_rewrite_distinct)
                    branch = Project([Col(n) for n in want],
                                     LFilter(resid, b2))
                else:
                    branch = LFilter(hv, branch)
            branches.append(branch)
        out = branches[0] if len(branches) == 1 else LUnion(branches)
        return out

    @staticmethod
    def _rewrite_explode(node: LogicalPlan) -> LogicalPlan:
        """Project containing explode()/posexplode() → the Explode
        operator (shared by SQL text and the DataFrame API)."""
        from ..expressions import Alias, ExplodeMarker
        from .logical import Explode, Project
        if not isinstance(node, Project):
            return node

        def marker(e):
            base = e.children[0] if isinstance(e, Alias) else e
            return base if isinstance(base, ExplodeMarker) else None

        markers = [e for e in node.exprs if marker(e) is not None]
        if not markers:
            return node
        if len(markers) != 1:
            raise AnalysisException(
                "only one explode() per select is supported")
        m = markers[0]
        mk = marker(m)
        out_name = m.name if isinstance(m, Alias) else "col"
        pre = [e for e in node.exprs if marker(e) is None]
        insert_at = node.exprs.index(m)     # keep select-list position
        return Explode(pre, mk.children[0], out_name, mk.with_pos, "pos",
                       node.children[0], insert_at=insert_at)

    def _expand_stars(self, node: LogicalPlan) -> LogicalPlan:
        """Expand `*` / `tbl.*` left by the parser over unresolved relations
        (ResolveStar analog; runs after catalog resolution)."""
        from .parser import _Star
        if not isinstance(node, Project) \
                or not any(isinstance(e, _Star) for e in node.exprs):
            return node
        child = node.children[0]
        names = child.schema().names
        new: List[Expression] = []
        for e in node.exprs:
            if not isinstance(e, _Star):
                new.append(e)
            elif e.qualifier is None:
                new += [Col(n) for n in names]
            else:
                qmap = qualifier_map(child)
                pref = e.qualifier + "."
                # preserve child column order; a column belongs to the
                # qualifier if its (possibly join-renamed) name carries the
                # prefix literally, or a qualified alias maps to it
                qualified_plain = {v for k, v in qmap.items()
                                   if k.startswith(pref)}
                hits = [n for n in names
                        if n.startswith(pref) or n in qualified_plain]
                if not hits:
                    raise AnalysisException(
                        f"cannot resolve {e.qualifier}.* among ({', '.join(names)})")
                new += [Col(n) for n in hits]
        return Project(new, child)

    def _disambiguate_joins(self, node: LogicalPlan) -> LogicalPlan:
        """When both join sides expose a same-named column, rename each side's
        copy to its qualified name (``t.k`` / ``d.k``) so references bind
        unambiguously — the by-name analog of Catalyst exprId identity."""
        if not isinstance(node, Join) or node.using:
            return node
        try:
            ls = node.children[0].schema()
            rs = node.children[1].schema()
        except AnalysisException:
            return node
        overlap = set(ls.names) & set(rs.names)
        if not overlap:
            return node

        def rename(child, schema):
            rev: Dict[str, str] = {}
            for q, plain in qualifier_map(child).items():
                rev.setdefault(plain, q)
            exprs: List[Expression] = []
            changed = False
            for n in schema.names:
                if n in overlap and n in rev:
                    exprs.append(Alias(Col(n), rev[n]))
                    changed = True
                else:
                    exprs.append(Col(n))
            return _JoinSideRename(exprs, child) if changed else child

        left = rename(node.children[0], ls)
        right = rename(node.children[1], rs)
        if left is node.children[0] and right is node.children[1]:
            return node
        return Join(left, right, node.how, node.on, node.using)

    def _resolve_qualified(self, node: LogicalPlan) -> LogicalPlan:
        if not node.children or not node.expressions():
            return node
        qmap: Dict[str, str] = {}
        for c in node.children:
            try:
                qmap.update(qualifier_map(c))
            except AnalysisException:
                return node
        # plain names visible from children (qualified ref may also be the
        # literal column name, e.g. after a previous rewrite)
        try:
            plain = {n for c in node.children for n in c.schema().names}
            structs = {f.name: f.dataType
                       for c in node.children for f in c.schema().fields
                       if isinstance(f.dataType, T.StructType)}
        except AnalysisException:
            return node
        if not qmap and not structs:
            return node

        def rewrite(e: Expression) -> Expression:
            if isinstance(e, Col) and e.name not in plain:
                if e.name in qmap:
                    return Col(qmap[e.name])
                # s.field on a struct-typed column (qualifiers take
                # precedence — an alias named like a struct column shadows
                # its fields, same as the reference's resolution order)
                base, dot, fld = e.name.partition(".")
                if dot and base in structs and fld in structs[base].names:
                    from ..expressions import GetField
                    return GetField(Col(base), fld)
            if isinstance(e, AggregateFunction) or e.children:
                return e.map_children(rewrite)
            return e

        return node.map_expressions(rewrite)

    def _resolve_relations(self, plan: LogicalPlan, _depth: int = 0) -> LogicalPlan:
        if _depth > 32:
            raise AnalysisException("cyclic or too deeply nested view definitions")

        def fn(node: LogicalPlan) -> LogicalPlan:
            if isinstance(node, UnresolvedRelation):
                if self.catalog is None:
                    raise AnalysisException(f"table not found: {node.name}")
                # view bodies may themselves reference views: recurse
                resolved = self._resolve_relations(
                    self.catalog.lookup(node.name), _depth + 1)
                # a view's body is a query of its own: its ``*`` is expanded
                # here, so that an alias over the view has a schema to
                # qualify (a self-join of one view under two aliases, a
                # correlated reference that goes through such an alias)
                resolved = resolved.transform_up(self._disambiguate_joins) \
                    .transform_up(self._expand_stars)
                return SubqueryAlias(node.name, resolved)
            return node
        return plan.transform_up(fn)

    def _resolve_functions(self, node: LogicalPlan) -> LogicalPlan:
        """UnresolvedFunction -> registered UDF (FunctionRegistry lookup)."""
        from .udf import UnresolvedFunction
        if not node.expressions():
            return node

        from .window import WindowExpression

        def fe(e: Expression) -> Expression:
            if isinstance(e, WindowExpression):
                # the window function lives in .func, not .children
                return e.map_parts(fe)
            e = e.map_children(fe)
            if isinstance(e, UnresolvedFunction):
                wrapper = None
                if self.catalog is not None \
                        and hasattr(self.catalog, "lookup_function"):
                    wrapper = self.catalog.lookup_function(e.fn_name)
                if wrapper is None:
                    raise AnalysisException(
                        f"undefined function: {e.fn_name}")
                from .udf import PythonUDF
                return PythonUDF(e.fn_name, wrapper.fn, wrapper.returnType,
                                 list(e.children),
                                 getattr(wrapper, "_vectorized", False))
            return e

        return node.map_expressions(fe)

    def _replace_set_ops(self, node: LogicalPlan) -> LogicalPlan:
        """INTERSECT -> Distinct(semi join); EXCEPT -> Distinct(anti join)
        (`ReplaceIntersectWithSemiJoin` / `ReplaceExceptWithAntiJoin`).
        The right side's columns are renamed fresh so the all-column
        equality condition binds unambiguously."""
        from .logical import Except, Intersect
        if not isinstance(node, (Intersect, Except)):
            return node
        left, right = node.children
        ls, rs = left.schema(), right.schema()
        if len(ls.names) != len(rs.names):
            raise AnalysisException(
                f"{node!r} requires same-arity sides: "
                f"{len(ls.names)} vs {len(rs.names)}")
        renamed = [f"__setop_{i}_{n}" for i, n in enumerate(rs.names)]
        rproj = Project([Alias(Col(n), rn)
                         for n, rn in zip(rs.names, renamed)], right)
        cond = None
        for ln, rn in zip(ls.names, renamed):
            eq = EQ(Col(ln), Col(rn))
            cond = eq if cond is None else And(cond, eq)
        how = "left_semi" if isinstance(node, Intersect) else "left_anti"
        return Distinct(Join(left, rproj, how, cond, None))

    def _rewrite_node(self, node: LogicalPlan) -> LogicalPlan:
        if isinstance(node, Aggregate):
            return rewrite_distinct_aggregates(node)
        if isinstance(node, Sort):
            return self._resolve_sort_references(node)
        if isinstance(node, Project):
            return self._extract_window_expressions(node)
        return node

    def _extract_window_expressions(self, node: Project) -> LogicalPlan:
        """ExtractWindowExpressions: pull `f(...) OVER spec` out of the
        select list into WindowNode operators (one per distinct spec),
        leaving Col references behind."""
        from .window import WindowExpression, WindowNode, contains_window
        if not any(contains_window(e) for e in node.exprs):
            return node
        found: List[Tuple[WindowExpression, str]] = []

        def repl(e: Expression) -> Expression:
            if isinstance(e, WindowExpression):
                for we, n in found:
                    if repr(we) == repr(e):
                        return Col(n)
                name = fresh_name("win", repr(e), len(found))
                found.append((e, name))
                return Col(name)
            return e.map_children(repl)

        new_exprs = []
        for e in node.exprs:
            r = repl(e)
            # a bare window expr keeps its pretty name
            if isinstance(r, Col) and not isinstance(e, Alias):
                r = Alias(r, e.name) if r.name != e.name else r
            new_exprs.append(r)

        child = node.children[0]
        by_spec: Dict[Any, List[Tuple[WindowExpression, str]]] = {}
        order: List[Any] = []
        for we, n in found:
            k = we.spec._key()
            if k not in by_spec:
                by_spec[k] = []
                order.append(k)
            by_spec[k].append((we, n))
        for k in order:
            child = WindowNode(by_spec[k], child)
        return type(node)(new_exprs, child)

    def _resolve_sort_references(self, node: Sort) -> LogicalPlan:
        """ORDER BY may reference input columns dropped by the SELECT list
        (Spark's ResolveSortReferences): push the Sort below the Project,
        substituting select-list aliases with their defining expressions."""
        child = node.children[0]
        if not isinstance(child, Project):
            return node
        proj = child
        out_names = set(proj.schema().names)
        refs = set()
        for o in node.orders:
            refs |= o.child.references()
        missing = refs - out_names
        if not missing:
            return node
        try:
            input_names = set(proj.children[0].schema().names)
        except AnalysisException:
            return node
        qmap = qualifier_map(proj.children[0])
        if not all(m in input_names or m in qmap for m in missing):
            return node  # genuinely unresolvable; validation will report
        amap: Dict[str, Expression] = {}
        for e in proj.exprs:
            if isinstance(e, Alias):
                amap[e.name] = e.children[0]

        def subst(e: Expression) -> Expression:
            if isinstance(e, Col):
                if e.name in amap:
                    return amap[e.name]
                if e.name not in input_names and e.name in qmap:
                    return Col(qmap[e.name])
            return e.map_children(subst)

        new_orders = [SortOrder(subst(o.child), o.ascending, o.nulls_first)
                      for o in node.orders]
        return Project(proj.exprs, Sort(new_orders, proj.children[0],
                                        node.is_global))

    def _validate(self, plan: LogicalPlan) -> None:
        # forces schema computation everywhere → surfacing unresolved
        # columns / type errors with plan context
        for c in plan.children:
            self._validate(c)
        try:
            plan.schema()
        except AnalysisException:
            raise
        except KeyError as e:
            raise AnalysisException(f"cannot resolve column {e} in {plan!r}")
