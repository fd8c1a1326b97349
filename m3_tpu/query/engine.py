"""Query engine: PromQL AST evaluation over storage blocks.

Reference: /root/reference/src/query/executor/ — Engine.ExecuteExpr
(engine.go:116) builds the transform DAG and pushes blocks through it
(state.go:183). Here evaluation is direct recursion over the AST: every node
produces a dense [S, T] block (or a [T] scalar row), so each transform is one
vectorized call into m3_tpu.query.functions — the DAG collapses into array
ops the device can fuse.
"""

from __future__ import annotations

import functools
import re as _re
import threading
import time
from dataclasses import dataclass, replace
from typing import Protocol

import numpy as np

from ..block.core import Bounds, SeriesMeta, Tags, make_tags
from .functions import aggregation as A
from .functions import binary as B
from .functions import linear as L
from .functions import temporal as T
from .functions import temporal_fused as TF
from .promql import (
    Aggregation,
    BinaryOp,
    Call,
    Expr,
    Matcher,
    NumberLiteral,
    RangeSelector,
    StringLiteral,
    Subquery,
    Unary,
    VectorSelector,
    parse,
)

NANOS = 1_000_000_000
DEFAULT_LOOKBACK = 5 * 60 * NANOS


@dataclass
class Result:
    """A evaluated vector: values [S, T] + per-series metas (scalar results
    have one row and scalar=True)."""

    values: np.ndarray
    metas: list[SeriesMeta]
    scalar: bool = False


class Storage(Protocol):
    """storage.Storage seam (src/query/storage/types.go): raw series fetch."""

    def fetch(
        self, matchers: list[Matcher], start_nanos: int, end_nanos: int
    ) -> list[tuple[Tags, np.ndarray, np.ndarray]]:
        """→ [(tags, times i64[n], values f64[n])] raw samples, times sorted."""
        ...


def consolidate_row(
    times: np.ndarray, vals: np.ndarray, grid: np.ndarray,
    lookback_nanos: int,
) -> np.ndarray:
    """ONE series' samples onto the step grid: value at step = last
    sample in (t-lookback, t]. This is THE 'last' consolidation rule —
    the fused device plan (query/plan.py) replicates it in-program and
    the err-lane host stitch (m3_storage) reuses it directly, so the
    bit-identity contract has exactly one host definition."""
    if len(times) == 0:
        return np.full(len(grid), np.nan)
    idx = np.searchsorted(times, grid, side="right") - 1
    ok = idx >= 0
    sample_t = times[np.maximum(idx, 0)]
    ok &= grid - sample_t < lookback_nanos
    return np.where(ok, vals[np.maximum(idx, 0)], np.nan)


def consolidate(
    series: list[tuple[Tags, np.ndarray, np.ndarray]],
    bounds: Bounds,
    lookback_nanos: int,
) -> Result:
    """Samples → step grid: value at step = last sample in (t-lookback, t]
    (storage/m3/consolidators/ 'last' consolidation)."""
    s = len(series)
    grid = bounds.timestamps()
    out = np.full((s, bounds.steps), np.nan)
    metas = []
    for i, (tags, times, vals) in enumerate(series):
        metas.append(SeriesMeta(tags=tags))
        if len(times) == 0:
            continue
        out[i] = consolidate_row(times, vals, grid, lookback_nanos)
    return Result(values=out, metas=metas)


class Engine:
    """executor.Engine equivalent."""

    def __init__(
        self,
        storage: Storage,
        lookback_nanos: int = DEFAULT_LOOKBACK,
        limits=None,
        global_enforcer=None,
        tenant_enforcers=None,
        scheduler=None,
    ) -> None:
        self.storage = storage
        self.lookback = lookback_nanos
        # per-query cost limits (query/cost.py); None = unlimited
        self.limits = limits
        self.global_enforcer = global_enforcer
        # per-tenant middle scopes (query/tenants.TenantEnforcers): when
        # set, the enforcer chain is query → tenant → global and each
        # query's parent scope resolves from the thread's tenant context
        self.tenant_enforcers = tenant_enforcers
        # admission scheduler (query/scheduler.QueryScheduler): when set,
        # every TOP-LEVEL query passes cost-aware admission before eval
        # and may be shed with a typed QueryShedError; nested evaluation
        # rides the outer query's slot
        self.scheduler = scheduler
        self._enforcer = threading.local()

    def query_range(
        self, query: str, start_nanos: int, end_nanos: int, step_nanos: int
    ) -> Result:
        # per-query accounting (stats.py): one QueryStats record rides a
        # thread-local through engine → storage → database; sealed records
        # feed the slow-query ring + m3tpu_query_* metrics. ``qs`` is None
        # on nested evaluation (an outer query already owns the record).
        from . import stats

        qs = stats.start(query)
        if qs is not None:
            # the storage adapter knows which namespace this engine serves
            # (M3Storage.namespace); /debug/active_queries shows it
            qs.namespace = str(getattr(self.storage, "namespace", "") or "")
        t_start = time.perf_counter()
        err: str | None = None
        admitted = False
        try:
            with stats.stage("parse"):
                ast = parse(query)
            steps = int((end_nanos - start_nanos) // step_nanos) + 1
            bounds = Bounds(start_nanos, step_nanos, steps)
            # @ start()/end() bind to the TOP-LEVEL query range, even inside
            # subqueries (prometheus PreprocessExpr)
            _bind_at(ast, bounds)
            if qs is not None and self.scheduler is not None:
                # cost-aware admission: may block briefly, may shed with
                # a typed QueryShedError (coordinator → HTTP 503); only
                # top-level queries admit — nested evaluation rides the
                # outer query's slot. The queue wait is bounded by the
                # caller's propagated deadline when one is ambient
                # (coordinator timeout param/header), else by the
                # scheduler's own max_queue_wait.
                from ..net.resilience import current_deadline

                self.scheduler.admit(
                    query, steps, record=qs, deadline=current_deadline()
                )
                admitted = True
            parent = self.global_enforcer
            if self.tenant_enforcers is not None:
                # the per-tenant middle scope: charges flow query →
                # tenant → global, so a runaway tenant trips its own
                # ceiling before it can exhaust the fleet's
                from . import tenants

                parent = self.tenant_enforcers.scope_for(tenants.current())
            if self.limits is None and parent is None:
                return self._eval(ast, bounds)
            from .cost import Enforcer, QueryLimits

            enforcer = Enforcer(
                self.limits if self.limits is not None else QueryLimits(),
                parent,
            )
            self._enforcer.current = enforcer
            try:
                return self._eval(ast, bounds)
            finally:
                self._enforcer.current = None
                enforcer.release()
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            from .cost import QueryLimitError

            if isinstance(exc, QueryLimitError):
                # the slow-query ring must show WHICH chain scope 422'd
                # the query — stamped on the thread's active record (the
                # outer record when this frame is a nested evaluation)
                cur = stats.current()
                if cur is not None:
                    cur.limit_exceeded = exc.scope
            raise
        finally:
            if admitted:
                self.scheduler.release()
                if err is None and qs is not None:
                    # feed the matched-series observation back into the
                    # cost memo so the NEXT run of this query is priced
                    # from evidence instead of the optimistic default
                    self.scheduler.observe(query, qs.series_scanned)
            if qs is not None:
                stats.finish(qs, time.perf_counter() - t_start, error=err)

    def query_instant(self, query: str, time_nanos: int) -> Result:
        return self.query_range(query, time_nanos, time_nanos, NANOS)

    def explain(
        self, query: str, start_nanos: int, end_nanos: int, step_nanos: int
    ) -> dict:
        """EXPLAIN: evaluate the query while recording where its time and
        data went — the full per-stage timing record (parse /
        index_resolve / fetch / decode / exec), scan counters, and the
        resident-vs-streamed routing decision PER (series, block) from the
        storage adapter (why a block streamed: buffered overlay, evicted
        page, pool off). Returns the sealed stats record plus a result
        summary; the record also lands in the slow-query ring and metrics
        like any query, prefixed ``EXPLAIN`` so dashboards can exclude it.
        """
        from . import stats

        st = stats.start(f"EXPLAIN {query}")
        if st is not None:
            st.record_routing = True
            st.namespace = str(getattr(self.storage, "namespace", "") or "")
        t_start = time.perf_counter()
        err: str | None = None
        try:
            r = self.query_range(query, start_nanos, end_nanos, step_nanos)
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            if st is not None:
                stats.finish(st, time.perf_counter() - t_start, error=err)
        out = st.to_dict() if st is not None else {"query": query}
        out["result"] = {
            "series": len(r.metas),
            "steps": int(np.asarray(r.values).shape[1]) if len(r.metas) else 0,
        }
        return out

    def scan_totals(self, query: str, start_nanos: int, end_nanos: int) -> dict:
        """Flagship raw-sample scan as an engine surface: ``query`` must
        be a plain vector selector (e.g. ``metric{job="x"}``) — the
        totals are whole-block reductions over the matched series'
        compressed streams, NOT PromQL semantics (no step grid, no
        lookback consolidation). Routing is the storage adapter's:
        decode-from-HBM when every matched block is resident
        (m3_tpu/resident/), streamed upload+decode otherwise; the result's
        ``path`` field and the per-query resident_hit counters
        (query/stats.py) record which way it went."""
        from . import stats

        storage_scan = getattr(self.storage, "scan_totals", None)
        if storage_scan is None:
            raise ValueError("storage does not support scan_totals")
        qs = stats.start(f"scan_totals({query})")
        if qs is not None:
            qs.namespace = str(getattr(self.storage, "namespace", "") or "")
        t_start = time.perf_counter()
        err: str | None = None
        try:
            with stats.stage("parse"):
                ast = parse(query)
            if not isinstance(ast, VectorSelector):
                raise ValueError("scan_totals: query must be a vector selector")
            if ast.at_nanos is not None or ast.offset_nanos:
                raise ValueError("scan_totals: @/offset modifiers unsupported")
            matchers = list(ast.matchers)
            if ast.name:
                matchers.append(Matcher("__name__", "=", ast.name))
            with stats.stage("fetch"):
                return storage_scan(matchers, start_nanos, end_nanos)
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            if qs is not None:
                stats.finish(qs, time.perf_counter() - t_start, error=err)

    # --- evaluation ---

    def _fetch(self, sel: VectorSelector, bounds: Bounds, extra_steps: int = 0) -> Result:
        start = bounds.start_nanos - sel.offset_nanos - extra_steps * bounds.step_nanos
        end = bounds.start_nanos - sel.offset_nanos + bounds.step_nanos * bounds.steps
        matchers = list(sel.matchers)
        if sel.name:
            matchers.append(Matcher("__name__", "=", sel.name))
        from . import stats

        b = Bounds(start, bounds.step_nanos, bounds.steps + extra_steps)
        # one-dispatch fused pipeline (query/plan.py): when the storage
        # adapter can serve fetch+consolidate as ONE device program it
        # returns the finished step grid — bit-identical to the staged
        # consolidate below — and the per-series host loops disappear.
        # None = ineligible (reason recorded in EXPLAIN routing): run
        # the staged path unchanged.
        grid_fetch = getattr(self.storage, "fetch_grid", None)
        if grid_fetch is not None:
            with stats.stage("fetch"):
                fused = grid_fetch(
                    matchers, start - self.lookback, end, b.timestamps(),
                    self.lookback,
                )
            if fused is not None:
                # metas arrive as ready SeriesMeta (cached on the plan
                # entry — matched set is invariant while the plan holds)
                metas, values, datapoints = fused
                stats.add(series=len(metas), datapoints=datapoints)
                enforcer = getattr(self._enforcer, "current", None)
                if enforcer is not None:
                    enforcer.charge(len(metas), datapoints)
                return Result(values, list(metas))
        with stats.stage("fetch"):
            raw = self.storage.fetch(matchers, start - self.lookback, end)
        stats.add(
            series=len(raw), datapoints=sum(len(t) for _, t, _ in raw)
        )
        enforcer = getattr(self._enforcer, "current", None)
        if enforcer is not None:
            # charge fetched series + raw datapoints against the query's
            # cost limits (query/cost.go block accounting)
            enforcer.charge(len(raw), sum(len(t) for _, t, _ in raw))
        return consolidate(raw, b, self.lookback)

    def _eval(self, e: Expr, bounds: Bounds) -> Result:
        if isinstance(e, NumberLiteral):
            return Result(
                np.full((1, bounds.steps), e.value), [SeriesMeta(())], scalar=True
            )
        if isinstance(e, VectorSelector):
            if e.at_nanos is not None:
                # @ pins evaluation: one instant, broadcast across steps
                at = _resolve_at(e.at_nanos, bounds)
                r = self._fetch(
                    replace(e, at_nanos=None), Bounds(at, bounds.step_nanos, 1)
                )
                return Result(
                    np.tile(np.asarray(r.values), (1, bounds.steps)), r.metas
                )
            return self._fetch(e, bounds)
        if isinstance(e, Unary):
            r = self._eval(e.expr, bounds)
            vals = -r.values if e.op == "-" else r.values
            return Result(vals, r.metas, r.scalar)
        if isinstance(e, Call):
            return self._call(e, bounds)
        if isinstance(e, Aggregation):
            return self._aggregate(e, bounds)
        if isinstance(e, BinaryOp):
            return self._binary(e, bounds)
        if isinstance(e, RangeSelector):
            raise ValueError("promql: range selector outside function call")
        if isinstance(e, StringLiteral):
            raise ValueError("promql: string literal in value position")
        raise TypeError(f"unhandled node {e!r}")

    # temporal functions taking a range argument
    _TEMPORAL = {
        "rate": lambda v, w, s: T.rate(v, w, s),
        "irate": lambda v, w, s: T.irate(v, w, s),
        "increase": lambda v, w, s: T.increase(v, w, s),
        "delta": lambda v, w, s: T.delta(v, w, s),
        "idelta": lambda v, w, s: T.idelta(v, w, s),
        "deriv": lambda v, w, s: T.deriv(v, w, s),
        "resets": lambda v, w, s: T.resets(v, w),
        "changes": lambda v, w, s: T.changes(v, w),
        "sum_over_time": lambda v, w, s: T.sum_over_time(v, w),
        "count_over_time": lambda v, w, s: T.count_over_time(v, w),
        "avg_over_time": lambda v, w, s: T.avg_over_time(v, w),
        "min_over_time": lambda v, w, s: T.min_over_time(v, w),
        "max_over_time": lambda v, w, s: T.max_over_time(v, w),
        "last_over_time": lambda v, w, s: T.last_over_time(v, w),
        "stddev_over_time": lambda v, w, s: T.stddev_over_time(v, w),
        "stdvar_over_time": lambda v, w, s: T.stdvar_over_time(v, w),
        "present_over_time": lambda v, w, s: np.where(
            np.asarray(T.count_over_time(v, w)) > 0, 1.0, np.nan
        ),
    }

    def _range_arg(self, arg: Expr, bounds: Bounds):
        """Range-vector argument → (values, metas, window, step_secs, post).

        ``values`` is a [S, N] sample matrix whose trailing axis a temporal
        function slides its ``window`` over; ``post`` maps the function's
        [S, N - window + 1] output onto the query's [S, steps] grid (identity
        for plain ranges; column re-selection for subqueries, whose samples
        are at the subquery step; broadcast for @-pinned ranges).
        """
        if isinstance(arg, RangeSelector):
            sel = arg.vector
            window = int(arg.range_nanos // bounds.step_nanos) + 1
            extra = window - 1
            step_s = bounds.step_nanos / NANOS
            if sel.at_nanos is not None:
                at = _resolve_at(sel.at_nanos, bounds)
                b_at = Bounds(
                    at - extra * bounds.step_nanos, bounds.step_nanos, window
                )
                r = self._fetch(replace(sel, at_nanos=None), b_at)

                def post(out, _steps=bounds.steps):
                    return np.tile(out[:, -1:], (1, _steps))

                return np.asarray(r.values), r.metas, window, step_s, post
            r = self._fetch(sel, bounds, extra_steps=extra)
            return np.asarray(r.values), r.metas, window, step_s, lambda out: out
        if isinstance(arg, Subquery):
            return self._subquery_arg(arg, bounds)
        raise ValueError("promql: function requires a range vector")

    def _subquery_arg(self, sq: Subquery, bounds: Bounds):
        sub_step = sq.step_nanos or bounds.step_nanos
        if sq.at_nanos is not None:
            at = _resolve_at(sq.at_nanos, bounds)
            outer_ts = np.asarray([at - sq.offset_nanos], np.int64)
        else:
            outer_ts = bounds.timestamps() - sq.offset_nanos
        window = int(sq.range_nanos // sub_step) + 1
        # inner evaluation instants align to ABSOLUTE multiples of the
        # subquery step (prometheus subquery semantics), so results don't
        # shift with the outer query's start; the grid extends DOWN past
        # (outer_min - range) so the earliest outer step has a full window
        lo = int(outer_ts.min()) - sq.range_nanos
        g_start = (lo // sub_step) * sub_step
        n_sub = int((int(outer_ts.max()) - g_start) // sub_step) + 1
        sub_bounds = Bounds(g_start, sub_step, n_sub)
        inner = self._eval(sq.expr, sub_bounds)
        vals = np.asarray(inner.values)
        grid = sub_bounds.timestamps()
        # output column j of a sliced temporal result ends at grid[j + w - 1];
        # each outer step wants the window ending at the last grid point <= t
        idx = np.searchsorted(grid, outer_ts, side="right") - 1
        cols = np.clip(idx - (window - 1), 0, max(n_sub - window, 0))

        if sq.at_nanos is not None:

            def post(out, _steps=bounds.steps, _cols=cols):
                return np.tile(out[:, _cols[:1]], (1, _steps))

        else:

            def post(out, _cols=cols):
                return out[:, _cols]

        return vals, inner.metas, window, sub_step / NANOS, post

    def _temporal(self, name: str, arg: Expr, bounds: Bounds, device_fn) -> Result:
        """Range function ``name`` over ``arg``. The ``query.temporal``
        stage runs from the range's grid in hand to the built Result. In
        it, a selection over float64 samples that float32 cannot hold is
        taken exactly on the host (``query.temporal.select``); anything
        else is ``device_fn(values, window, step_seconds)``, whose host
        side (float32 conversion, upload, the fused kernel's or the eager
        operations' dispatch) is ``query.temporal.dispatch`` and whose
        blocking read-back (the device queue ahead of it, the work, the
        transfer) is ``query.temporal.wait``."""
        from . import stats

        vals, metas, w, step_s, post = self._range_arg(arg, bounds)
        with stats.stage("query.temporal"):
            if TF.exact_selection(name, vals):
                with stats.stage("query.temporal.select"):
                    out = TF.select_over_time(name, vals, w)
            else:
                with stats.stage("query.temporal.dispatch"):
                    out = device_fn(vals, w, step_s)
                with stats.stage("query.temporal.wait"):
                    out = np.asarray(out)
            return Result(post(out[:, w - 1 :]), metas)

    def _call(self, e: Call, bounds: Bounds) -> Result:
        name = e.func
        if name in self._TEMPORAL:
            fn = self._TEMPORAL[name]
            if name in TF.FUSABLE:
                # one VMEM-resident pallas pass on TPU (temporal_fused.py)
                fn = functools.partial(TF.temporal_apply, name)
            return self._temporal(name, e.args[0], bounds, fn)
        if name == "quantile_over_time":
            q = _number(e.args[0])
            return self._temporal(
                name, e.args[1], bounds,
                lambda v, w, s: T.quantile_over_time(v, w, q))
        if name == "predict_linear":
            t = _number(e.args[1])
            return self._temporal(
                name, e.args[0], bounds,
                lambda v, w, s: T.predict_linear(v, w, s, t))
        if name == "holt_winters":
            sf, tf = _number(e.args[1]), _number(e.args[2])
            return self._temporal(
                name, e.args[0], bounds,
                lambda v, w, s: T.holt_winters(v, w, sf, tf))
        if name == "label_replace":
            return self._label_replace(e, bounds)
        if name == "label_join":
            return self._label_join(e, bounds)
        if name in L.MATH_FNS:
            r = self._eval(e.args[0], bounds)
            return Result(np.asarray(L.MATH_FNS[name](r.values)), r.metas, r.scalar)
        if name == "round":
            r = self._eval(e.args[0], bounds)
            to = _number(e.args[1]) if len(e.args) > 1 else 1.0
            return Result(np.asarray(L.round_to(r.values, to)), r.metas, r.scalar)
        if name == "clamp_min":
            r = self._eval(e.args[0], bounds)
            return Result(np.asarray(L.clamp_min(r.values, _number(e.args[1]))), r.metas)
        if name == "clamp_max":
            r = self._eval(e.args[0], bounds)
            return Result(np.asarray(L.clamp_max(r.values, _number(e.args[1]))), r.metas)
        if name == "clamp":
            r = self._eval(e.args[0], bounds)
            lo, hi = _number(e.args[1]), _number(e.args[2])
            return Result(np.clip(r.values, lo, hi), r.metas)
        if name == "histogram_quantile":
            q = _number(e.args[0])
            r = self._eval(e.args[1], bounds)
            index, bnds, metas = L.histogram_buckets(r.metas)
            out = np.asarray(L.histogram_quantile(q, r.values, index, bnds))
            return Result(out, metas)
        if name in ("sort", "sort_desc"):
            r = self._eval(e.args[0], bounds)
            order = L.sort_series(r.values, descending=name == "sort_desc")
            return Result(r.values[order], [r.metas[i] for i in order])
        if name == "absent":
            r = self._eval(e.args[0], bounds)
            vals = np.asarray(A.absent(r.values))
            return Result(vals, [SeriesMeta(())])
        if name == "scalar":
            r = self._eval(e.args[0], bounds)
            if len(r.metas) == 1:
                return Result(r.values[:1], [SeriesMeta(())], scalar=True)
            return Result(np.full((1, bounds.steps), np.nan), [SeriesMeta(())], scalar=True)
        if name == "vector":
            r = self._eval(e.args[0], bounds)
            return Result(r.values, [SeriesMeta(())])
        if name == "time":
            t = bounds.timestamps() / NANOS
            return Result(t[None, :].astype(np.float64), [SeriesMeta(())], scalar=True)
        if name == "timestamp":
            r = self._eval(e.args[0], bounds)
            t = (bounds.timestamps() / NANOS)[None, :]
            out = np.where(np.isnan(np.asarray(r.values)), np.nan, t)
            return Result(out, r.metas)
        if name in ("day_of_month", "day_of_week", "days_in_month", "hour", "minute", "month", "year"):
            if e.args:
                r = self._eval(e.args[0], bounds)
                vals, metas = r.values, r.metas
            else:
                vals = (bounds.timestamps() / NANOS)[None, :].astype(np.float64)
                metas = [SeriesMeta(())]
            return Result(L.datetime_fn(name, vals), metas)
        raise ValueError(f"promql: unsupported function {name}")

    # --- label manipulation (functions/label_replace, label_join —
    # src/query/functions/tag/ in the reference) ---

    def _label_replace(self, e: Call, bounds: Bounds) -> Result:
        r = self._eval(e.args[0], bounds)
        dst, repl, src, regex_s = (_string(a) for a in e.args[1:5])
        pattern = _re.compile(regex_s)
        metas = []
        for m in r.metas:
            tags = dict(m.tags)
            val = tags.get(src.encode(), b"").decode()
            mm = pattern.fullmatch(val)
            if mm is not None:
                new = mm.expand(_promql_template(repl))
                if new:
                    tags[dst.encode()] = new.encode()
                else:
                    tags.pop(dst.encode(), None)
            metas.append(
                SeriesMeta(tags=tuple(sorted(tags.items())), name=m.name)
            )
        return Result(r.values, metas, r.scalar)

    def _label_join(self, e: Call, bounds: Bounds) -> Result:
        r = self._eval(e.args[0], bounds)
        dst = _string(e.args[1])
        sep = _string(e.args[2])
        srcs = [_string(a).encode() for a in e.args[3:]]
        metas = []
        for m in r.metas:
            tags = dict(m.tags)
            joined = sep.encode().join(tags.get(sl, b"") for sl in srcs)
            if joined:
                tags[dst.encode()] = joined
            else:
                tags.pop(dst.encode(), None)
            metas.append(
                SeriesMeta(tags=tuple(sorted(tags.items())), name=m.name)
            )
        return Result(r.values, metas, r.scalar)

    def _aggregate(self, e: Aggregation, bounds: Bounds) -> Result:
        r = self._eval(e.expr, bounds)
        matching = [g.encode() for g in e.grouping]
        layout = A.group_by_tags(r.metas, matching or None, e.without)
        vals = np.asarray(r.values)
        if e.op in ("topk", "bottomk"):
            k = int(_number(e.param))
            fn = A.topk if e.op == "topk" else A.bottomk
            out = np.asarray(fn(vals, layout, k))
            keep = ~np.all(np.isnan(out), axis=1)
            return Result(out[keep], [r.metas[i] for i in np.nonzero(keep)[0]])
        if e.op == "quantile":
            out = np.asarray(A.grouped_quantile(vals, layout, _number(e.param)))
            return Result(out, layout.metas)
        if e.op == "count_values":
            label = e.param.value if isinstance(e.param, StringLiteral) else "value"
            out, metas = A.count_values(vals, r.metas, label.encode())
            return Result(out, metas)
        fn = {
            "sum": A.grouped_sum,
            "min": A.grouped_min,
            "max": A.grouped_max,
            "avg": A.grouped_avg,
            "count": A.grouped_count,
            "stddev": A.grouped_stddev,
            "stdvar": A.grouped_stdvar,
        }[e.op]
        return Result(np.asarray(fn(vals, layout)), layout.metas)

    def _binary(self, e: BinaryOp, bounds: Bounds) -> Result:
        lhs = self._eval(e.lhs, bounds)
        rhs = self._eval(e.rhs, bounds)
        lv, rv = np.asarray(lhs.values), np.asarray(rhs.values)

        if e.op in ("and", "or", "unless"):
            m = B.VectorMatching(on=e.on, matching_labels=tuple(x.encode() for x in e.matching_labels))
            fn = {"and": B.logical_and, "or": B.logical_or, "unless": B.logical_unless}[e.op]
            vals, metas = fn(lv, rv, lhs.metas, rhs.metas, m)
            return Result(np.asarray(vals), metas)

        is_comp = e.op in B.COMP_FNS
        # scalar op scalar / vector op scalar / scalar op vector
        if lhs.scalar and rhs.scalar:
            out = self._apply_scalar(e, lv, rv)
            return Result(out, lhs.metas, scalar=True)
        if rhs.scalar:
            out = self._apply_scalar(e, lv, rv)  # broadcast [1,T]
            return Result(out, _drop_names(lhs.metas) if not is_comp else lhs.metas)
        if lhs.scalar:
            if is_comp and not e.return_bool:
                cond = B.COMP_FNS[e.op](lv, rv)
                return Result(np.where(cond, rv, np.nan), rhs.metas)
            out = self._apply_scalar(e, lv, rv)
            return Result(out, _drop_names(rhs.metas) if not is_comp else rhs.metas)

        # vector op vector
        m = B.VectorMatching(on=e.on, matching_labels=tuple(x.encode() for x in e.matching_labels))
        if e.group_left or e.group_right:
            return self._binary_grouped(e, m, lhs, rhs, lv, rv, is_comp)
        tl, tr, metas = B.intersect(m, lhs.metas, rhs.metas)
        if is_comp:
            out = np.asarray(B.comparison(e.op, lv, rv, tl, tr, e.return_bool))
            metas = [lhs.metas[i] for i in tl] if not e.return_bool else metas
            return Result(out, metas)
        out = np.asarray(B.arithmetic(e.op, lv, rv, tl, tr))
        return Result(out, metas)

    def _binary_grouped(self, e: BinaryOp, m, lhs, rhs, lv, rv, is_comp) -> Result:
        """Many-to-one vector matching (binary.go group_left/group_right):
        each series on the MANY side joins at most one series on the ONE
        side; result keeps the many side's labels, plus any carried labels
        named in group_left(...)/group_right(...)."""
        many, one = (lhs, rhs) if e.group_left else (rhs, lhs)
        one_index: dict = {}
        for j, om in enumerate(one.metas):
            key = B._match_key(om.tags, m)
            if key in one_index:
                raise ValueError(
                    "promql: many-to-many matching: multiple series on the "
                    f"'one' side share match key {key!r}"
                )
            one_index[key] = j
        take_many, take_one, metas = [], [], []
        include = [x.encode() for x in e.include_labels]
        for i, mm in enumerate(many.metas):
            j = one_index.get(B._match_key(mm.tags, m))
            if j is None:
                continue
            take_many.append(i)
            take_one.append(j)
            tags = dict(mm.tags)
            if not is_comp:
                # arithmetic drops the metric name, as in the 1:1 path
                tags.pop(b"__name__", None)
            if include:
                one_tags = dict(one.metas[j].tags)
                for lbl in include:
                    if lbl in one_tags:
                        tags[lbl] = one_tags[lbl]
                    else:
                        tags.pop(lbl, None)
            metas.append(SeriesMeta(tags=tuple(sorted(tags.items())), name=mm.name))
        tm = np.asarray(take_many, np.int32)
        to = np.asarray(take_one, np.int32)
        # orient back to lhs/rhs for the (non-commutative) operator
        tl, tr = (tm, to) if e.group_left else (to, tm)
        if is_comp:
            out = np.asarray(B.comparison(e.op, lv, rv, tl, tr, e.return_bool))
            return Result(out, metas)
        out = np.asarray(B.arithmetic(e.op, lv, rv, tl, tr))
        return Result(out, metas)

    def _apply_scalar(self, e: BinaryOp, lv, rv):
        if e.op in B.COMP_FNS:
            cond = B.COMP_FNS[e.op](lv, rv)
            if e.return_bool:
                return cond.astype(np.float64)
            return np.where(cond, lv, np.nan)
        return np.asarray(B.ARITH_FNS[e.op](np.asarray(lv), np.asarray(rv)))


def _drop_names(metas: list[SeriesMeta]) -> list[SeriesMeta]:
    return [
        SeriesMeta(tags=tuple((k, v) for k, v in m.tags if k != b"__name__"), name=m.name)
        for m in metas
    ]


def _number(e: Expr | None) -> float:
    if isinstance(e, NumberLiteral):
        return e.value
    if isinstance(e, Unary) and isinstance(e.expr, NumberLiteral):
        return -e.expr.value if e.op == "-" else e.expr.value
    raise ValueError("promql: expected a number literal")


def _string(e: Expr) -> str:
    if isinstance(e, StringLiteral):
        return e.value
    raise ValueError("promql: expected a string literal")


def _bind_at(e, bounds: Bounds) -> None:
    """Resolve @ start()/end() sentinels against the top-level query bounds
    (must run before evaluation: subqueries evaluate their inner expression
    under DIFFERENT bounds, which must not re-bind start/end)."""
    if isinstance(e, VectorSelector):
        if isinstance(e.at_nanos, str):
            e.at_nanos = _resolve_at(e.at_nanos, bounds)
    elif isinstance(e, RangeSelector):
        _bind_at(e.vector, bounds)
    elif isinstance(e, Subquery):
        if isinstance(e.at_nanos, str):
            e.at_nanos = _resolve_at(e.at_nanos, bounds)
        _bind_at(e.expr, bounds)
    elif isinstance(e, Call):
        for a in e.args:
            _bind_at(a, bounds)
    elif isinstance(e, Aggregation):
        _bind_at(e.expr, bounds)
        if e.param is not None:
            _bind_at(e.param, bounds)
    elif isinstance(e, BinaryOp):
        _bind_at(e.lhs, bounds)
        _bind_at(e.rhs, bounds)
    elif isinstance(e, Unary):
        _bind_at(e.expr, bounds)


def _resolve_at(at, bounds: Bounds) -> int:
    """@ modifier value → absolute nanos (start()/end() use the bounds)."""
    if at == "start":
        return bounds.start_nanos
    if at == "end":
        return bounds.start_nanos + bounds.step_nanos * (bounds.steps - 1)
    return int(at)


def _promql_template(repl: str) -> str:
    """label_replace templates use $1/${name}; re.Match.expand wants \\1."""
    out = _re.sub(r"\$\{(\w+)\}", r"\\g<\1>", repl)
    return _re.sub(r"\$(\d+)", r"\\\1", out)
