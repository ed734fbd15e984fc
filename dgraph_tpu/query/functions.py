"""Root/filter function resolution: Function AST node → sorted uid set.

Equivalent of the reference's worker/task.go processTask function
dispatch (parseSrcFn:722, FuncType handling :255-661): each function is
resolved against the device arenas with the ops kernels, then (for lossy
tokenizers — float/year/term-eq/trigram/geo) exact-rechecked on the host,
mirroring the reference's post-passes (task.go:473-661).
"""

from __future__ import annotations

import functools
import json
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from dgraph_tpu import obs, ops
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu import tok as tokmod
from dgraph_tpu.models import geo as geomod
from dgraph_tpu.models.arena import ArenaManager, IndexArena
from dgraph_tpu.models.store import PostingStore
from dgraph_tpu.models.types import (
    TypeID,
    TypedValue,
    compare_vals,
    convert,
    numeric,
    type_from_name,
)
from dgraph_tpu.gql.ast import Function
from dgraph_tpu.utils.metrics import INDEX_RECHECK_UIDS

class QueryError(ValueError):
    pass


_EMPTY = np.empty(0, dtype=np.int64)

_INEQ = {"le", "ge", "lt", "gt", "eq"}

_VEC_CMP = {"eq": np.equal, "le": np.less_equal, "lt": np.less,
            "ge": np.greater_equal, "gt": np.greater}
# |seconds from the epoch| below which a float64 timestamp tells every two
# microseconds apart (its spacing there is 2**-20 s): years 1697-2242
_TS_EXACT = float(2 ** 33)


def _exact_below(tid: TypeID) -> float:
    """|value| below which the float64 value column holds a value of type
    ``tid`` exactly: a datetime's timestamp within ``_TS_EXACT``, an int
    within 2**53, a float always."""
    if tid in (TypeID.DATETIME, TypeID.DATE):
        return _TS_EXACT
    if tid == TypeID.INT:
        return float(2 ** 53)
    return np.inf


def _sorted_unique(uids: np.ndarray) -> np.ndarray:
    """``uids`` as sorted-unique int64; one O(n) check where they already
    are (a level's uids, a var block's)."""
    uids = np.asarray(uids, dtype=np.int64)
    if len(uids) > 1 and not np.all(uids[1:] > uids[:-1]):
        uids = np.unique(uids)
    return uids


def _member(uids: np.ndarray, of: np.ndarray) -> np.ndarray:
    """Which of ``uids`` are in ``of`` (sorted), by binary search."""
    if not len(of):
        return np.zeros(len(uids), dtype=bool)
    return of[np.minimum(np.searchsorted(of, uids), len(of) - 1)] == uids


class Probe(NamedTuple):
    """A filter leaf priced for ``FuncResolver.probe``: the size of its set,
    read without building it, and for an inequality its bounds, typed."""

    fn: Function
    size: int
    vals: Optional[List[TypedValue]] = None


def _wallclock(v: TypedValue) -> bool:
    """Does the value's wall clock read as its UTC instant (naive or
    UTC)?  Then a datetime tokenizer's fields order as the instants do."""
    off = getattr(v.value, "utcoffset", None)
    return off is None or not off()


class FuncResolver:
    """Resolves functions against a store+arenas+variable environment."""

    def __init__(
        self,
        store: PostingStore,
        arenas: ArenaManager,
        uid_vars: Dict[str, np.ndarray],
        value_vars: Dict[str, Dict[int, TypedValue]],
        stats: Optional[dict] = None,
        cancel=None,
    ):
        self.store = store
        self.arenas = arenas
        self.uid_vars = uid_vars
        self.value_vars = value_vars
        # per-request engine stats (QueryEngine passes its own): the
        # k-way intersection router counts its host-vs-device choices
        # here so debug=true responses agree with the process counters
        self.stats = stats
        # cooperative cancellation (sched/qos.py): index probes that
        # loop over per-token/cell expansions checkpoint this token
        self.cancel = cancel

    def checkpoint(self) -> None:
        """Cancellation checkpoint for resolver-side expansion loops
        (the graftlint ``unchecked-hop-loop`` contract)."""
        tok = self.cancel
        if tok is not None:
            tok.check()

    # -- public ------------------------------------------------------------

    def resolve(
        self, fn: Function, candidates: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """uid set satisfying ``fn``; ``candidates`` bounds val()/count()
        style functions that are only meaningful relative to a set.  Where
        the candidates are fewer than ``fn``'s set holds, they are looked
        up one by one (``probe``) and the set is never built."""
        if candidates is not None:
            plan = self.probe_plan(fn)
            if plan is not None and len(candidates) < plan.size:
                got = self.probe(plan, candidates)
                if got is not None:
                    return got
        name = fn.name
        if name == "uid":
            out = np.array(sorted(set(fn.uid_args)), dtype=np.int64)
            for ref in fn.needs_vars:
                if ref.name in self.uid_vars:
                    out = np.union1d(out, self.uid_vars[ref.name])
                elif ref.name in self.value_vars:
                    # uid(v) over a VALUE var uses its uid keys
                    # (uid(val-var) semantics, query.go fillVars)
                    vm = self.value_vars[ref.name]
                    out = np.union1d(
                        out, np.fromiter(vm.keys(), dtype=np.int64, count=len(vm))
                    )
            if candidates is not None:
                out = np.intersect1d(out, candidates)
            return out
        if fn.is_val_var:
            return self._val_var_compare(fn, candidates)
        if fn.is_count:
            return self._count_compare(fn, candidates)
        if name in _INEQ:
            return self._bound(self._ineq(fn), candidates)
        if name in ("allofterms", "anyofterms"):
            return self._bound(self._terms(fn, "term", name == "allofterms"), candidates)
        if name in ("alloftext", "anyoftext"):
            return self._bound(self._terms(fn, "fulltext", name == "alloftext"), candidates)
        if name == "has":
            a = self.arenas.has_rows(fn.attr)
            pd = self.store.peek(fn.attr)
            if pd is None or not pd.values:
                # plain data arena: incremental deltas leave degree-0
                # rows behind after deletes — has() must not report them
                n = len(a.h_src)
                deg = a.h_offsets[1 : n + 1] - a.h_offsets[:n]
                return self._bound(a.h_src[deg > 0].copy(), candidates)
            return self._bound(a.h_src.copy(), candidates)
        if name == "regexp":
            return self._bound(self._regexp(fn), candidates)
        if name in ("near", "within", "contains", "intersects"):
            return self._bound(self._geo(fn), candidates)
        if name == "checkpwd":
            return self._checkpwd(fn, candidates)
        if name == "uid_in":
            return self._uid_in(fn, candidates)
        raise QueryError(f"unknown function {fn.name!r}")

    # -- helpers -----------------------------------------------------------

    def _bound(self, uids: np.ndarray, candidates: Optional[np.ndarray]) -> np.ndarray:
        if candidates is None:
            return uids
        return np.intersect1d(uids, candidates)

    # -- probing the candidates ---------------------------------------------

    def probe_plan(self, fn: Function) -> Optional[Probe]:
        """``fn`` priced for ``probe``: the size of its set read without
        building it — a ``uid()`` leaf's bound arrays, an inequality's index
        rows (the total ``_expand_rows`` would expand) — or None where
        ``probe`` cannot answer ``fn``: any other function, a lang tag on
        the function or the predicate, no sortable index, or a bound the
        numeric value column cannot hold exactly."""
        if fn.name == "uid":
            size = len(fn.uid_args)
            for ref in fn.needs_vars:
                if ref.name in self.uid_vars:
                    size += len(self.uid_vars[ref.name])
                elif ref.name in self.value_vars:
                    size += len(self.value_vars[ref.name])
            return Probe(fn, size)
        if fn.name not in _INEQ or fn.is_val_var or fn.is_count or fn.lang or not fn.args:
            return None
        pred = fn.attr
        name = self.store.schema.sortable_tokenizer(pred)
        if name is None or self._pred_has_langs(pred):
            return None
        tk = tokmod.get_tokenizer(name)
        idx = self.arenas.index(pred, name)
        vals: List[TypedValue] = []
        size = 0
        for raw in self._ineq_args(fn):
            val = self._typed_value(pred, raw)
            bound = numeric(val)
            if (val.tid != tk.typ or bound is None or not _wallclock(val)
                    or abs(bound) >= _exact_below(val.tid)):
                return None
            lo, hi = self._ineq_rows(idx, tk, fn.name, tk.fn(val)[0])
            size += int(idx.csr.h_offsets[hi] - idx.csr.h_offsets[lo])
            vals.append(val)
        return Probe(fn, size, vals)

    def probe(self, plan: Probe, candidates: np.ndarray) -> Optional[np.ndarray]:
        """``plan.fn``'s set within ``candidates``, found by looking each
        candidate up — in the uid variables' arrays, or in the predicate's
        numeric value column — where the set is never built: sorted-unique
        int64, the uids ``_bound`` gives.  Booked as a k-way of route
        ``probe`` (query/joinplan.py ``note_probe``).  None where the value
        column cannot stand for the index (``_probe_values``)."""
        from dgraph_tpu.query.joinplan import note_probe

        cands = _sorted_unique(candidates)
        if not len(cands):
            return _EMPTY
        if plan.vals is None:
            with obs.stage(self.stats if self.stats is not None else {}, "kway_ms"):
                out = self._probe_uid(plan.fn, cands)
        else:
            out = self._probe_values(plan, cands)
            if out is None:
                return None
        note_probe(self.stats, len(cands))
        return out

    def narrow(
        self, fns: List[Function], candidates: np.ndarray
    ) -> Tuple[np.ndarray, List[Function]]:
        """An ``and`` of frontier-free leaves, driven by its smallest set:
        ``candidates`` probed by every leaf whose set outnumbers them, the
        smallest such leaf first, each narrowing them for the next.  Gives
        the narrowed candidates and the leaves left to build whole."""
        plans = sorted(
            (p for p in map(self.probe_plan, fns) if p is not None),
            key=lambda p: p.size,
        )
        out = candidates
        done = set()
        while True:
            plan = next((p for p in plans if len(out) < p.size), None)
            if plan is None:
                break
            plans.remove(plan)
            got = self.probe(plan, out)
            if got is not None:
                out = got
                done.add(id(plan.fn))
        return out, [fn for fn in fns if id(fn) not in done]

    def _probe_uid(self, fn: Function, cands: np.ndarray) -> np.ndarray:
        """``uid()``'s literal uids and variables, each searched for the
        candidates; what any of them holds is kept, so nothing is unioned."""
        keep = np.zeros(len(cands), dtype=bool)
        if fn.uid_args:
            keep |= _member(cands, np.unique(np.asarray(fn.uid_args, dtype=np.int64)))
        for ref in fn.needs_vars:
            if ref.name in self.uid_vars:
                of = _sorted_unique(self.uid_vars[ref.name])
            elif ref.name in self.value_vars:
                vm = self.value_vars[ref.name]
                of = np.unique(np.fromiter(vm.keys(), dtype=np.int64, count=len(vm)))
            else:
                continue
            keep |= _member(cands, of)
        return cands[keep]

    def _probe_values(self, plan: Probe, cands: np.ndarray) -> Optional[np.ndarray]:
        """The candidates whose untagged value compares true against any of
        ``plan.vals`` (``_value_recheck``).  The numeric value column stands
        for the index only where it holds every value of the predicate
        (``whole``), untagged (``langless``), and values and tokens order
        alike (``wallclock``); else None."""
        fn = plan.fn
        va = self.arenas.values(fn.attr)
        if not (va.n and va.whole and va.langless and va.wallclock):
            return None
        return functools.reduce(np.union1d, [
            self._value_recheck(fn.attr, va, cands, fn.name, val) for val in plan.vals
        ])

    def _expand_rows(self, arena, rows: np.ndarray) -> np.ndarray:
        """Union of the posting lists at ``rows`` (expand + unique),
        size-routed host/device like QueryEngine._expand — through the
        SAME calibrated break-even (query/planner.py::expand_route; the
        static expand_device_min compare when the planner is off or the
        knob is pinned), so resolver expansions are priced and recorded
        like engine-level ones."""
        from dgraph_tpu.query import planner

        rows = np.asarray(rows, dtype=np.int64)
        rows = rows[rows >= 0]
        if rows.size == 0 or arena.n_edges == 0:
            return _EMPTY
        total = int(arena.degree_of_rows(rows).sum())
        if total == 0:
            return _EMPTY
        use_device, dec = planner.expand_route(
            total, self.arenas.expand_device_min
        )
        if dec is not None:
            planner.record(self.stats, dec)
        # recorded decisions must also be CLOSED (note_outcome), or
        # resolver traffic would inflate the mispredict-rate denominator
        # with entries that can never be checked
        st = self.stats if self.stats is not None else {}
        r0 = st.get("resolver_expand_ms", 0.0)
        if not use_device:
            with obs.stage(st, "resolver_expand_ms"):
                out, _seg = arena.expand_host(rows)
                res = np.unique(out)
            planner.note_outcome(dec, (st["resolver_expand_ms"] - r0) * 1e3)
            return res
        with obs.stage(st, "resolver_expand_ms"):
            cap = ops.bucket(total)
            if hasattr(arena, "ensure_device"):
                arena.ensure_device()  # stale after incremental host deltas
            out, _seg, _t = ops.expand_csr(
                arena.offsets, arena.dst,
                ops.pad_rows(rows, ops.bucket(len(rows))), cap,
            )
            u = np.asarray(ops.sort_unique(out))
            res = u[u != SENT].astype(np.int64)
        planner.note_outcome(dec, (st["resolver_expand_ms"] - r0) * 1e3)
        return res

    def _pred_index(self, pred: str, prefer_sortable: bool) -> IndexArena:
        toks = self.store.schema.tokenizers(pred)
        if not toks:
            raise QueryError(f"predicate {pred!r} is not indexed")
        name = None
        if prefer_sortable:
            name = self.store.schema.sortable_tokenizer(pred)
        if name is None:
            name = toks[0]
        return self.arenas.index(pred, name)

    def _typed_value(self, pred: str, raw: str) -> TypedValue:
        tid = self.store.schema.type_of(pred)
        if tid == TypeID.DEFAULT:
            tid = TypeID.STRING
        return convert(TypedValue(TypeID.STRING, raw), tid)

    def _host_recheck(self, pred: str, uids: np.ndarray, op: str, val: TypedValue, lang: str = "") -> np.ndarray:
        """``uids`` whose value compares true, one store probe a uid: the
        recheck of lang-tagged functions and predicates (a uid's value is
        chosen along the lang chain), and of the few values the numeric
        column cannot hold exactly."""
        st = self.stats if self.stats is not None else {}
        INDEX_RECHECK_UIDS.add("value", len(uids))
        out = []
        langs = lang.split(",") if lang else [""]
        with obs.stage(st, "recheck_ms"):
            for u in uids.tolist():
                v = None
                for l in langs:
                    v = (
                        self.store.any_value(pred, int(u))
                        if l == "."
                        else self.store.value(pred, int(u), l)
                    )
                    if v is not None:
                        break
                if v is not None and compare_vals(op, v, val):
                    out.append(u)
        return np.array(out, dtype=np.int64)

    def _value_recheck(self, pred: str, va, uids: np.ndarray, op: str, val: TypedValue) -> np.ndarray:
        """``uids`` whose untagged value compares true against ``val``, as
        one vector compare over ``va``, the predicate's numeric value
        column (the ValueArena host mirror device ordering sorts by).  A
        datetime is its UTC timestamp there, exact to the microsecond
        within ``_TS_EXACT`` seconds of the epoch, an int within 2**53
        (``_exact_below``); the few uids the column holds inexactly are
        compared one by one.  A uid missing from the column holds no value
        where the column holds every value, untagged (``whole`` and
        ``langless``), and fails; elsewhere it too is compared one by one."""
        bound = numeric(val)
        if bound is None or not va.n:
            return self._host_recheck(pred, uids, op, val)
        lim = _exact_below(val.tid)
        st = self.stats if self.stats is not None else {}
        with obs.stage(st, "recheck_ms"):
            pos = np.minimum(np.searchsorted(va.h_src, uids), va.n - 1)
            hit = va.h_src[pos] == uids
            col = va.h_vals[pos]
            exact = hit & (np.abs(col) < lim) & (abs(bound) < lim)
            kept = uids[exact & _VEC_CMP[op](col, bound)]
        INDEX_RECHECK_UIDS.add("value", int(exact.sum()))
        odd = uids[~exact & (hit | (not (va.whole and va.langless)))]
        if len(odd):
            kept = np.union1d(kept, self._host_recheck(pred, odd, op, val))
        return kept

    def _split_recheck(self, pred: str, idx: IndexArena, lo: int, hi: int,
                       token, op: str, val: TypedValue) -> np.ndarray:
        """A lossy sortable index's rows [lo, hi) for ``op`` against
        ``val``: a row whose token lies strictly inside the range holds
        only matches and is taken whole; only the bound's own token row
        can hold near-misses, and it alone is compared against the values
        (a uid holds one untagged value, so it sits in one row: a miss of
        that row is a miss).  Where the value column's wall clock is not
        its UTC instant (a datetime with an offset, in the column or in the
        bound) tokens do not order as the values do, and every candidate is
        compared."""
        cand = self._expand_rows(idx.csr, np.arange(lo, hi))
        va = self.arenas.values(pred)
        if not (va.wallclock and _wallclock(val)):
            return self._value_recheck(pred, va, cand, op, val)
        b = idx.row_of(token)
        if not lo <= b < hi:
            INDEX_RECHECK_UIDS.add("token", len(cand))
            return cand
        edge = self._expand_rows(idx.csr, np.array([b]))
        INDEX_RECHECK_UIDS.add("token", len(cand) - len(edge))
        missed = np.setdiff1d(
            edge, self._value_recheck(pred, va, edge, op, val), assume_unique=True
        )
        if not len(missed):
            return cand
        keep = np.ones(len(cand), dtype=bool)
        keep[np.searchsorted(cand, missed)] = False
        return cand[keep]

    # -- function families ---------------------------------------------------

    def _ineq(self, fn: Function) -> np.ndarray:
        if not fn.args:
            raise QueryError(f"{fn.name} needs a value argument")
        out = _EMPTY
        for raw in self._ineq_args(fn):
            out = np.union1d(out, self._ineq_one(fn, raw))
        return out

    @staticmethod
    def _ineq_args(fn: Function) -> List[str]:
        """The raw values an inequality compares against."""
        # eq may take multiple values — varargs or a bracket list, both
        # meaning "any of" (gql parseFunction list args)
        vals: List[str] = []
        for raw in fn.args if fn.name == "eq" else fn.args[:1]:
            if fn.name == "eq" and raw.startswith("["):
                try:
                    items = json.loads(raw)
                except json.JSONDecodeError:
                    vals.append(raw)
                    continue
                for x in items:
                    # the bracket-list parser floats all numbers (geo
                    # coords); integral floats must round-trip as ints or
                    # int-typed predicates choke on "19.0"
                    if isinstance(x, float) and x.is_integer():
                        vals.append(str(int(x)))
                    else:
                        vals.append(str(x))
                continue
            vals.append(raw)
        return vals

    @staticmethod
    def _ineq_rows(idx: IndexArena, tk, op: str, token) -> Tuple[int, int]:
        """A sortable index's token rows [lo, hi) that can hold uids
        comparing true: a lossy token's row holds values on both sides of
        the bound, so lt/gt keep it and the recheck decides."""
        if op == "eq":
            return idx.row_range(lo=token, hi=token)
        if op in ("le", "lt"):
            return idx.row_range(hi=token, hi_open=op == "lt" and not tk.lossy)
        return idx.row_range(lo=token, lo_open=op == "gt" and not tk.lossy)

    def _ineq_one(self, fn: Function, raw: str) -> np.ndarray:
        pred, op = fn.attr, fn.name
        val = self._typed_value(pred, raw)
        idx = self._pred_index(pred, prefer_sortable=True)
        tk = tokmod.get_tokenizer(idx.tokenizer)
        if op == "eq" and not tk.sortable:
            # term/fulltext-indexed eq: token intersection + exact recheck.
            # fulltext tokens reduce under the function's @lang tag, the
            # same per-language analyzer the index build used
            # (tok.tokens_for_value_lang) — mismatched stemmers would
            # miss every lang-tagged value
            toks = tokmod.tokens_for_value_lang(tk.name, val, fn.lang)
            rows = [idx.row_of(t) for t in toks]
            if any(r < 0 for r in rows) or not rows:
                return _EMPTY
            sets = [self._expand_rows(idx.csr, np.array([r])) for r in rows]
            # size-routed k-way intersection (query/joinplan.py): the
            # candidates came off-device — above the gate they stay
            # there for ONE batched intersect instead of a host fold
            from dgraph_tpu.query.joinplan import kway_intersect

            cand = kway_intersect(sets, stats=self.stats)
            return self._host_recheck(pred, cand, "eq", val, fn.lang)
        if not tk.sortable and op != "eq":
            raise QueryError(
                f"inequality on {pred!r} needs a sortable index (have {idx.tokenizer})"
            )
        token = tk.fn(val)[0]
        lo, hi = self._ineq_rows(idx, tk, op, token)
        if fn.lang or self._pred_has_langs(pred):
            # lang-tagged functions must verify the match against the
            # TAGGED value only; and an UNtagged function over a predicate
            # with tagged values must re-check against the untagged value
            # — the index spans every language (task.go:612-661 lang
            # filters), so a tagged token can land inside the untagged
            # comparison range
            cand = self._expand_rows(idx.csr, np.arange(lo, hi))
            return self._host_recheck(pred, cand, op, val, fn.lang)
        if tk.lossy:
            return self._split_recheck(pred, idx, lo, hi, token, op, val)
        return self._expand_rows(idx.csr, np.arange(lo, hi))

    def _pred_has_langs(self, pred: str) -> bool:
        """Does the predicate carry any lang-tagged values?  Cached on the
        PredicateData snapshot (replaced wholesale on dirty refresh)."""
        pd = self.store.peek(pred)
        if pd is None:
            return False
        flag = getattr(pd, "_has_langs", None)
        if flag is None:
            flag = any(lang for (_u, lang) in pd.values.keys())
            try:
                pd._has_langs = flag
            except AttributeError:
                pass  # slotted/foreign store impl: recompute per call
        return flag

    def _terms(self, fn: Function, tokenizer: str, all_of: bool) -> np.ndarray:
        if not fn.args:
            raise QueryError(f"{fn.name} needs a value argument")
        toks_avail = self.store.schema.tokenizers(fn.attr)
        if tokenizer not in toks_avail:
            raise QueryError(f"{fn.name} on {fn.attr!r} needs @index({tokenizer})")
        idx = self.arenas.index(fn.attr, tokenizer)
        text = " ".join(fn.args)
        qtoks = (
            tokmod.term_tokens(text)
            if tokenizer == "term"
            else tokmod.fulltext_tokens(text, fn.lang.split(",")[0] if fn.lang else "en")
        )
        if not qtoks:
            return _EMPTY
        sets = []
        for t in qtoks:
            self.checkpoint()
            r = idx.row_of(t)
            if r < 0:
                if all_of:
                    return _EMPTY
                sets.append(_EMPTY)
            else:
                sets.append(self._expand_rows(idx.csr, np.array([r])))
        if all_of:
            # allofterms = k-way intersection of token posting sets:
            # size-routed through the join tier (query/joinplan.py)
            from dgraph_tpu.query.joinplan import kway_intersect

            return kway_intersect(sets, stats=self.stats)
        out = sets[0]
        for s in sets[1:]:
            out = np.union1d(out, s)
        return out

    def _regexp(self, fn: Function) -> np.ndarray:
        if not fn.args:
            raise QueryError("regexp needs a pattern")
        raw = fn.args[0]
        flags = 0
        if not raw.startswith("/") or "/" not in raw[1:]:
            # reference requires /pattern/[flags] (parser.go regexp arg)
            raise QueryError(f"regexp argument must be /pattern/: got {raw!r}")
        body, _, tail = raw[1:].rpartition("/")
        pat = body
        if "i" in tail:
            flags |= re.IGNORECASE
        try:
            rx = re.compile(pat, flags)
        except re.error as e:
            raise QueryError(f"bad regexp {pat!r}: {e}")
        # trigram candidate generation (worker/trigram.go:36): extract
        # literal runs >= 3 chars and AND their trigram lists.  Only sound
        # for pure concatenation with exact case: alternation/optional
        # groups make runs disjunctive, and the index stores case-
        # preserving trigrams — in those cases fall back to a full scan
        # (still correct: the regex re-check below is exact).
        cand = None
        prunable = (
            "trigram" in self.store.schema.tokenizers(fn.attr)
            and not (flags & re.IGNORECASE)
            and not re.search(r"[|?]|\(\?", pat)
        )
        if prunable:
            idx = self.arenas.index(fn.attr, "trigram")
            tsets = []
            for lit in _literal_runs(pat):
                for tg in tokmod.trigram_tokens(lit):
                    self.checkpoint()
                    r = idx.row_of(tg)
                    tsets.append(
                        self._expand_rows(idx.csr, np.array([r]))
                        if r >= 0
                        else _EMPTY
                    )
            if tsets:
                # trigram AND: one size-routed k-way pass over every
                # literal's posting set (query/joinplan.py)
                from dgraph_tpu.query.joinplan import kway_intersect

                cand = kway_intersect(tsets, stats=self.stats)
        if cand is None:
            pd = self.store.peek(fn.attr)
            cand = (
                np.array(sorted({u for (u, _l) in pd.values.keys()}), dtype=np.int64)
                if pd
                else _EMPTY
            )
        langs = fn.lang.split(",") if fn.lang else [""]
        if langs == [""]:
            # untagged fast path: ONE searchsorted over the cached value
            # mirror replaces the per-uid store.value dict chain; the
            # remaining per-candidate cost is rx.search itself (C code)
            pd = self.store.peek(fn.attr)
            if pd is None or not len(cand):
                return _EMPTY
            hit, pos, mv = pd.untagged_lookup(cand)
            uids = cand[hit]
            vals = mv[pos[hit]]
            keep = np.fromiter(
                (rx.search(str(v.value)) is not None for v in vals),
                dtype=bool,
                count=len(vals),
            )
            return np.unique(uids[keep])
        out = []
        for u in cand.tolist():
            for l in langs:
                v = (
                    self.store.any_value(fn.attr, int(u))
                    if l == "."
                    else self.store.value(fn.attr, int(u), l)
                )
                if v is not None and rx.search(str(v.value)):
                    out.append(u)
                    break
        return np.array(sorted(set(out)), dtype=np.int64)

    def _geo(self, fn: Function) -> np.ndarray:
        if not fn.args:
            raise QueryError(f"{fn.name} needs coordinates")
        coords = json.loads(fn.args[0])
        max_m = float(fn.args[1]) if len(fn.args) > 1 else None
        if fn.name == "near":
            q = geomod.Geom("Point", tuple(coords))
        elif isinstance(coords[0], (int, float)):
            q = geomod.Geom("Point", tuple(coords))
        else:
            ring = tuple(tuple(c) for c in (coords[0] if isinstance(coords[0][0], list) else coords))
            q = geomod.Geom("Polygon", ring)
        if "geo" not in self.store.schema.tokenizers(fn.attr):
            raise QueryError(f"{fn.name} on {fn.attr!r} needs @index(geo)")
        idx = self.arenas.index(fn.attr, "geo")
        if fn.name == "near":
            if max_m is None:
                raise QueryError("near needs a distance argument")
            # candidate cells: the query point's ancestors plus neighbors
            # found via the coarse cells of an expanded bbox
            import math as _m

            dlat = max_m / 111_320.0  # meters per degree latitude
            lng, lat = q.coords
            # longitude degrees shrink by cos(lat) away from the equator
            dlng = dlat / max(_m.cos(_m.radians(lat)), 1e-6)
            ring = (
                (lng - dlng, lat - dlat), (lng + dlng, lat - dlat),
                (lng + dlng, lat + dlat), (lng - dlng, lat + dlat),
            )
            cells = geomod.polygon_cells(ring)
        else:
            cells = geomod.query_cells(q)
        cand = None
        sets = []
        for c in cells:
            self.checkpoint()
            r = idx.row_of(c)
            if r >= 0:
                sets.append(self._expand_rows(idx.csr, np.array([r])))
        cand = np.unique(np.concatenate(sets)) if sets else _EMPTY
        # exact post-filter (types/geofilter.go FilterGeoUids:325),
        # vectorized: ONE searchsorted over the untagged value mirror
        # replaces the per-uid store.value probe, and near()'s haversine
        # runs over the whole Point column in one numpy pass.  Polygon
        # predicates (within/contains/intersects) still walk per geometry
        # — ring math is data-dependent — but over mirror-gathered values.
        pd = self.store.peek(fn.attr)
        if pd is None or not len(cand):
            return _EMPTY
        hit, pos, mv = pd.untagged_lookup(cand)
        uids = cand[hit]
        geoms = mv[pos[hit]]
        if fn.name == "near":
            is_pt = np.fromiter(
                (v.value.kind == "Point" for v in geoms),
                dtype=bool,
                count=len(geoms),
            )
            uids = uids[is_pt]
            pts = geoms[is_pt]
            if not len(pts):
                return _EMPTY
            lngs = np.fromiter((v.value.coords[0] for v in pts), np.float64, len(pts))
            lats = np.fromiter((v.value.coords[1] for v in pts), np.float64, len(pts))
            keep = geomod.haversine_m_vec(q.coords, lngs, lats) <= max_m
        else:
            keep = np.fromiter(
                (geomod.matches_filter(fn.name, q, v.value) for v in geoms),
                dtype=bool,
                count=len(geoms),
            )
        return np.sort(uids[keep])

    def _count_compare(self, fn: Function, candidates: Optional[np.ndarray]) -> np.ndarray:
        if not fn.args:
            raise QueryError("count comparison needs a value")
        n = int(fn.args[0])
        arena = self.arenas.data(fn.attr)
        degs = arena.h_offsets[1:] - arena.h_offsets[:-1]
        src = arena.h_src
        # incremental deletes leave degree-0 rows in patched arenas; a
        # row-less uid and a zero-degree row must behave identically
        # (count-0 matches only through the explicit candidates union)
        live = degs > 0
        src, degs = src[live], degs[live]
        op = fn.name
        mask = {
            "eq": degs == n,
            "le": degs <= n,
            "lt": degs < n,
            "ge": degs >= n,
            "gt": degs > n,
        }[op]
        out = src[mask]
        # uids with zero edges have no arena row; include them whenever a
        # count of 0 satisfies the comparison (ge 0, le N, eq 0, ...)
        zero_satisfies = {
            "eq": n == 0, "le": 0 <= n, "lt": 0 < n, "ge": 0 >= n, "gt": 0 > n,
        }[op]
        if candidates is not None and zero_satisfies:
            out = np.union1d(out, np.setdiff1d(candidates, src))
        return self._bound(out, candidates)

    def _val_var_compare(self, fn: Function, candidates: Optional[np.ndarray]) -> np.ndarray:
        vmap = self.value_vars.get(fn.attr, {})
        if not fn.args:
            raise QueryError(f"{fn.name}(val({fn.attr})) needs a value")
        target_raw = fn.args[0]
        out = []
        uids = candidates if candidates is not None else np.array(sorted(vmap), dtype=np.int64)
        for u in uids.tolist():
            v = vmap.get(int(u))
            if v is None:
                continue
            tv = (
                convert(TypedValue(TypeID.STRING, target_raw), v.tid)
                if not isinstance(target_raw, TypedValue)
                else target_raw
            )
            if compare_vals(fn.name, v, tv):
                out.append(u)
        return np.array(out, dtype=np.int64)

    def _checkpwd(self, fn: Function, candidates: Optional[np.ndarray]) -> np.ndarray:
        from dgraph_tpu.models.password import verify_password

        out = []
        uids = candidates if candidates is not None else _EMPTY
        for u in uids.tolist():
            v = self.store.value(fn.attr, int(u))
            if v is not None and verify_password(fn.args[0], str(v.value)):
                out.append(u)
        return np.array(out, dtype=np.int64)

    def _uid_in(self, fn: Function, candidates: Optional[np.ndarray]) -> np.ndarray:
        """uid_in(pred, uid): candidates having a ``pred`` edge to uid."""
        if not fn.args and not fn.uid_args:
            raise QueryError("uid_in needs a target uid")
        target = fn.uid_args[0] if fn.uid_args else int(fn.args[0], 0)
        rev = self.arenas.reverse(fn.attr)
        rows = rev.rows_for_uids_host(np.array([target], dtype=np.int64))
        sources = self._expand_rows(rev, rows)
        return self._bound(sources, candidates)


def _literal_runs(pattern: str) -> List[str]:
    """Literal substrings of a regex usable for trigram candidates —
    conservative: strip groups/classes/escapes; runs must not merge
    across removed metacharacters (separator is \\x00, never space,
    since literals may contain spaces)."""
    s = re.sub(r"\\.|\[[^\]]*\]|\(\?[^)]*\)", "\x00", pattern)
    # anything directly before *, ?, or {m,n} may occur zero (or many)
    # times — NOT a required literal; drop it with its quantifier
    # (codesearch's RegexpQuery does the same cut).  Groups resolve
    # innermost-first: a quantified group is dropped whole, a plain
    # group is transparent for its contents but splits runs at its
    # edges (conservative), iterated to a fixpoint for nesting.
    prev = None
    while prev != s:
        prev = s
        s = re.sub(r"\([^()]*\)(\{[^}]*\}|[*?+])", "\x00", s)
        s = re.sub(r"\(([^()]*)\)", "\x00\\1\x00", s)
    s = re.sub(r".\{[^}]*\}", "\x00", s)
    s = re.sub(r".[*?]", "\x00", s)
    s = re.sub(r"[(){}|^$.*+?]", "\x00", s)
    return [seg for seg in s.split("\x00") if len(seg.strip()) >= 3]
