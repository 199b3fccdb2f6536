"""Layer 3: the range certifier — interval abstract interpretation over
the traced step program.

Layers 1-2 (jaxpr_check.py, lint.py) verify SHAPE-level discipline:
dtypes, donation, purity, mirrors. What nothing checked mechanically
until now is VALUE-level safety: every `spec.narrow_horizon_us` cap —
raft's `65_535 * election_lo_us // N`, twopc's `32_767 * 1_000` — was a
hand-derived formula in a comment, enforced by an engine refusal whose
correctness rested on pencil-and-paper reasoning about adversarial fault
schedules. This module closes that gap with a classical interval
abstract interpretation (the Cousot/Astrée tradition, built for exactly
this silent-wraparound bug class) over the SAME traced donated
`_step_split` jaxpr the Layer-1 rules walk — one shared trace per
workload across all rules.

The abstract domain is a per-variable integer interval extended with two
flags: `inf` (the value may additionally be exactly the INF_US sentinel
— disarmed timers, empty pool slots, disabled chaos) and `poison` (the
value may hold sentinel-derived junk: the engine's compute-then-discard
idiom runs arithmetic over sentinel lanes and masks the result away, so
arithmetic on a maybe-sentinel operand yields values the finite interval
cannot claim). Input intervals seed from three sources: the engine's own
documented invariants (`engine.interval_hints`: live time offsets stay
below INF_GUARD — the rebase guard's exact premise), the spec's
machine-readable `rate_floors` declarations, and an interval run of the
real `_init` program (init bounds are DERIVED, not assumed). Protocol
state then iterates to a widening fixpoint over the step loop
(threshold widening: dtype boundaries, powers of two, REBASE_US).

Per-workload certificates:

  (a) narrow fields — every `spec.narrow_fields` entry is certified
      either step-CLOSED (its reachable interval never escapes the
      narrow dtype: enums, masks, ids), HARD-capped (a declared
      horizon-independent bound fits the dtype), or RATE-bounded: the
      interpreter verifies the per-event increment (`inc`) against the
      step program, and the certified safe horizon
      `(dtype_max - init_max) * floor_us // (ratchet * inc)` must cover
      the spec's declared `narrow_horizon_us` — both derated for clock
      skew through the SAME `spec.derate_horizon` the engine refusal
      uses. The hand-derived formulas become checked, not trusted.
  (b) clock no-wrap — given the rebase invariant (offsets < INF_GUARD),
      no signed-int arithmetic in the virtual-time cone (TIME taint,
      same lattice as Layer 1) can exceed int32 — including the spike /
      reorder latency adders and the exact integer-ppm skew scaling at
      the maximal traced config.
  (c) index bounds — every dynamic index site (gather / scatter /
      dynamic_slice: ring cursors, occurrence counters, pool slots) is
      statically in-bounds for its array extent. Sites lowered with
      PROMISE_IN_BOUNDS (undefined behavior when violated) MUST prove;
      sites with defined out-of-bounds semantics (FILL_OR_DROP / CLIP)
      are enumerated with status `guarded` when intervals alone cannot
      prove them.
  (d) `_sum64` — the engine's 65536-lane exactness guard is rederived
      from the traced reduction's own interval transfer
      (max_lanes = u32_max // addend_max) instead of asserted.

What is and is not provable (docs/analysis.md#layer-3): interval
analysis is non-relational. Two documented assumptions close the gaps:
the MESSAGE-COPY induction (every in-flight payload word is a copy of an
in-range protocol value; payload leaves are seeded accordingly, and a
narrow store provable only under that premise is reported with status
`assumed-copy`, never silently) and ONE-HOT routing (a dot_general whose
mask operand is 0/1-valued is modeled as selection — the engine's
documented pool-routing idiom — not as a subset sum). Violations carry a
backward witness slice naming the contributing carry leaves, same UX as
the rng-taint rule.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import numpy as np

import jax
from jax.extend import core as jcore
import jax.numpy as jnp

from . import RuleResult
from .jaxprutil import (
    TIME, TaintMap, _sub_jaxprs, backward_invars, is_literal, lit_value,
)

NEG_INF = float("-inf")
POS_INF = float("inf")

INF_US_VAL = 2**31 - 1  # spec.INF_US
INF_GUARD_VAL = 1 << 30  # spec.INF_GUARD: live-offset / sentinel boundary


class Iv(NamedTuple):
    """One abstract value: a finite interval plus sentinel flags.

    `lo > hi` encodes an EMPTY finite part (a value that is only ever
    the sentinel). `inf` — may additionally be exactly INF_US. `poison`
    — may additionally hold sentinel-derived junk (arithmetic that ran
    over a sentinel lane before the mask discarded it); checks skip
    poisoned operands rather than report junk wraps as findings."""

    lo: Any
    hi: Any
    inf: bool = False
    poison: bool = False

    @property
    def empty(self) -> bool:
        return self.lo > self.hi

    def render(self) -> str:
        fin = "()" if self.empty else f"[{self.lo}, {self.hi}]"
        return fin + ("+INF" if self.inf else "") + (
            "+poison" if self.poison else ""
        )


EMPTY = Iv(POS_INF, NEG_INF)
BOOL_IV = Iv(0, 1)


def iv(lo, hi, inf: bool = False, poison: bool = False) -> Iv:
    return Iv(lo, hi, inf, poison)


def dtype_range(dt) -> Iv:
    dt = np.dtype(dt)
    if dt.kind == "b":
        return BOOL_IV
    if dt.kind == "u":
        return Iv(0, int(2 ** (8 * dt.itemsize) - 1))
    if dt.kind == "i":
        n = 8 * dt.itemsize
        return Iv(-(2 ** (n - 1)), 2 ** (n - 1) - 1)
    return Iv(NEG_INF, POS_INF)  # floats: unbounded


def fits(x: Iv, dt) -> bool:
    """The finite part of `x` fits dtype `dt` (sentinel flags excluded:
    INF_US is the legal i32 sentinel, poison is judged at its source)."""
    if x.empty:
        return True
    r = dtype_range(dt)
    return x.lo >= r.lo and x.hi <= r.hi


def join(a: Iv, b: Iv) -> Iv:
    return Iv(
        min(a.lo, b.lo), max(a.hi, b.hi),
        a.inf or b.inf, a.poison or b.poison,
    )


# threshold-widening ladders: dtype boundaries, small enums, powers of
# two, and the engine's own landmark constants (REBASE_US, INF_GUARD)
_HI_LADDER = (
    [0, 1, 2, 3, 7, 15, 31, 63, 127, 255, 511, 1023, 4095, 16383, 32767,
     65535, 1 << 20, 1 << 24, 1 << 28, (1 << 30) - 1, 2**31 - 1,
     2**32 - 1]
)
_LO_LADDER = (
    [0, -1, -2, -3, -7, -15, -31, -127, -128, -255, -32768, -(1 << 20),
     -(2**31)]
)


def widen(old: Iv, new: Iv) -> Iv:
    """old ∇ new: jump escaped bounds to the next ladder threshold."""
    j = join(old, new)
    lo, hi = j.lo, j.hi
    if hi > old.hi:
        hi = next((t for t in _HI_LADDER if t >= j.hi), POS_INF)
    if lo < old.lo:
        lo = next((t for t in _LO_LADDER if t <= j.lo), NEG_INF)
    return Iv(lo, hi, j.inf, j.poison)


def _flags(*xs: Iv, poison_on_inf: bool = True) -> Tuple[bool, bool]:
    """(inf, poison) for an ARITHMETIC result: sentinels don't survive
    arithmetic as sentinels — they become junk (poison)."""
    p = any(x.poison for x in xs)
    if poison_on_inf:
        p = p or any(x.inf for x in xs)
    return False, p


def _arith(xs: Sequence[Iv], lo, hi) -> Iv:
    if any(x.empty for x in xs):
        # finite part vacuous: the value is sentinel-only junk
        return Iv(POS_INF, NEG_INF, False, True)
    _, p = _flags(*xs)
    return Iv(lo, hi, False, p)


def iv_add(a: Iv, b: Iv) -> Iv:
    return _arith((a, b), a.lo + b.lo, a.hi + b.hi)


def iv_sub(a: Iv, b: Iv) -> Iv:
    return _arith((a, b), a.lo - b.hi, a.hi - b.lo)


def _mul1(x, y):
    if x in (NEG_INF, POS_INF) and y == 0:
        return 0
    if y in (NEG_INF, POS_INF) and x == 0:
        return 0
    return x * y


def iv_mul(a: Iv, b: Iv) -> Iv:
    cs = [_mul1(x, y) for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
    return _arith((a, b), min(cs), max(cs))


def _trunc_div(x, m):
    if m == 0:
        return 0
    if x in (NEG_INF, POS_INF) or m in (NEG_INF, POS_INF):
        q = x / m if m != 0 else 0
        return q if q in (NEG_INF, POS_INF) else int(q)
    q = abs(x) // abs(m)
    return q if (x >= 0) == (m > 0) else -q


def iv_div(a: Iv, b: Iv, out_dt) -> Iv:
    if not a.empty and not b.empty and (b.lo > 0 or b.hi < 0):
        cs = [_trunc_div(x, y) for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
        return _arith((a, b), min(cs), max(cs))
    r = dtype_range(out_dt)  # divisor may be 0: backend-defined
    return Iv(r.lo, r.hi, False, a.poison or b.poison or a.inf or b.inf)


def iv_rem(a: Iv, b: Iv, out_dt) -> Iv:
    """lax.rem: sign follows the dividend, |r| < |divisor| — but ONLY
    for a provably nonzero divisor: rem-by-zero is backend-defined (the
    same fallback iv_div takes), so a maybe-zero divisor yields the
    dtype range."""
    if a.empty or b.empty:
        return Iv(POS_INF, NEG_INF, False, True)
    m = max(abs(b.lo), abs(b.hi))
    maybe_zero = not (b.lo > 0 or b.hi < 0)
    if maybe_zero or m in (NEG_INF, POS_INF):
        r = dtype_range(out_dt)
        return Iv(r.lo, r.hi, False, a.poison or b.poison or a.inf or b.inf)
    lo = 0 if a.lo >= 0 else -(m - 1) if m > 0 else 0
    hi = 0 if a.hi <= 0 else (m - 1) if m > 0 else 0
    if a.lo >= 0:
        hi = min(hi, a.hi)  # dividend smaller than divisor is unchanged
    return _arith((a, b), lo, hi)


def _eff_hi(x: Iv):
    """Upper bound including a possible INF_US sentinel."""
    if x.inf:
        return INF_US_VAL
    return x.hi


def _eff_lo(x: Iv):
    if x.empty:
        return INF_US_VAL if x.inf else POS_INF
    return x.lo


def iv_min(a: Iv, b: Iv) -> Iv:
    lo = min(_eff_lo(a), _eff_lo(b))
    hi = min(_eff_hi(a) if not a.empty or a.inf else POS_INF,
             _eff_hi(b) if not b.empty or b.inf else POS_INF)
    inf = a.inf and b.inf and hi >= INF_US_VAL
    if inf:
        # min may be exactly the sentinel only when both sides can be
        fin_lo = min(a.lo, b.lo)
        fin_hi = max(a.hi, b.hi)  # finite candidates from either side
        return Iv(fin_lo, fin_hi, True, a.poison or b.poison)
    return Iv(lo, hi, False, a.poison or b.poison)


def iv_max(a: Iv, b: Iv) -> Iv:
    inf = a.inf or b.inf
    lo = max(_eff_lo(a) if not a.empty else NEG_INF,
             _eff_lo(b) if not b.empty else NEG_INF)
    if lo in (POS_INF,):
        lo = NEG_INF
    hi = max(a.hi, b.hi)
    if inf:
        return Iv(lo, hi, True, a.poison or b.poison)
    return Iv(lo, hi, False, a.poison or b.poison)


def _bit_hull(hi) -> int:
    """Smallest 2^k - 1 >= hi (the bitwise-or/xor upper bound)."""
    if hi in (NEG_INF, POS_INF):
        return POS_INF
    return (1 << int(hi).bit_length()) - 1


def iv_of_value(val, dt) -> Iv:
    """Interval of a concrete constant/literal, sentinel-aware for i32."""
    arr = np.asarray(val)
    if arr.size == 0:
        return EMPTY
    if arr.dtype.kind == "b":
        return Iv(int(arr.min()), int(arr.max()))
    if arr.dtype.kind not in "iu" or np.dtype(dt).kind not in "iu":
        try:
            return Iv(float(arr.min()), float(arr.max()))
        except (TypeError, ValueError):
            return dtype_range(dt)
    vals = arr.astype(np.int64)
    if np.dtype(dt) == np.int32:
        finite = vals[vals < INF_GUARD_VAL]
        has_inf = bool((vals == INF_US_VAL).any())
        guard_vals = vals[(vals >= INF_GUARD_VAL) & (vals != INF_US_VAL)]
        if guard_vals.size:  # non-sentinel large constants stay finite
            finite = vals
            has_inf = False
        if finite.size == 0:
            return Iv(POS_INF, NEG_INF, has_inf, False)
        return Iv(int(finite.min()), int(finite.max()), has_inf, False)
    return Iv(int(vals.min()), int(vals.max()))


# ------------------------------------------------------------ the machine


class IndexSite(NamedTuple):
    """One dynamic-index site examined by the bounds certificate."""

    prim: str
    mode: str
    index_iv: Iv
    allowed: Tuple[int, int]
    ok: bool
    where_eqn: Any  # enclosing top-level eqn, for the backward witness


class IntervalMap:
    """Forward interval propagation over a closed jaxpr.

    Same recursion skeleton as jaxprutil.TaintMap: sub-jaxprs (jit /
    cond / while / scan) are entered with operand intervals, `top_eqn`
    names the enclosing top-level equation for witness slicing, and loop
    bodies iterate to a (threshold-widened) fixpoint. `on_eqn(eqn,
    in_ivs, out_ivs, top_eqn)` fires per equation on every pass; checks
    that must not double-count run on the caller's FINAL pass only."""

    def __init__(
        self,
        closed: jcore.ClosedJaxpr,
        invar_ivs: Sequence[Iv],
        on_eqn: Optional[Callable] = None,
    ) -> None:
        self.env: Dict[Any, Iv] = {}
        self.on_eqn = on_eqn
        self.index_sites: List[IndexSite] = []
        self.eqns_seen = 0
        # contraction sites modeled under the ONE-HOT assumption (dot
        # routing / masked sums): counted so the certificate can surface
        # how much of the claim rests on that premise, like assumed-copy
        self.onehot_sites = 0
        self._defs: Dict[Any, Any] = {}  # var -> defining eqn
        jaxpr = closed.jaxpr
        self._seed_consts(jaxpr, closed.consts)
        if len(invar_ivs) != len(jaxpr.invars):
            raise ValueError(
                f"{len(invar_ivs)} seed intervals for "
                f"{len(jaxpr.invars)} invars"
            )
        for v, x in zip(jaxpr.invars, invar_ivs):
            self.env[v] = x
        self._jaxpr = jaxpr
        self.top_eqn: Any = None

    def _seed_consts(self, jaxpr, consts) -> None:
        for cv, val in zip(jaxpr.constvars, consts):
            self.env[cv] = iv_of_value(val, getattr(cv.aval, "dtype", None))
        for cv in jaxpr.constvars[len(consts):]:
            self.env.setdefault(cv, dtype_range(cv.aval.dtype))

    def read(self, atom: Any) -> Iv:
        if is_literal(atom):
            return iv_of_value(atom.val, getattr(atom.aval, "dtype", None))
        got = self.env.get(atom)
        if got is None:
            return dtype_range(getattr(atom.aval, "dtype", None))
        return got

    def run(self) -> "IntervalMap":
        self.top_eqn = None
        self._run(self._jaxpr, top=True)
        return self

    # -- recursion ---------------------------------------------------------

    def _run(self, jaxpr: jcore.Jaxpr, top: bool = False) -> None:
        for eqn in jaxpr.eqns:
            if top:
                self.top_eqn = eqn
            self.eqns_seen += 1
            in_ivs = [self.read(v) for v in eqn.invars]
            name = eqn.primitive.name
            if name == "jit":
                outs = self._run_call(eqn.params["jaxpr"], in_ivs)
            elif name == "cond":
                outs = self._run_cond(eqn, in_ivs)
            elif name == "while":
                outs = self._run_while(eqn, in_ivs)
            elif name == "scan":
                outs = self._run_scan(eqn, in_ivs)
            elif _sub_jaxprs(eqn):
                # unknown higher-order primitive: sound fallback
                for sub, consts in _sub_jaxprs(eqn):
                    self._seed_consts(sub, consts)
                    for ivr in sub.invars:
                        self.env[ivr] = dtype_range(
                            getattr(ivr.aval, "dtype", None)
                        )
                    self._run(sub)
                outs = [
                    dtype_range(getattr(ov.aval, "dtype", None))
                    for ov in eqn.outvars
                ]
            else:
                outs = self._transfer(eqn, in_ivs)
            for ov, x in zip(eqn.outvars, outs):
                self.env[ov] = x
                self._defs[ov] = eqn
            if self.on_eqn is not None:
                self.on_eqn(eqn, in_ivs, outs, self.top_eqn)

    def _run_call(self, closed_sub, in_ivs) -> List[Iv]:
        sub = closed_sub.jaxpr
        self._seed_consts(sub, closed_sub.consts)
        for v, x in zip(sub.invars, in_ivs):
            self.env[v] = x
        self._run(sub)
        return [self.read(ov) for ov in sub.outvars]

    def _run_cond(self, eqn, in_ivs) -> List[Iv]:
        branches = eqn.params["branches"]
        pred = in_ivs[0]
        outs: Optional[List[Iv]] = None
        for bi, br in enumerate(branches):
            if not pred.empty and not (pred.lo <= bi <= pred.hi):
                continue  # branch statically unreachable
            res = self._run_call(br, in_ivs[1:])
            outs = res if outs is None else [
                join(a, b) for a, b in zip(outs, res)
            ]
        if outs is None:
            outs = [
                dtype_range(getattr(ov.aval, "dtype", None))
                for ov in eqn.outvars
            ]
        return outs

    def _loop_fix(self, body, consts_ivs, carry0: List[Iv],
                  extra: Sequence[Iv] = ()) -> List[Iv]:
        dts = [getattr(v.aval, "dtype", None) for v in body.jaxpr.invars[
            len(consts_ivs): len(consts_ivs) + len(carry0)
        ]]
        carry = list(carry0)
        for i in range(12):
            res = self._run_call(body, consts_ivs + carry + list(extra))
            nxt = res[: len(carry)]
            grown = []
            for c, n, dt in zip(carry, nxt, dts):
                g = join(c, n)
                if i >= 6 and g != c:
                    # still growing after the ladder passes: jump to the
                    # dtype top so the final result IS a fixpoint (a
                    # non-fixpoint fallback would under-approximate the
                    # carry and silently miss in-loop wraps)
                    top = dtype_range(dt)
                    g = Iv(top.lo, top.hi, g.inf, g.poison)
                elif i >= 1:
                    g = widen(c, g)
                grown.append(g)
            if grown == carry:
                return res
            carry = grown
        return self._run_call(body, consts_ivs + carry + list(extra))

    def _run_while(self, eqn, in_ivs) -> List[Iv]:
        cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        body = eqn.params["body_jaxpr"]
        carry0 = in_ivs[cn + bn:]
        res = self._loop_fix(body, in_ivs[cn: cn + bn], carry0)
        # cond jaxpr runs for its side conditions' visit coverage
        self._run_call(eqn.params["cond_jaxpr"], in_ivs[:cn] + res)
        return [join(a, b) for a, b in zip(carry0, res)]

    def _run_scan(self, eqn, in_ivs) -> List[Iv]:
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        body = eqn.params["jaxpr"]
        consts, carry0, xs = (
            in_ivs[:nc], in_ivs[nc: nc + ncar], in_ivs[nc + ncar:],
        )
        length = int(eqn.params.get("length") or 0)
        n_body_eqns = len(body.jaxpr.eqns)
        if 0 < length * max(n_body_eqns, 1) <= 65536:
            # small static trip count: exact abstract unroll (the planted
            # wrap fixtures live here; real steps carry no scans)
            carry = list(carry0)
            ys: Optional[List[Iv]] = None
            for _ in range(length):
                res = self._run_call(body, consts + carry + xs)
                carry = res[:ncar]
                yrow = res[ncar:]
                ys = yrow if ys is None else [
                    join(a, b) for a, b in zip(ys, yrow)
                ]
            return carry + (ys or [])
        res = self._loop_fix(body, consts, list(carry0), xs)
        return [join(a, b) for a, b in zip(list(carry0) + res[ncar:],
                                           res[:ncar] + res[ncar:])]

    # -- transfer functions ------------------------------------------------

    def _transfer(self, eqn, ivs: List[Iv]) -> List[Iv]:
        name = eqn.primitive.name
        out_dt = getattr(eqn.outvars[0].aval, "dtype", None)
        h = getattr(self, f"_t_{name}", None)
        if h is not None:
            out = h(eqn, ivs, out_dt)
        else:
            out = self._t_default(eqn, ivs, out_dt)
        if not isinstance(out, list):
            out = [out]
        if len(out) != len(eqn.outvars):
            out = [
                dtype_range(getattr(ov.aval, "dtype", None))
                for ov in eqn.outvars
            ]
        return out

    def _t_default(self, eqn, ivs, out_dt):
        return [
            dtype_range(getattr(ov.aval, "dtype", None))
            for ov in eqn.outvars
        ]

    # identity / shape-only
    def _ident(self, eqn, ivs, out_dt):
        return ivs[0]

    _t_copy = _ident
    _t_device_put = _ident
    _t_reshape = _ident
    _t_squeeze = _ident
    _t_expand_dims = _ident
    _t_broadcast_in_dim = _ident
    _t_transpose = _ident
    _t_slice = _ident
    _t_rev = _ident
    _t_stop_gradient = _ident
    _t_reduce_min = _ident  # hull-preserving (incl. the inf flag)
    _t_reduce_max = _ident
    _t_sort = lambda self, eqn, ivs, out_dt: list(ivs)  # noqa: E731

    def _t_concatenate(self, eqn, ivs, out_dt):
        out = ivs[0]
        for x in ivs[1:]:
            out = join(out, x)
        return out

    _IDENT_PRIMS = frozenset({
        "device_put", "copy", "broadcast_in_dim", "reshape", "squeeze",
        "expand_dims", "stop_gradient",
    })

    def _peel(self, atom):
        """Walk `atom` back through identity ops to its source atom."""
        for _ in range(8):
            eqn = self._defs.get(atom)
            if eqn is None or eqn.primitive.name not in self._IDENT_PRIMS:
                return atom
            atom = eqn.invars[0]
        return atom

    def _affine_of(self, atom) -> Optional[Tuple[Any, int]]:
        """(base atom, offset) when `atom` is base or base +/- literal."""
        atom = self._peel(atom)
        eqn = self._defs.get(atom)
        if eqn is not None and eqn.primitive.name in ("add", "sub"):
            sign = 1 if eqn.primitive.name == "add" else -1
            a, b = eqn.invars
            for x, y, s in ((a, b, sign), (b, a, 1)):
                if sign == -1 and x is b:
                    continue  # c - x is not affine in x
                c = lit_value(y)
                if c is not None:
                    return self._peel(x), s * c
        return atom, 0

    _CMP_OPS = {"lt": "lt", "le": "le", "gt": "gt", "ge": "ge"}

    def _t_select_n(self, eqn, ivs, out_dt):
        pred, cases = ivs[0], ivs[1:]
        if not pred.empty and pred.lo == pred.hi and not pred.poison:
            k = int(pred.lo)
            if 0 <= k < len(cases):
                return cases[k]
        # branch-condition refinement for the jnp negative-index idiom
        # `select(x < c, x + d, x)`: restrict x per branch when the pred
        # compares the SAME base the branches are affine in
        if len(cases) == 2:
            refined = self._refine_binary_select(eqn, cases)
            if refined is not None:
                return refined
        live = [
            c for i, c in enumerate(cases)
            if pred.empty or pred.poison or (pred.lo <= i <= pred.hi)
        ] or cases
        out = live[0]
        for c in live[1:]:
            out = join(out, c)
        return out

    def _refine_binary_select(self, eqn, cases) -> Optional[Iv]:
        pred_eqn = self._defs.get(self._peel(eqn.invars[0]))
        if pred_eqn is None or pred_eqn.primitive.name not in self._CMP_OPS:
            return None
        xa, ca = pred_eqn.invars
        c = lit_value(ca)
        if c is None:
            return None
        base = self._peel(xa)
        x = self.read(base)
        if x.empty or x.poison:
            return None
        affs = [self._affine_of(a) for a in eqn.invars[1:]]
        if any(b is not base for b, _ in affs):
            return None
        op = pred_eqn.primitive.name
        # case index 1 = pred true, 0 = pred false
        bounds = {
            "lt": ((c, x.hi), (x.lo, c - 1)),
            "le": ((c + 1, x.hi), (x.lo, c)),
            "gt": ((x.lo, c), (c + 1, x.hi)),
            "ge": ((x.lo, c - 1), (c, x.hi)),
        }[op]
        out: Optional[Iv] = None
        for (blo, bhi), (_, off) in zip(bounds, affs):
            lo, hi = max(x.lo, blo), min(x.hi, bhi)
            if lo > hi:
                continue  # branch unreachable for this x
            piece = Iv(lo + off, hi + off, x.inf, x.poison)
            out = piece if out is None else join(out, piece)
        return out

    @staticmethod
    def _uwrap(x: Iv, out_dt) -> Iv:
        """Unsigned arithmetic wraps BY DESIGN (the murmur hash chain
        lives on u32 wrap): when the mathematical interval escapes an
        unsigned dtype, fold to the full dtype range instead of letting
        hash math grow without bound. SIGNED results stay mathematical —
        a signed escape is exactly what the wrap checks must see."""
        if out_dt is None or np.dtype(out_dt).kind != "u":
            return x
        if x.empty or fits(x, out_dt):
            return x
        r = dtype_range(out_dt)
        return Iv(r.lo, r.hi, x.inf, x.poison)

    def _t_add(self, eqn, ivs, out_dt):
        return self._uwrap(iv_add(ivs[0], ivs[1]), out_dt)

    def _t_sub(self, eqn, ivs, out_dt):
        return self._uwrap(iv_sub(ivs[0], ivs[1]), out_dt)

    def _t_mul(self, eqn, ivs, out_dt):
        return self._uwrap(iv_mul(ivs[0], ivs[1]), out_dt)

    def _t_div(self, eqn, ivs, out_dt):
        return iv_div(ivs[0], ivs[1], out_dt)

    def _t_rem(self, eqn, ivs, out_dt):
        return iv_rem(ivs[0], ivs[1], out_dt)

    def _t_max(self, eqn, ivs, out_dt):
        return iv_max(ivs[0], ivs[1])

    def _t_min(self, eqn, ivs, out_dt):
        return iv_min(ivs[0], ivs[1])

    def _t_clamp(self, eqn, ivs, out_dt):
        return iv_min(iv_max(ivs[0], ivs[1]), ivs[2])

    def _t_neg(self, eqn, ivs, out_dt):
        a = ivs[0]
        return _arith((a,), -a.hi, -a.lo)

    def _t_abs(self, eqn, ivs, out_dt):
        a = ivs[0]
        if a.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
        return _arith((a,), lo, max(abs(a.lo), abs(a.hi)))

    def _t_sign(self, eqn, ivs, out_dt):
        a = ivs[0]
        if a.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        return Iv(
            -1 if a.lo < 0 else 0 if a.lo == 0 else 1,
            1 if a.hi > 0 else 0 if a.hi == 0 else -1,
            False, a.poison or a.inf,
        )

    @staticmethod
    def _cmp_fold(op: str, a: Iv, b: Iv) -> Iv:
        """Constant-fold a comparison when the intervals decide it (the
        modulo/negative-index guards hinge on this: `lt(rem, 0)` over a
        provably non-negative rem is FALSE, which lets select_n pick the
        un-shifted branch). Poisoned operands never fold — junk values
        are not bounded by their finite interval. A possible INF_US
        sentinel participates at the top of the effective hull."""
        if a.poison or b.poison or a.empty or b.empty:
            return BOOL_IV
        a_hi = INF_US_VAL if a.inf and a.hi < INF_US_VAL else a.hi
        b_hi = INF_US_VAL if b.inf and b.hi < INF_US_VAL else b.hi
        if op == "lt":
            if a_hi < b.lo:
                return Iv(1, 1)
            if a.lo >= b_hi:
                return Iv(0, 0)
        elif op == "le":
            if a_hi <= b.lo:
                return Iv(1, 1)
            if a.lo > b_hi:
                return Iv(0, 0)
        elif op == "gt":
            if a.lo > b_hi:
                return Iv(1, 1)
            if a_hi <= b.lo:
                return Iv(0, 0)
        elif op == "ge":
            if a.lo >= b_hi:
                return Iv(1, 1)
            if a_hi < b.lo:
                return Iv(0, 0)
        elif op == "eq":
            if a_hi < b.lo or b_hi < a.lo:
                return Iv(0, 0)
            if (a.lo == a_hi == b.lo == b_hi) and not (a.inf or b.inf):
                return Iv(1, 1)
        elif op == "ne":
            if a_hi < b.lo or b_hi < a.lo:
                return Iv(1, 1)
            if (a.lo == a_hi == b.lo == b_hi) and not (a.inf or b.inf):
                return Iv(0, 0)
        return BOOL_IV

    def _cmp(self, eqn, ivs, out_dt):
        return self._cmp_fold(eqn.primitive.name, ivs[0], ivs[1])

    _t_eq = _cmp
    _t_ne = _cmp
    _t_lt = _cmp
    _t_le = _cmp
    _t_gt = _cmp
    _t_ge = _cmp

    def _t_is_finite(self, eqn, ivs, out_dt):
        return BOOL_IV

    def _t_not(self, eqn, ivs, out_dt):
        a = ivs[0]
        dt = np.dtype(out_dt)
        if dt.kind == "b":
            if a.empty:
                return BOOL_IV
            return Iv(1 - a.hi, 1 - a.lo, False, a.poison)
        if dt.kind == "u":  # unsigned ~x = (2^N - 1) - x
            top = int(2 ** (8 * dt.itemsize) - 1)
            if a.empty or a.lo < 0 or a.hi in (POS_INF,):
                return dtype_range(out_dt)
            return _arith((a,), top - a.hi, top - a.lo)
        return _arith((a,), -a.hi - 1, -a.lo - 1)  # signed ~x = -x-1

    def _bitint(self, eqn, ivs, out_dt, kind):
        a, b = ivs[0], ivs[1]
        if np.dtype(out_dt).kind == "b":
            # monotone 0/1 fold for and/or (xor stays undecided): keeps
            # constant guard conjunctions decidable for select_n
            if (
                kind in ("and", "or") and not (a.poison or b.poison)
                and not (a.empty or b.empty)
                and 0 <= a.lo and a.hi <= 1 and 0 <= b.lo and b.hi <= 1
            ):
                if kind == "and":
                    return Iv(int(a.lo) & int(b.lo), int(a.hi) & int(b.hi))
                return Iv(int(a.lo) | int(b.lo), int(a.hi) | int(b.hi))
            return BOOL_IV
        if a.empty or b.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        if a.lo < 0 or b.lo < 0:
            r = dtype_range(out_dt)
            return Iv(r.lo, r.hi, False, a.poison or b.poison)
        _, p = _flags(a, b)
        if kind == "and":
            return Iv(0, min(a.hi, b.hi), False, p)
        return Iv(0, _bit_hull(max(a.hi, b.hi)), False, p)

    def _t_and(self, eqn, ivs, out_dt):
        return self._bitint(eqn, ivs, out_dt, "and")

    def _t_or(self, eqn, ivs, out_dt):
        return self._bitint(eqn, ivs, out_dt, "or")

    def _t_xor(self, eqn, ivs, out_dt):
        return self._bitint(eqn, ivs, out_dt, "xor")

    def _t_shift_left(self, eqn, ivs, out_dt):
        a, s = ivs[0], ivs[1]
        if a.empty or s.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        if (
            a.lo < 0 or s.lo < 0 or s.hi > 64
            or a.hi in (POS_INF,) or s.hi in (POS_INF,)
        ):
            r = dtype_range(out_dt)
            return Iv(r.lo, r.hi, False, a.poison or s.poison)
        return self._uwrap(
            _arith((a, s), int(a.lo) << int(s.lo), int(a.hi) << int(s.hi)),
            out_dt,
        )

    def _t_shift_right_logical(self, eqn, ivs, out_dt):
        a, s = ivs[0], ivs[1]
        bits = 8 * np.dtype(out_dt).itemsize
        if a.empty or s.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        smin = 0 if s.lo in (NEG_INF,) else max(int(s.lo), 0)
        smax = bits if s.hi in (POS_INF,) else min(max(int(s.hi), 0), bits)
        if a.lo < 0 or a.hi in (POS_INF,):
            # negative (or unbounded) reinterprets as a large unsigned
            return Iv(0, (2**bits - 1) >> smin, False, a.poison or s.poison)
        return _arith((a, s), int(a.lo) >> smax, int(a.hi) >> smin)

    def _t_shift_right_arithmetic(self, eqn, ivs, out_dt):
        a, s = ivs[0], ivs[1]
        if a.empty or s.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        if a.lo in (NEG_INF,) or a.hi in (POS_INF,):
            return _arith((a, s), a.lo, a.hi)  # shrinks toward 0
        smin = 0 if s.lo in (NEG_INF,) else max(int(s.lo), 0)
        smax = 63 if s.hi in (POS_INF,) else min(max(int(s.hi), 0), 63)
        cs = [int(x) >> sh for x in (a.lo, a.hi) for sh in (smin, smax)]
        return _arith((a, s), min(cs), max(cs))

    def _t_convert_element_type(self, eqn, ivs, out_dt):
        """Math-preserving: the interval claims PRE-WRAP mathematical
        values; dtype-escape is judged at the narrow-store checks, not
        silently folded back in here (a wrapping cast is exactly the
        bug class this layer exists to surface)."""
        a = ivs[0]
        if np.dtype(out_dt).kind == "b":
            return BOOL_IV
        if np.dtype(out_dt).kind in "iu" and not a.empty and not (
            a.lo in (NEG_INF,) or a.hi in (POS_INF,)
        ):
            return Iv(
                math.floor(a.lo), math.ceil(a.hi), a.inf, a.poison
            )
        return a

    def _t_iota(self, eqn, ivs, out_dt):
        dim = eqn.params["dimension"]
        return Iv(0, max(int(eqn.params["shape"][dim]) - 1, 0))

    def _t_population_count(self, eqn, ivs, out_dt):
        a = ivs[0]
        bits = 8 * np.dtype(out_dt).itemsize
        if not a.empty and 0 <= a.lo and a.hi not in (POS_INF,):
            return Iv(0, int(a.hi).bit_length(), False, a.poison or a.inf)
        return Iv(0, bits, False, a.poison)

    def _t_clz(self, eqn, ivs, out_dt):
        bits = 8 * np.dtype(out_dt).itemsize
        return Iv(0, bits, False, ivs[0].poison)

    def _t_argmin(self, eqn, ivs, out_dt):
        axes = eqn.params.get("axes", (0,))
        shape = tuple(getattr(eqn.invars[0].aval, "shape", (1,)))
        n = 1
        for a in axes:
            n *= shape[a]
        return Iv(0, max(n - 1, 0))

    _t_argmax = _t_argmin

    _MASK_TRANSPARENT = frozenset({
        "broadcast_in_dim", "reshape", "convert_element_type", "squeeze",
        "transpose", "expand_dims", "copy",
    })

    def _masked_product(self, atom) -> bool:
        """True when `atom` is (through shape-only ops) a product with a
        0/1 mask operand against a non-mask operand — the engine's
        one-hot-contraction idiom written as `(mask * x).sum(axis)`.
        Such a sum is modeled as SELECTION (at most one term survives),
        the same documented one-hot assumption as dot_general routing."""
        for _ in range(6):
            eqn = self._defs.get(atom)
            if eqn is None:
                return False
            name = eqn.primitive.name
            if name in self._MASK_TRANSPARENT:
                atom = eqn.invars[0]
                continue
            if name != "mul":
                return False
            a, b = self.read(eqn.invars[0]), self.read(eqn.invars[1])
            is_mask = [
                not x.empty and not x.poison and x.lo >= 0 and x.hi <= 1
                for x in (a, b)
            ]
            return is_mask[0] != is_mask[1]  # exactly one 0/1 operand
        return False

    def _t_reduce_sum(self, eqn, ivs, out_dt):
        a = ivs[0]
        axes = eqn.params.get("axes", ())
        shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        n = 1
        for ax in axes:
            if ax < len(shape):
                n *= shape[ax]
        if a.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        if self._masked_product(eqn.invars[0]):
            self.onehot_sites += 1
            return join(Iv(0, 0), Iv(a.lo, a.hi, False, a.poison))
        # sum of exactly n terms each in [lo, hi]
        return self._uwrap(
            _arith((a,), _mul1(n, a.lo), _mul1(n, a.hi)), out_dt,
        )

    def _t_cumsum(self, eqn, ivs, out_dt):
        # coarse: every prefix is bounded by the full-axis sum hull.
        # NOTE cumsum's param is `axis` (scalar), not reduce_sum's `axes`
        a = ivs[0]
        shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        ax = eqn.params.get("axis")
        n = shape[ax] if ax is not None and ax < len(shape) else 1
        if a.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        return self._uwrap(
            _arith(
                (a,),
                min(a.lo, _mul1(n, a.lo)), max(a.hi, _mul1(n, a.hi)),
            ),
            out_dt,
        )

    _t_cumprod = _t_default  # no precise need; sound dtype fallback
    _t_cummax = _ident
    _t_cummin = _ident

    def _t_reduce_or(self, eqn, ivs, out_dt):
        if np.dtype(out_dt).kind == "b":
            return BOOL_IV
        a = ivs[0]
        if not a.empty and a.lo >= 0:
            return Iv(0, _bit_hull(a.hi), False, a.poison or a.inf)
        return dtype_range(out_dt)

    def _t_reduce_and(self, eqn, ivs, out_dt):
        if np.dtype(out_dt).kind == "b":
            return BOOL_IV
        a = ivs[0]
        if not a.empty and a.lo >= 0:
            return Iv(0, a.hi, False, a.poison or a.inf)
        return dtype_range(out_dt)

    def _t_dot_general(self, eqn, ivs, out_dt):
        (lc, rc), _ = eqn.params["dimension_numbers"]
        lshape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        k = 1
        for ax in lc:
            if ax < len(lshape):
                k *= lshape[ax]
        a, b = ivs[0], ivs[1]
        p = iv_mul(a, b)
        is_mask = [
            not x.empty and x.lo >= 0 and x.hi <= 1 and not x.poison
            for x in (a, b)
        ]
        if is_mask[0] != is_mask[1]:
            # the engine's routing idiom: EXACTLY ONE 0/1 mask operand
            # against a value operand selects (at most one hit per
            # output) — modeled as selection, not a subset sum. A
            # mask-x-mask contraction is a COUNT (hull [0, k]) and must
            # fall through to the k-scaled path below. Documented
            # assumption; see module docstring.
            self.onehot_sites += 1
            return join(Iv(0, 0), Iv(p.lo, p.hi, False, p.poison))
        if p.empty:
            return Iv(POS_INF, NEG_INF, False, True)
        return Iv(_mul1(k, p.lo) if p.lo < 0 else min(p.lo, _mul1(k, p.lo)),
                  _mul1(k, p.hi), False, p.poison)

    # -- dynamic indexing: the bounds certificate's scan set ---------------

    def _record_site(self, eqn, idx_iv: Iv, allowed: Tuple[int, int],
                     mode) -> None:
        ok = (
            not idx_iv.poison and not idx_iv.inf and not idx_iv.empty
            and idx_iv.lo >= allowed[0] and idx_iv.hi <= allowed[1]
        )
        self.index_sites.append(IndexSite(
            prim=eqn.primitive.name,
            mode=str(mode) if mode is not None else "none",
            index_iv=idx_iv,
            allowed=(int(allowed[0]), int(allowed[1])),  # JSON-pure ints
            ok=ok,
            where_eqn=self.top_eqn if self.top_eqn is not None else eqn,
        ))

    def _t_gather(self, eqn, ivs, out_dt):
        operand, idx = ivs[0], ivs[1]
        dn = eqn.params["dimension_numbers"]
        sizes = eqn.params["slice_sizes"]
        oshape = tuple(eqn.invars[0].aval.shape)
        allowed_hi = min(
            (oshape[d] - sizes[d] for d in dn.start_index_map), default=0
        )
        self._record_site(eqn, idx, (0, allowed_hi), eqn.params.get("mode"))
        return Iv(operand.lo, operand.hi, operand.inf, operand.poison)

    def _t_scatter(self, eqn, ivs, out_dt):
        operand, idx, upd = ivs[0], ivs[1], ivs[2]
        dn = eqn.params["dimension_numbers"]
        oshape = tuple(eqn.invars[0].aval.shape)
        allowed_hi = 0
        if dn.scatter_dims_to_operand_dims:
            # every in-tree site scatters whole windows at single
            # positions (inserted dims), so the start bound is the dim
            # extent; a windowed scatter start would need extent - size
            allowed_hi = min(
                oshape[d] - 1 for d in dn.scatter_dims_to_operand_dims
            )
        self._record_site(eqn, idx, (0, allowed_hi), eqn.params.get("mode"))
        return join(operand, upd)

    _t_scatter_add = _t_scatter

    def _t_dynamic_slice(self, eqn, ivs, out_dt):
        operand = ivs[0]
        oshape = tuple(eqn.invars[0].aval.shape)
        sizes = eqn.params["slice_sizes"]
        for d, idx in enumerate(ivs[1:]):
            self._record_site(eqn, idx, (0, oshape[d] - sizes[d]), "clamp")
        return operand

    def _t_dynamic_update_slice(self, eqn, ivs, out_dt):
        operand, upd = ivs[0], ivs[1]
        oshape = tuple(eqn.invars[0].aval.shape)
        ushape = tuple(eqn.invars[1].aval.shape)
        for d, idx in enumerate(ivs[2:]):
            self._record_site(eqn, idx, (0, oshape[d] - ushape[d]), "clamp")
        return join(operand, upd)


# ----------------------------------------------------------- seeding layer


PAYLOAD_PREFIXES = ("hot.msgs.payload", "hot.strag.payload")

# default protocol-value hull when a spec declares no rate fields: wide
# enough to exercise real arithmetic, far from i32 overflow
DEFAULT_PV = (1 << 24) - 1


def _rate_kind(entry) -> str:
    from ..tpu.spec import HardCap, RateFloor

    if isinstance(entry, RateFloor):
        return "rate"
    if isinstance(entry, HardCap):
        return "hard"
    raise TypeError(
        f"rate_floors values must be RateFloor or HardCap, got {entry!r}"
    )


def classify_narrow(spec) -> Dict[str, str]:
    """{field -> 'rate' | 'hard' | 'closed'} for spec.narrow_fields."""
    floors = dict(spec.rate_floors or {})
    out = {}
    for f in (spec.narrow_fields or {}):
        out[f] = _rate_kind(floors[f]) if f in floors else "closed"
    return out


def init_intervals(trace) -> Dict[str, Iv]:
    """Interval-run the REAL `_init` program: {leaf name -> iv} over the
    full SimState template. Init bounds are derived, not assumed."""
    from ..tpu.engine import named_leaves

    closed = trace.closed_init
    seeds = [dtype_range(v.aval.dtype) for v in closed.jaxpr.invars]
    im = IntervalMap(closed, seeds).run()
    names = [n for n, _ in named_leaves(trace.init_template)]
    out = {}
    for name, ov in zip(names, closed.jaxpr.outvars):
        out[name] = im.read(ov)
    return out


def step_seeds(
    trace,
    init_ivs: Dict[str, Iv],
    payload_override: Optional[Iv] = None,
) -> Tuple[List[Iv], Dict[str, Iv], Set[str]]:
    """(per-invar seeds, {name -> seed}, evolving-leaf names) for one
    fixpoint run over `_step_split`.

    Sources, in priority order: engine invariants (interval_hints),
    narrow-field classification (rate fields pinned at
    [init_lo, dtype_max - inc]; hard caps pinned at [init_lo, cap];
    closed fields EVOLVE from their init interval), payload leaves
    pinned at the message-copy hull, everything else protocol-owned and
    evolving from init."""
    from ..tpu.engine import interval_hints
    from ..tpu.spec import HardCap, RateFloor

    sim = trace.sim
    hints = interval_hints(
        sim,
        refill=getattr(trace, "refill", False),
        devloop=getattr(trace, "devloop", False),
    )
    kinds = classify_narrow(sim.spec)
    floors = dict(sim.spec.rate_floors or {})

    rate_caps = [
        dtype_range(sim.spec.narrow_fields[f]).hi - floors[f].inc
        for f, k in kinds.items() if k == "rate"
    ]
    pv_hi = min(rate_caps) if rate_caps else DEFAULT_PV
    payload_iv = payload_override or Iv(-pv_hi, pv_hi)

    seeds: Dict[str, Iv] = {}
    evolve: Set[str] = set()
    for name in trace.names:
        if any(name.startswith(p) for p in PAYLOAD_PREFIXES):
            seeds[name] = payload_iv
            continue
        if name in hints:
            lo, hi, may_inf = hints[name]
            seeds[name] = Iv(lo, hi, may_inf)
            continue
        leaf_field = None
        ini_key = name.replace("hot.", "", 1)
        if name.startswith("hot.node."):
            leaf_field = name[len("hot.node."):]
        elif name.startswith("hot.dur."):
            # durability watermark: every dur leaf is a SNAPSHOT of its
            # node twin (advance/reset copy node -> dur, disk recovery
            # copies dur -> node), so it carries the node field's
            # spec-declared interval — seeding it wider would let the
            # recovery copy-back break the node leaf's own certificate
            leaf_field = name[len("hot.dur."):]
            ini_key = f"node.{leaf_field}"
        ini = init_ivs.get(ini_key, None)
        if leaf_field in kinds:
            k = kinds[leaf_field]
            dt_hi = dtype_range(sim.spec.narrow_fields[leaf_field]).hi
            ini = ini or Iv(0, 0)
            if k == "rate":
                seeds[name] = Iv(
                    min(ini.lo, 0), dt_hi - floors[leaf_field].inc
                )
            elif k == "hard":
                seeds[name] = Iv(min(ini.lo, 0), floors[leaf_field].cap)
            else:
                seeds[name] = ini
                evolve.add(name)
            continue
        # plain protocol leaf: evolve from init (or dtype range when the
        # leaf has no init twin, e.g. trace-only extras)
        if ini is not None:
            seeds[name] = ini
            evolve.add(name)
        else:
            dt = None
            for n2, leaf in zip(trace.names, trace.invars_avals):
                if n2 == name:
                    dt = leaf.dtype
                    break
            seeds[name] = dtype_range(dt)
    return [seeds[n] for n in trace.names], seeds, evolve


# ------------------------------------------------------------ the fixpoint


@dataclasses.dataclass
class StepAnalysis:
    """One converged interval pass over a step program."""

    im: IntervalMap
    in_env: Dict[str, Iv]
    out_env: Dict[str, Iv]
    passes: int
    converged: bool


def fixpoint_step(
    closed,
    in_names: Sequence[str],
    out_names: Sequence[str],
    seeds: Dict[str, Iv],
    evolve: Set[str] = frozenset(),
    max_passes: int = 16,
) -> StepAnalysis:
    """Iterate the step program to a widening fixpoint over `evolve`
    leaves (in-leaf name == out-leaf name join, threshold widening from
    pass 2), then one FINAL pass whose IntervalMap carries the converged
    environment — the pass every check reads.

    Evolving seeds are intersected with their leaf's DTYPE range: the
    carry physically stores that dtype, so the at-rest value is in range
    by construction (i32 wrap-around included — unbounded counters like
    log indices stabilize at full i32 instead of diverging; whether a
    WRAP on the way there matters is the narrow-store and TIME-cone
    checks' business, which read the mathematical pre-store intervals)."""
    in_avals = {
        n: v.aval for n, v in zip(in_names, closed.jaxpr.invars)
    }
    cur = dict(seeds)
    out_pos = {n: i for i, n in enumerate(out_names)}
    passes = 0
    converged = False
    for i in range(max_passes):
        passes += 1
        im = IntervalMap(closed, [cur[n] for n in in_names]).run()
        outs = [im.read(ov) for ov in closed.jaxpr.outvars]
        changed = False
        for n in evolve:
            j = out_pos.get(n)
            if j is None:
                continue
            new = join(cur[n], outs[j])
            dtr = dtype_range(in_avals[n].dtype)
            if i >= 4 and new != cur[n]:
                # still growing after the ladder passes: an unbounded
                # counter — jump straight to its dtype top
                new = Iv(dtr.lo, dtr.hi, new.inf, new.poison)
            elif i >= 1:
                new = widen(cur[n], new)
            if not new.empty:
                new = Iv(
                    max(new.lo, dtr.lo), min(new.hi, dtr.hi),
                    new.inf, new.poison,
                )
            if new != cur[n]:
                cur[n] = new
                changed = True
        if not changed:
            converged = True
            break
    im = IntervalMap(closed, [cur[n] for n in in_names]).run()
    outs = [im.read(ov) for ov in closed.jaxpr.outvars]
    return StepAnalysis(
        im=im, in_env=cur,
        out_env={n: outs[j] for n, j in out_pos.items()},
        passes=passes, converged=converged,
    )


def time_tainted_eqns(closed, in_names, time_leaves) -> Set[int]:
    """{id(eqn)} whose inputs carry TIME taint (jaxprutil lattice)."""
    masks = [TIME if n in time_leaves else 0 for n in in_names]
    hit: Set[int] = set()

    def visit(eqn, read):
        if any(read(v) & TIME for v in eqn.invars):
            hit.add(id(eqn))

    TaintMap(closed, masks).run(visit)
    return hit


_OVERFLOW_PRIMS = frozenset({"add", "sub", "mul"})


def time_overflow_findings(
    closed,
    in_names: Sequence[str],
    seeds: Dict[str, Iv],
    time_leaves: Set[str],
    res: RuleResult,
    where: str,
) -> Tuple[int, int]:
    """Certificate (b): no signed-int arithmetic in the TIME cone can
    exceed its dtype, given the seeded invariants. Sentinel-poisoned
    operands are skipped (the engine's compute-then-discard idiom);
    everything else that wraps is a finding with a backward witness."""
    tainted = time_tainted_eqns(closed, in_names, time_leaves)
    checked_ids: Set[int] = set()
    # keyed by eqn id, joined across visits: a loop body's wrap may only
    # appear on a LATER unrolled/widened visit of the same equation
    flagged_by_id: Dict[int, Tuple[Any, Any, str, Iv]] = {}

    def on_eqn(eqn, in_ivs, out_ivs, top_eqn):
        if id(eqn) not in tainted or eqn.primitive.name not in _OVERFLOW_PRIMS:
            return
        dt = getattr(eqn.outvars[0].aval, "dtype", None)
        if dt is None or np.dtype(dt).kind != "i":
            return
        checked_ids.add(id(eqn))
        out = out_ivs[0]
        if out.poison or out.empty:
            return
        # an operand already saturating its dtype is no longer a bounded
        # time quantity (an unbounded counter that data-flowed past a
        # clock): arithmetic on it wraps vacuously, and the FIRST wrap
        # in any real chain fires on bounded operands upstream
        full = dtype_range(dt)
        for x in in_ivs:
            if not x.empty and (x.lo <= full.lo or x.hi >= full.hi):
                return
        if not fits(out, dt):
            prev = flagged_by_id.get(id(eqn))
            joined = out if prev is None else join(prev[3], out)
            flagged_by_id[id(eqn)] = (eqn, top_eqn, str(dt), joined)

    im = IntervalMap(closed, [seeds[n] for n in in_names], on_eqn=on_eqn)
    im.run()
    checked = len(checked_ids)
    flagged = len(flagged_by_id)
    for eqn, top_eqn, dt, out in flagged_by_id.values():
        src = top_eqn if top_eqn is not None else eqn
        hits = backward_invars(closed.jaxpr, list(src.invars))
        names = [in_names[i] for i in hits if in_names[i] in time_leaves][:6]
        res.add(
            where,
            f"virtual-clock wrap: `{eqn.primitive.name}` on a time-typed "
            f"value reaches {out.render()} — outside {dt} (reaches "
            f"{names or ['<local>']}); the i32-us clock must never wrap "
            "within the horizon",
        )
    return checked, flagged


def index_bound_rows(
    analysis: StepAnalysis,
    closed,
    in_names: Sequence[str],
    res: RuleResult,
    where: str,
) -> List[Dict[str, Any]]:
    """Certificate (c): every dynamic index statically in-bounds.
    PROMISE_IN_BOUNDS sites must prove (out-of-bounds there is undefined
    behavior the engine merely trusted until now); defined-semantics
    sites (fill/drop/clip) that intervals alone cannot prove are
    enumerated with status `guarded`."""
    rows = []
    for site in analysis.im.index_sites:
        hits = backward_invars(closed.jaxpr, list(site.where_eqn.invars))
        witness = [
            in_names[i] for i in hits
            if not in_names[i].startswith("const.")
        ][:4]
        promised = "PROMISE_IN_BOUNDS" in site.mode
        status = (
            "proved" if site.ok
            else "violated" if promised else "guarded"
        )
        rows.append({
            "prim": site.prim,
            "mode": site.mode,
            "index": [
                None if site.index_iv.lo in (NEG_INF, POS_INF)
                else int(site.index_iv.lo),
                None if site.index_iv.hi in (NEG_INF, POS_INF)
                else int(site.index_iv.hi),
            ],
            "allowed": list(site.allowed),
            "status": status,
            "witness": witness,
        })
        if status == "violated":
            res.add(
                where,
                f"dynamic index not provably in-bounds: `{site.prim}` "
                f"(mode {site.mode}) index {site.index_iv.render()} vs "
                f"allowed [0, {site.allowed[1]}] — out of bounds here is "
                f"UNDEFINED; witness {witness or ['<local>']}",
            )
    return rows


# -------------------------------------------------------- narrow-field rows


def narrow_field_rows(
    trace,
    analysis: StepAnalysis,
    init_ivs: Dict[str, Iv],
    res: RuleResult,
    where: str,
    reanalyze: Callable[[Iv], StepAnalysis],
) -> List[Dict[str, Any]]:
    """Certificate (a): one row per narrow field. A store that escapes
    its dtype under the message-copy hull is re-analyzed with payloads
    pinned to the field's own dtype range: if it then fits, the row is
    `assumed-copy` (provable only under the copy induction — reported,
    never silent); if it still escapes, the narrowing is UNSOUND and the
    rule fires with a witness naming the field."""
    from ..tpu.spec import HardCap, RateFloor, derate_horizon

    sim = trace.sim
    spec = sim.spec
    kinds = classify_narrow(spec)
    floors = dict(spec.rate_floors or {})
    closed = trace.closed_step
    out_pos = {n: i for i, n in enumerate(trace.out_names)}
    rows: List[Dict[str, Any]] = []
    retry_cache: Dict[Tuple[int, int], StepAnalysis] = {}

    for f, dt in (spec.narrow_fields or {}).items():
        leaf = f"hot.node.{f}"
        kind = kinds[f]
        dtr = dtype_range(dt)
        seed = analysis.in_env.get(leaf, dtr)
        store = analysis.out_env.get(leaf)
        ini = init_ivs.get(f"node.{f}", Iv(0, 0))
        row: Dict[str, Any] = {
            "field": f,
            "dtype": str(jnp.dtype(dt)),
            "kind": kind,
            "init": [int(ini.lo), int(ini.hi)] if not ini.empty else None,
            "certified_horizon_us": None,  # None = unbounded
        }
        if store is None:
            res.add(where, f"narrow field {f}: no matching carry out leaf")
            row["status"] = "violated"
            rows.append(row)
            continue
        budget_hi = dtr.hi
        budget_lo = dtr.lo
        if kind == "rate":
            fl: RateFloor = floors[f]
            budget_hi = seed.hi + fl.inc  # growth bound: <= inc per event
            row.update(
                floor_us=fl.floor_us, ratchet=fl.ratchet, inc=fl.inc,
            )
            init_hi = max(int(ini.hi), 0) if not ini.empty else 0
            row["certified_horizon_us"] = (
                (dtr.hi - init_hi) * fl.floor_us // (fl.ratchet * fl.inc)
            )
        elif kind == "hard":
            hc: HardCap = floors[f]
            row["hard_cap"] = hc.cap
            if hc.cap > dtr.hi:
                res.add(
                    where,
                    f"narrow field {f}: declared HardCap {hc.cap} does "
                    f"not fit {row['dtype']} (max {dtr.hi})",
                )
                row["status"] = "violated"
                rows.append(row)
                continue
            budget_hi = hc.cap
        # a maybe-INF_US sentinel does NOT fit a narrow store: the cast
        # would wrap 2^31-1, so the inf flag disqualifies alongside
        # poison (fits() tolerates the sentinel only for i32 leaves)
        ok = (
            not store.empty and store.lo >= budget_lo
            and store.hi <= budget_hi and not store.poison
            and not store.inf
        )
        row["store"] = (
            None if store.empty
            else [
                None if store.lo in (NEG_INF,) else int(store.lo),
                None if store.hi in (POS_INF,) else int(store.hi),
            ]
        )
        if ok:
            # (a rate field's one-step growth budget reaches dtype_max
            # exactly — that is the certified boundary, not a wrap)
            row["status"] = "proved"
            rows.append(row)
            continue
        # retry under the copy premise: payloads bounded like the field
        # itself (for rate fields, the same pre-wrap budget the state
        # seed uses — a copied value is a copy of an IN-BUDGET value)
        retry_hi = dtr.hi - floors[f].inc if kind == "rate" else dtr.hi
        key = (int(dtr.lo), int(retry_hi))
        retry = retry_cache.get(key)
        if retry is None:
            retry = reanalyze(Iv(dtr.lo, retry_hi))
            retry_cache[key] = retry
        store2 = retry.out_env.get(leaf, store)
        seed2 = retry.in_env.get(leaf, seed)
        budget2_hi = budget_hi
        if kind == "rate":
            budget2_hi = seed2.hi + floors[f].inc
        ok2 = (
            not store2.empty and store2.lo >= budget_lo
            and store2.hi <= budget2_hi and not store2.poison
            and not store2.inf
        )
        if ok2:
            row["status"] = "assumed-copy"
            row["store"] = [int(store2.lo), int(store2.hi)]
            rows.append(row)
            continue
        row["status"] = "violated"
        outvar = closed.jaxpr.outvars[out_pos[leaf]]
        hits = backward_invars(closed.jaxpr, [outvar])
        witness = [
            trace.names[i] for i in hits
            if trace.names[i].startswith("hot.node.")
            or any(trace.names[i].startswith(p) for p in PAYLOAD_PREFIXES)
        ][:6]
        res.add(
            where,
            f"narrow field {f} ({row['dtype']}, {kind}) may wrap: store "
            f"interval {store2.render()} escapes "
            f"[{budget_lo}, {budget2_hi}]"
            + (" (growth exceeds the declared per-event inc)"
               if kind == "rate" else
               " and no rate floor is declared for it")
            + f"; witness {witness or [leaf]}",
        )
        rows.append(row)
    return rows


# ------------------------------------------------------------- certificates


def horizon_certificate(trace, rows: List[Dict[str, Any]],
                        res: RuleResult, where: str) -> Dict[str, Any]:
    """Fold the per-field rows into the workload's horizon certificate:
    min certified horizon over rate fields, derated for the traced
    config's clock skew through spec.derate_horizon (the engine's own
    helper), and checked against BOTH the declared narrow_horizon_us and
    the traced config's horizon_us."""
    from ..tpu.spec import derate_horizon

    sim = trace.sim
    declared = sim.spec.narrow_horizon_us
    ppm = (
        sim.config.nem_skew_max_ppm if sim.config.nem_skew_enabled else 0
    )
    finite = [
        (r["certified_horizon_us"], r["field"]) for r in rows
        if r.get("certified_horizon_us") is not None
    ]
    certified = min(finite)[0] if finite else None
    binding = min(finite)[1] if finite else None
    cert = {
        "declared_us": declared,
        "certified_us": certified,
        "binding_field": binding,
        "skew_max_ppm": ppm,
        "derated_declared_us": (
            None if declared is None else derate_horizon(declared, ppm)
        ),
        "derated_certified_us": (
            None if certified is None else derate_horizon(certified, ppm)
        ),
        "config_horizon_us": sim.config.horizon_us,
    }
    ok = True
    if certified is not None and declared is None:
        ok = False
        res.add(
            where,
            f"rate-bounded narrow fields (binding: {binding}, certified "
            f"{certified} us) but the spec declares no narrow_horizon_us "
            "— the engine refusal is not guarding this table",
        )
    if certified is not None and declared is not None:
        if derate_horizon(declared, ppm) > derate_horizon(certified, ppm):
            ok = False
            res.add(
                where,
                f"declared narrow_horizon_us={declared} exceeds the "
                f"certified safe horizon {certified} us (binding field: "
                f"{binding}) — the hand-derived cap over-promises",
            )
        if sim.config.horizon_us > derate_horizon(certified, ppm):
            ok = False
            res.add(
                where,
                f"traced config horizon_us={sim.config.horizon_us} "
                f"exceeds the derated certified horizon "
                f"{derate_horizon(certified, ppm)} us",
            )
    cert["ok"] = ok
    return cert


def sum64_certificate(res: RuleResult) -> Dict[str, Any]:
    """Certificate (d): rederive `_sum64`'s lane-exactness bound from
    the traced reduction's interval transfer instead of asserting it.
    Each u32 partial sums L addends; the lo half's addends reach
    2^16 - 1, so exactness needs L <= u32_max // (2^16 - 1). The
    engine's asserted cap must be <= the rederived one, and the guard
    must actually exist at the asserted cap."""
    from ..tpu.engine import _sum64

    asserted = 65536
    x = jax.ShapeDtypeStruct((asserted,), jnp.int32)
    closed = jax.make_jaxpr(lambda v: _sum64(v))(x)
    addend_hi = 0
    sum_his: List[int] = []
    reduce_ok = True

    def on_eqn(eqn, in_ivs, out_ivs, top_eqn):
        nonlocal addend_hi, reduce_ok
        if eqn.primitive.name != "reduce_sum":
            return
        a, out = in_ivs[0], out_ivs[0]
        addend_hi = max(addend_hi, int(a.hi))
        sum_his.append(int(out.hi))
        dt = eqn.outvars[0].aval.dtype
        if not fits(out, dt):
            reduce_ok = False

    IntervalMap(
        closed, [Iv(0, 2**31 - 1)], on_eqn=on_eqn,
    ).run()
    rederived = (2**32 - 1) // max(addend_hi, 1)
    guard_fires = False
    try:
        _sum64(jax.ShapeDtypeStruct((asserted + 1,), jnp.int32))
    except ValueError:
        guard_fires = True  # the lane-cap refusal, raised pre-trace
    except Exception:
        # any OTHER error means the guard no longer fires before the
        # first array op (e.g. it was removed and the ShapeDtypeStruct
        # probe hit real array code) — report it as a certificate
        # failure, never crash the analysis run
        guard_fires = False
    ok = reduce_ok and asserted <= rederived and guard_fires
    res.checked += 1
    if not ok:
        res.add(
            "_sum64",
            f"lane-exactness bound broken: asserted {asserted}, "
            f"rederived {rederived} (addend max {addend_hi}), partials "
            f"exact: {reduce_ok}, guard fires at cap+1: {guard_fires}",
        )
    return {
        "asserted_lanes": asserted,
        "rederived_lanes": rederived,
        "addend_max": addend_hi,
        "partials_exact": reduce_ok,
        "guard_fires_past_cap": guard_fires,
        "ok": ok,
    }


# ----------------------------------------------------------------- entry


def verify_ranges(trace, log=None) -> Tuple[List[RuleResult], Dict[str, Any]]:
    """Run the `range` rule over one workload's shared trace: the
    interval fixpoint, certificates (a)-(c), and the summary rows.
    Returns ([RuleResult], certificate dict for the summary JSON)."""
    res = RuleResult("range")
    name = trace.name
    where = f"{name}:_step_split"
    if log:
        log(f"[analysis] range: interval fixpoint over {name} ...")

    init_ivs = init_intervals(trace)
    _, seed_env, evolve = step_seeds(trace, init_ivs)
    closed = trace.closed_step

    analysis = fixpoint_step(
        closed, trace.names, trace.out_names, seed_env, evolve,
    )
    res.checked += analysis.im.eqns_seen

    def reanalyze(payload_iv: Iv) -> StepAnalysis:
        _, s_env, ev = step_seeds(
            trace, init_ivs, payload_override=payload_iv,
        )
        return fixpoint_step(
            closed, trace.names, trace.out_names, s_env, ev,
        )

    rows = narrow_field_rows(
        trace, analysis, init_ivs, res, where, reanalyze,
    )
    res.checked += len(rows)
    horizon = horizon_certificate(trace, rows, res, where)

    time_leaves = trace.time_leaves
    checked_t, flagged_t = time_overflow_findings(
        closed, trace.names, analysis.in_env, time_leaves, res, where,
    )
    res.checked += checked_t

    idx_rows = index_bound_rows(analysis, closed, trace.names, res, where)
    res.checked += len(idx_rows)

    cert = {
        "workload": name,
        "fields": rows,
        "horizon": horizon,
        "clock": {
            "time_eqns_checked": checked_t,
            "overflows": flagged_t,
            "offset_invariant_hi": INF_GUARD_VAL - 1,
            "fixpoint_passes": analysis.passes,
            "converged": analysis.converged,
        },
        "assumptions": {
            # premise-dependence made visible, never silent: copy rows
            # carry status assumed-copy; one-hot-modeled contraction
            # sites are counted here
            "one_hot_selection_sites": analysis.im.onehot_sites,
            "assumed_copy_fields": sum(
                1 for r in rows if r["status"] == "assumed-copy"
            ),
        },
        "indices": {
            "sites": len(idx_rows),
            "violated": sum(1 for r in idx_rows if r["status"] == "violated"),
            "guarded": sum(1 for r in idx_rows if r["status"] == "guarded"),
            "rows": idx_rows,
        },
    }
    if log:
        log(
            f"[analysis] range {name}: {len(rows)} narrow fields, "
            f"{checked_t} time eqns, {len(idx_rows)} index sites, "
            f"{len(res.violations)} violations"
        )
    return [res], cert
