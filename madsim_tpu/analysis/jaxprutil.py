"""Shared jaxpr / StableHLO introspection for the static verifier.

The jaxpr-level rules (madsim_tpu/analysis/jaxpr_check.py) all reduce to
three primitives implemented here:

  * `iter_eqns` — walk every equation of a closed jaxpr INCLUDING the
    sub-jaxprs nested in jit / while / scan / cond / custom_* params,
    so a callback or cross-lane reduction can't hide inside a call.
  * `TaintMap` — forward data-flow of a tiny 4-bit taint lattice
    (KEY / STATE / TIME / SALT) from the function's invars through every
    equation. This is what makes the RNG-taint and time-f32 rules
    cheap: no per-variable invar sets, just masks, with an on-demand
    backward slice (`backward_invars`) to name witnesses when a rule
    actually fires.
  * `donated_arg_flags` — parse a lowered program's StableHLO argument
    attributes (`tf.aliasing_output`) into per-flat-arg donation flags,
    aligned with jax's flatten order, so donation coverage is checked on
    the REAL lowered program rather than on intent.

The engine's PRNG is the murmur3 finalizer chain (tpu/prng.py); its two
fmix multiply constants identify every mix equation in a jaxpr, and the
fold structure `mix(key ^ word * GOLDEN)` makes a draw's key lineage and
folded words ordinary data flow — which is why plain taint propagation is
enough to verify the single-RNG funnel.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend import core as jcore

# murmur3 constants (tpu/prng.py / nemesis.mix32): the fmix multiplies
# identify mix equations; GOLDEN identifies fold word-multiplies.
FMIX_C1 = 0x85EBCA6B
FMIX_C2 = 0xC2B2AE35
GOLDEN = 0x9E3779B9

# taint lattice bits
KEY = 1  # derived from the schedule key root (ConstState.key0 / seeds)
STATE = 2  # derived from a protocol/config side channel
TIME = 4  # derived from a virtual-time quantity (us offsets)
SALT = 8  # derived from an allowlisted salt literal (the coverage chain)
KEY2 = 16  # derived from the per-step chain key (SimState.key)

# primitives that imply a host round-trip / sync inside a jitted program
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "infeed", "outfeed", "host_callback_call",
})

# reduction-style primitives whose `axes`/`dimension` params name the
# reduced dims (the lane-independence rule's scan set). Note
# `reduce_precision` is NOT here: it rounds mantissas elementwise.
REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_and", "reduce_or",
    "reduce_prod", "reduce_xor", "argmax", "argmin", "reduce",
})

_CUMULATIVE_PRIMS = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})


def scalar_value(x: Any) -> Optional[int]:
    """The python int of a 0-d integer constant, else None."""
    try:
        arr = np.asarray(x)
    except Exception:
        return None
    if arr.ndim != 0 or arr.dtype.kind not in "iu":
        return None
    return int(arr)


def is_literal(atom: Any) -> bool:
    """True for a jaxpr Literal atom (an inline constant, not a Var)."""
    return isinstance(atom, jcore.Literal)


def lit_value(atom: Any) -> Optional[int]:
    """Scalar int value of a jaxpr Literal atom, else None."""
    if is_literal(atom):
        return scalar_value(atom.val)
    return None


def _sub_jaxprs(eqn) -> List[Tuple[jcore.Jaxpr, tuple]]:
    """Every (Jaxpr, consts) nested in an equation's params.

    ClosedJaxprs keep their consts (a salt constant closed over by an
    inline-jitted helper must not lose its taint at the call boundary);
    bare Jaxprs yield empty consts."""
    out: List[Tuple[jcore.Jaxpr, tuple]] = []

    def rec(v):
        if isinstance(v, jcore.ClosedJaxpr):
            out.append((v.jaxpr, tuple(v.consts)))
        elif isinstance(v, jcore.Jaxpr):
            out.append((v, ()))
        elif isinstance(v, (tuple, list)):
            for x in v:
                rec(x)

    for v in eqn.params.values():
        rec(v)
    return out


# primitives whose sub-jaxpr re-enters with its own outputs (loop carry):
# one propagation pass under-approximates taint that arrives on
# iteration >= 2, so these bodies are iterated to a fixpoint
_LOOP_PRIMS = frozenset({"while", "scan"})


def iter_eqns(jaxpr: jcore.Jaxpr, depth: int = 0) -> Iterator[Tuple[Any, int]]:
    """(eqn, nesting depth) for every equation, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, depth
        for sub, _consts in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, depth + 1)


class TaintMap:
    """Forward taint propagation over a closed jaxpr.

    `invar_masks[i]` seeds the i-th invar; constvars (and literals, read
    lazily) whose scalar value is in `salt_values` carry SALT. Default
    propagation is the OR of input masks; ALL taint is stripped from
    boolean outputs (r9 — previously only TIME): a bool is a 1-bit
    control value, and every rule here targets VALUE flows — keys,
    times, magnitudes — not control flow that looked at one. The refill
    engine made this load-bearing: lane-retirement flags are data-flow
    descendants of handler state (which carries KEY2 through the event
    merges), and the admission machinery derives from those flags —
    under bool-carried taint no trajectory-dependent scheduler could
    ever verify. The trade is explicit: a draw whose index is rebuilt
    from BOOLEAN trajectory flags launders here (the carry-boundary
    re-seeding of the occurrence counters always laundered the same
    way); integer-valued coupling (index=clock and friends) is still
    caught. Sub-jaxpr handling (r9, grown for the refill step's
    lax.cond):
    `jit` and `cond` bodies are entered with each inner invar seeded by
    its MATCHING operand's mask (precise 1:1 mapping — the old
    union-of-all-operands seeding made every value inside the refill
    branch carry every taint at once), and their per-branch outvar masks
    map back to the call's outvars (joined across cond branches). The
    cond PREDICATE deliberately does not fold into the outputs: control
    dependence does not launder data taint, the same principle as the
    TIME strip at bools. `while` carries are seeded per-slot and
    iterated to a fixpoint against the body's own outputs (r19 — the
    device-loop boundary's sequential fold/mutate loops carry schedule
    roots next to ctl rows, and the old whole-carry union drowned them);
    `scan` (what a static-trip-count fori_loop lowers to) is handled the
    same way, with the stacked ys joined across fixpoint passes. All
    sub-jaxpr equations are visited too.
    """

    def __init__(
        self,
        closed: jcore.ClosedJaxpr,
        invar_masks: Sequence[int],
        salt_values: Sequence[int] = (),
    ) -> None:
        self.salt_values = frozenset(int(v) for v in salt_values)
        self.env: Dict[Any, int] = {}
        jaxpr = closed.jaxpr
        for cv, val in zip(jaxpr.constvars, closed.consts):
            sv = scalar_value(val)
            self.env[cv] = SALT if sv in self.salt_values else 0
        if len(invar_masks) != len(jaxpr.invars):
            raise ValueError(
                f"invar_masks has {len(invar_masks)} entries for "
                f"{len(jaxpr.invars)} invars"
            )
        for v, m in zip(jaxpr.invars, invar_masks):
            self.env[v] = int(m)
        self._jaxpr = jaxpr

    def read(self, atom: Any) -> int:
        # bools carry no taint wherever they come from (invar, output,
        # constant): they are 1-bit control values — see the class doc
        dt = getattr(getattr(atom, "aval", None), "dtype", None)
        if dt is not None and str(dt) == "bool":
            return 0
        lv = lit_value(atom)
        if lv is not None and lv in self.salt_values:
            return SALT
        if is_literal(atom):
            return 0
        return self.env.get(atom, 0)

    def run(self, visit: Optional[Callable[[Any, Callable], None]] = None):
        """Propagate through every eqn; `visit(eqn, read)` is called per
        equation (at every nesting level) AFTER its inputs are resolved.
        During the walk `self.top_eqn` names the top-level equation
        enclosing the current one — witness extraction slices the outer
        jaxpr from it, so violations inside inline-jitted helpers still
        report real leaf names."""
        self.top_eqn: Any = None
        self._run(self._jaxpr, visit, top=True)
        return self

    def _seed_consts(self, sub: jcore.Jaxpr, consts: tuple) -> None:
        for cv, val in zip(sub.constvars, consts):
            sv = scalar_value(val)
            self.env[cv] = SALT if sv in self.salt_values else 0
        for cv in sub.constvars[len(consts):]:
            self.env.setdefault(cv, 0)

    def _set_outs(self, eqn, masks: Sequence[int]) -> None:
        # (bool outputs are additionally zeroed at read() — the one
        # uniform enforcement point of the control-boundary strip)
        for ov, om in zip(eqn.outvars, masks):
            dt = getattr(ov.aval, "dtype", None)
            if dt is not None and str(dt) == "bool":
                om = 0
            self.env[ov] = om

    def _call_sub(
        self, sub: jcore.Jaxpr, consts: tuple, in_masks: Sequence[int],
        visit,
    ) -> List[int]:
        """Enter a sub-jaxpr with 1:1 operand->invar mask seeding and
        return its outvar masks. Seeding OVERWRITES: jax caches traced
        helper jaxprs (clip, where, take, ...), so two call sites can
        share the very same Var objects — OR-accumulating across sites
        would leak one call's taint into every other (a clip used on a
        time value somewhere would time-taint the refill step's cursor
        clip). Each precise call re-propagates the shared body under its
        own operand masks; the body's bindings are recomputed, so
        clobbering a previous site's is sound."""
        self._seed_consts(sub, consts)
        for v, m in zip(sub.invars, in_masks):
            self.env[v] = int(m)
        self._run(sub, visit)
        return [self.read(ov) for ov in sub.outvars]

    def _run(self, jaxpr: jcore.Jaxpr, visit, top: bool = False) -> None:
        for eqn in jaxpr.eqns:
            if top:
                self.top_eqn = eqn
            if visit is not None:
                visit(eqn, self.read)
            name = eqn.primitive.name
            subs = _sub_jaxprs(eqn)
            # precise call handling: jit (1:1 invars) and cond (operand
            # k+1 -> branch invar k; outvars joined across branches, the
            # predicate excluded — control flow doesn't launder data
            # taint). Shape-mismatched calls fall through to the
            # conservative union path below.
            if name == "jit" and len(subs) == 1 and len(
                subs[0][0].invars
            ) == len(eqn.invars):
                in_masks = [self.read(iv) for iv in eqn.invars]
                outs = self._call_sub(
                    subs[0][0], subs[0][1], in_masks, visit
                )
                self._set_outs(eqn, outs)
                continue
            if name == "cond" and subs and all(
                len(sub.invars) == len(eqn.invars) - 1 for sub, _ in subs
            ):
                in_masks = [self.read(iv) for iv in eqn.invars]
                outs: Optional[List[int]] = None
                for sub, consts in subs:
                    res = self._call_sub(sub, consts, in_masks[1:], visit)
                    outs = res if outs is None else [
                        a | b for a, b in zip(outs, res)
                    ]
                self._set_outs(eqn, outs or [])
                continue
            # precise while handling (r19): 1:1 carry seeding iterated
            # to a fixpoint. The old conservative union made every carry
            # slot of a sequential loop carry every OTHER slot's taint —
            # sound, but it damned the device-loop generation boundary,
            # whose corpus-fold/mutate fori_loops legitimately carry
            # schedule-root seeds NEXT TO ctl rows and coverage words in
            # one carry. Per-slot masks joined with the body's own
            # outputs per pass model exactly how a while carry re-enters;
            # real cross-slot flows still propagate (they appear in the
            # body's dataflow), so nothing is laundered. The cond jaxpr
            # produces only the loop predicate (a bool — control, not
            # value, flow) but is still walked for visit() coverage.
            if name == "while" and {
                "cond_nconsts", "body_nconsts", "cond_jaxpr", "body_jaxpr",
            } <= set(eqn.params):
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                cj = eqn.params["cond_jaxpr"]
                bj = eqn.params["body_jaxpr"]
                in_masks = [self.read(iv) for iv in eqn.invars]
                cconsts = in_masks[:cn]
                bconsts = in_masks[cn:cn + bn]
                carry = in_masks[cn + bn:]
                if len(bj.jaxpr.invars) == bn + len(carry) and len(
                    bj.jaxpr.outvars
                ) == len(carry):
                    # bounded: masks only grow in a 5-bit lattice
                    for _ in range(8):
                        outs = self._call_sub(
                            bj.jaxpr, tuple(bj.consts),
                            bconsts + carry, visit,
                        )
                        new = [a | b for a, b in zip(carry, outs)]
                        if new == carry:
                            break
                        carry = new
                    if len(cj.jaxpr.invars) == cn + len(carry):
                        self._call_sub(
                            cj.jaxpr, tuple(cj.consts),
                            cconsts + carry, visit,
                        )
                    self._set_outs(eqn, carry)
                    continue
            # scan gets the same precise treatment (a static-trip-count
            # fori_loop lowers to scan, so the device-loop boundary's
            # sequential fold/mutate loops arrive HERE): consts stay
            # fixed, the carry slots iterate to a fixpoint against the
            # body's carry outputs, the stacked ys join across passes
            if name == "scan" and {
                "num_consts", "num_carry", "jaxpr",
            } <= set(eqn.params):
                nc = eqn.params["num_consts"]
                nk = eqn.params["num_carry"]
                bj = eqn.params["jaxpr"]
                in_masks = [self.read(iv) for iv in eqn.invars]
                consts = in_masks[:nc]
                carry = in_masks[nc:nc + nk]
                xs = in_masks[nc + nk:]
                if len(bj.jaxpr.invars) == len(in_masks) and len(
                    bj.jaxpr.outvars
                ) >= nk:
                    ys: Optional[List[int]] = None
                    for _ in range(8):
                        outs = self._call_sub(
                            bj.jaxpr, tuple(bj.consts),
                            consts + carry + xs, visit,
                        )
                        youts = outs[nk:]
                        ys = youts if ys is None else [
                            a | b for a, b in zip(ys, youts)
                        ]
                        new = [a | b for a, b in zip(carry, outs[:nk])]
                        if new == carry:
                            break
                        carry = new
                    self._set_outs(eqn, carry + (ys or []))
                    continue
            m = 0
            for iv in eqn.invars:
                m |= self.read(iv)
            # loop bodies re-enter with their own outputs: iterate to a
            # fixpoint (bounded — masks only grow in a 5-bit lattice)
            passes = 4 if name in _LOOP_PRIMS and subs else 1
            for _ in range(passes):
                grew = False
                for sub, consts in subs:
                    self._seed_consts(sub, consts)
                    for iv in sub.invars:
                        old = self.env.get(iv, 0)
                        if old | m != old:
                            grew = True
                        self.env[iv] = old | m
                    self._run(sub, visit)
                    for ov_inner in sub.outvars:
                        nm = m | self.read(ov_inner)
                        if nm != m:
                            grew = True
                        m = nm
                if not grew:
                    break
            self._set_outs(eqn, [m] * len(eqn.outvars))


def is_mix_mul(eqn) -> bool:
    """True for the second-stage fmix multiply — exactly one per mix()."""
    if eqn.primitive.name != "mul":
        return False
    return any(lit_value(iv) == FMIX_C2 for iv in eqn.invars)


def backward_invars(jaxpr: jcore.Jaxpr, seeds: Sequence[Any]) -> List[int]:
    """Indices of the jaxpr invars backward-reachable from `seeds` (vars).

    Witness extraction for taint violations: names which function inputs
    actually feed an offending equation. Single-level (does not descend
    into sub-jaxprs — violations are reported at their own level)."""
    defs: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn
    invar_pos = {v: i for i, v in enumerate(jaxpr.invars)}
    seen: set = set()
    hits: set = set()
    stack = [s for s in seeds if not is_literal(s)]
    while stack:
        v = stack.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        if v in invar_pos:
            hits.add(invar_pos[v])
            continue
        eqn = defs.get(v)
        if eqn is None:
            continue
        for iv in eqn.invars:
            if not is_literal(iv):
                stack.append(iv)
    return sorted(hits)


def find_while_eqns(jaxpr: jcore.Jaxpr) -> List[Any]:
    return [e for e, _ in iter_eqns(jaxpr) if e.primitive.name == "while"]


def while_carry_avals(eqn) -> List[Any]:
    """The carry avals of a `while` equation (consts excluded)."""
    nconsts = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
    return [v.aval for v in eqn.invars[nconsts:]]


def while_const_avals(eqn) -> List[Any]:
    nconsts = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
    return [v.aval for v in eqn.invars[:nconsts]]


def aval_sig(aval) -> Tuple[Tuple[int, ...], str]:
    return (tuple(aval.shape), str(aval.dtype))


# ---------------------------------------------------------------- StableHLO


def donated_arg_flags(stablehlo_text: str) -> Dict[int, bool]:
    """{flat arg index -> has tf.aliasing_output} from lowered StableHLO.

    jax marks every donated argument it could alias to an output with a
    `tf.aliasing_output` attribute at lowering time; argument order is
    jax's flatten order of the call's dynamic args, so the flags line up
    with `named_leaves` of the same pytrees."""
    import re

    m = re.search(
        r"func\.func\s+public\s+@main\((.*?)\)\s*->", stablehlo_text, re.S
    )
    if m is None:
        raise ValueError("could not find @main signature in lowered text")
    sig = m.group(1)
    flags: Dict[int, bool] = {}
    for am in re.finditer(
        r"%arg(\d+):\s*[^\s,{]+(?:\s*\{([^{}]*)\})?", sig
    ):
        idx = int(am.group(1))
        attrs = am.group(2) or ""
        flags[idx] = "tf.aliasing_output" in attrs
    if not flags:
        raise ValueError("no arguments parsed from @main signature")
    return flags


def reduced_axes(eqn) -> List[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """[(operand shape, reduced axes), ...] for reduction-style eqns.

    dot_general yields one entry per contracted operand (lhs AND rhs) —
    a lane contraction on either side is a cross-lane coupling."""
    name = eqn.primitive.name
    params = eqn.params
    if not eqn.invars:
        return []
    shape = tuple(getattr(eqn.invars[0].aval, "shape", ()))
    if name in REDUCE_PRIMS:
        axes = params.get("axes")
        if axes is None:
            return []
        return [(shape, tuple(int(a) for a in axes))]
    if name in _CUMULATIVE_PRIMS:
        ax = params.get("axis")
        return [(shape, (int(ax),))] if ax is not None else []
    if name == "sort":
        ax = params.get("dimension")
        return [(shape, (int(ax),))] if ax is not None else []
    if name == "dot_general":
        (lc, rc), _batch = params["dimension_numbers"]
        out = [(shape, tuple(int(a) for a in lc))]
        if len(eqn.invars) > 1:
            rshape = tuple(getattr(eqn.invars[1].aval, "shape", ()))
            out.append((rshape, tuple(int(a) for a in rc)))
        return out
    return []
