"""Layer 1: jaxpr-level determinism/purity rules over the real step program.

For each workload this module builds the ACTUAL sweep configuration the
fuzzer runs — every nemesis clause enabled (crash+wipe, partition, clog,
spike, loss, dup, reorder, skew), the buggify straggler tail on, triage
ctl threaded, coverage instrumented — and traces the donated
`_step_split` program abstractly (ShapeDtypeStructs; no device compute,
no XLA compile). Five rules walk the closed jaxpr / lowered StableHLO:

  callbacks          no host-sync primitive anywhere in the step (a
                     single io_callback/debug.print re-serializes every
                     chunked dispatch on the host and is invisible in
                     tests that only check values).
  rng-taint          (a) schedule purity: any murmur mix touched by
                     `key0` taint must see NOTHING but key0 and the
                     occurrence counters — fault schedules stay pure
                     functions of (seed, clause, k), the invariant
                     `FaultPlan.schedule` mirrors. (b) funnel
                     containment: the per-step key chain's own update
                     must derive from the key alone — protocol state
                     must never leak INTO the RNG funnel carry.
                     (Handler draws keyed off the step chain may fold
                     event identity — e.g. twopc's per-tid vote coin —
                     that is per-seed deterministic and allowed.)
  donation           the hot+cold carry is fully donated/aliased in the
                     lowered program and ConstState leaves never are;
                     plus the structural split: const = {key0, ctl,
                     skew_ppm} exactly, and the `_run` while-loop carry
                     is hot+cold only (key0 leaking back into the carry
                     is the regression the r8 split can silently lose).
  dtype              narrow_fields leaves hold their declared at-rest
                     dtype across the loop carry, time_fields stay i32,
                     and NO float arithmetic touches a time-typed value
                     (the integer-ppm skew bug as a checked rule class).
  lane-independence  no reduction over the lane (batch) axis inside the
                     step outside the allowlist — lanes must stay
                     embarrassingly parallel or sharded sweeps and
                     chunking stop being bit-identical.

All rules fail loudly with leaf/eqn names. Allowlists and suppression:
docs/analysis.md.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import RuleResult
from .jaxprutil import (
    CALLBACK_PRIMS,
    KEY,
    KEY2,
    SALT,
    STATE,
    TIME,
    TaintMap,
    aval_sig,
    backward_invars,
    donated_arg_flags,
    find_while_eqns,
    is_mix_mul,
    iter_eqns,
    reduced_axes,
    while_carry_avals,
    while_const_avals,
)

# default lane count for abstract tracing: a small prime that no
# structural dimension (node count, pool slots, payload width, clause
# rows) uses, so "shape[0] == LANES" identifies the lane axis reliably
LANES = 13
# admission-queue length for the refill trace: a distinct prime, so the
# queue axis can never be mistaken for the lane axis
REFILL_ADMISSIONS = 29

# the refill step's sanctioned lane-axis primitives (engine._refill_apply):
# the retirement rank (cumsum), the admitted count (reduce_sum) and the
# any-retired cond predicate (reduce_or) couple lanes ONLY in the
# seed->lane ASSIGNMENT — never in any admission's trajectory, which stays
# the pure per-seed function chunking/sharding bit-identity needs (the
# refill determinism tests pin exactly that). Everything else in the
# refill step remains subject to the lane rule.
REFILL_LANE_ALLOW = ("cumsum", "reduce_sum", "reduce_or")

# the device-loop step adds ONE lane-axis primitive on top of the refill
# set: the generation-boundary fire predicate `jnp.all(done)` (engine
# `_devloop_apply`) lowers to reduce_and. Like the refill reductions it
# couples lanes only in WHEN the boundary fires — never inside any
# admission's trajectory, which the devloop bit-identity tests pin
# against the host loop lane by lane.
DEVLOOP_LANE_ALLOW = REFILL_LANE_ALLOW + ("reduce_and",)

# cross-device collective primitives: the multi-chip determinism contract
# (docs/multichip.md) says the shard_map'd refill segment contains ZERO of
# these — each device owns its sub-queue/lanes/result buffers and gathers
# happen at segment end on the host. Any future exception must be
# allowlisted by EXACT primitive name in SHARD_COLLECTIVE_ALLOW (empty
# in-tree), never by disabling the walk.
# real jaxpr PRIMITIVE names only (eqn.primitive.name): API sugar like
# jnp/pmean/pshuffle and grouped collectives (axis_index_groups is a
# psum/all_gather PARAM) all lower to these underlying primitives, so
# they are caught via this set — listing non-primitive names here would
# only misstate the coverage.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "pbroadcast", "pgather",
})
SHARD_COLLECTIVE_ALLOW: Tuple[str, ...] = ()

# occurrence counters: the ONLY non-key values a schedule draw may touch
NEUTRAL_LEAVES = frozenset({
    "hot.nem.crash_k", "hot.nem.part_k", "hot.nem.clog_k",
    "hot.nem.spike_k", "hot.nem.reconfig_k", "hot.nem.disk_k",
})
# the schedule key root: ConstState.key0 on the plain partition, carried
# as hot.key0 on the refill partition (a refilled lane adopts a new root)
KEY0_LEAVES = frozenset({"const.key0", "hot.key0"})
KEYCHAIN_LEAVES = frozenset({"hot.key"})

# time-typed leaves (virtual-us offsets): the operands the integer-ppm
# rule guards — float arithmetic on any of these loses microseconds
TIME_LEAF_NAMES = frozenset({
    "hot.clock", "hot.timer", "hot.chaos_at", "hot.part_at",
    "hot.msgs.deliver", "hot.strag.deliver",
    "hot.nem.clog_at", "hot.nem.spike_at", "hot.nem.reconfig_at",
    "hot.nem.disk_at",
    "cold.violation_at", "const.ctl.h_off",
})


def full_fault_plan():
    """Every clause kind at once: the maximal step program (what a storm
    campaign actually compiles; any rule that holds here holds for every
    subset config, which compiles strictly less machinery)."""
    from .. import nemesis as nem

    return nem.FaultPlan(
        name="analysis-full",
        clauses=(
            nem.Crash(wipe_rate=0.3),
            nem.Partition(),
            nem.LinkClog(),
            nem.LatencySpike(),
            nem.MsgLoss(rate=0.05),
            nem.Duplicate(rate=0.05),
            nem.Reorder(rate=0.1, window_us=50_000),
            nem.ClockSkew(max_ppm=50_000),
            nem.Reconfig(),
            nem.DiskFault(torn_rate=0.5),
        ),
    )


def spec_factories() -> Dict[str, object]:
    # one map, derived from the consolidated workload registry
    # (madsim_tpu.workloads) — includes wal (the one hand spec with a
    # durable plane: its hot.dur.* watermark leaves and recovery
    # copy-back are range-certified here) and every speclang-generated
    # entry, which is gated by the same rules as the hand-written specs
    from .. import workloads as registry

    return registry.spec_factories(analysis=True)


def build_verified_sim(
    name: str, lanes: int = LANES, refill: bool = False,
    lineage: bool = False, devloop: bool = False,
):
    """(sim, state, hot, cold, const) — all abstract (ShapeDtypeStructs).

    `state` is the eval_shape of the real `_init` (or, with `refill`, of
    the real `init_refill` with a REFILL_ADMISSIONS-deep queue — the
    continuous-batching carry partition; with `devloop`, of the real
    `init_devloop` — the device-resident search partition, whose step
    additionally contains the whole generation boundary: fold, rank,
    mutate, respawn; with `lineage`, of the causal-lineage carry);
    hot/cold/const the real `split_state` partition. Nothing touches a
    device."""
    from ..nemesis import OCC_CLAUSES, RATE_CLAUSES
    from ..tpu import nemesis as tpun
    from ..tpu.engine import (
        BatchedSim, TriageCtl, make_devloop_plan, split_state,
    )
    from ..tpu.spec import SimConfig

    factories = spec_factories()
    if name not in factories:
        raise ValueError(
            f"unknown workload {name!r} (choose from {sorted(factories)})"
        )
    spec = factories[name]()
    cfg = tpun.compile_plan(
        full_fault_plan(),
        SimConfig(
            horizon_us=2_000_000,
            loss_rate=0.05,
            buggify_delay_rate=0.01,  # straggler side pool in the program
        ),
    )
    plan = None
    if devloop:
        # trace capacities: the population reuses the REFILL_ADMISSIONS
        # prime (same queue axis, same role); ring/seen/window sizes are
        # small distinct values none of which equals LANES, so the lane
        # rule keeps identifying the lane axis by shape alone
        plan = make_devloop_plan(
            cfg, pop=REFILL_ADMISSIONS, top_k=7, seen_cap=64,
        )
    sim = BatchedSim(
        spec, cfg, triage=True, coverage=True, lineage=lineage,
        devloop=plan,
    )
    seeds = jax.ShapeDtypeStruct((lanes,), jnp.uint32)
    if refill or devloop:
        A = REFILL_ADMISSIONS
        qseeds = jax.ShapeDtypeStruct((A,), jnp.uint32)
        qctl = TriageCtl(
            off=jax.ShapeDtypeStruct((A,), jnp.int32),
            occ=jax.ShapeDtypeStruct((A, len(OCC_CLAUSES)), jnp.int32),
            rate_scale=jax.ShapeDtypeStruct(
                (A, len(RATE_CLAUSES)), jnp.float32
            ),
            h_epoch=jax.ShapeDtypeStruct((A,), jnp.int32),
            h_off=jax.ShapeDtypeStruct((A,), jnp.int32),
        )
        if devloop:
            # window G=2: the smallest shape that exercises BOTH boundary
            # branches (next_gen on gen 0, window_done on gen G-1)
            state = jax.eval_shape(
                lambda s, c: sim.init_devloop(s, lanes, c, window=2),
                qseeds, qctl,
            )
        else:
            state = jax.eval_shape(
                lambda s, c: sim.init_refill(s, lanes, c), qseeds, qctl,
            )
    else:
        state = jax.eval_shape(sim._init, seeds)
    hot, cold, const = split_state(state)
    return sim, state, hot, cold, const


def _leaf_names(hot, cold, const) -> List[str]:
    from ..tpu.engine import named_leaves

    return (
        [n for n, _ in named_leaves(hot, "hot")]
        + [n for n, _ in named_leaves(cold, "cold")]
        + [n for n, _ in named_leaves(const, "const")]
    )


def _time_leaves(sim) -> Set[str]:
    names = set(TIME_LEAF_NAMES)
    for f in sim.spec.time_fields:
        names.add(f"hot.node.{f}")
    return names


# refill admission inputs: the queue's seed column and the cursor /
# per-lane admission indices are schedule ROOTS (which work runs next),
# not trajectory material — neutral like the occurrence counters, so a
# refilled lane's re-init draws read as the pure (seed, site, k)
# functions they are. The queue's ctl rows stay STATE like every ctl.
REFILL_NEUTRAL = frozenset({
    "const.queue.seeds", "cold.refill.cursor", "cold.refill.admitted",
})

# device-loop search cursors: the same schedule-root argument extended to
# the in-jit generation boundary. The queue seed column now RIDES THE
# CARRY (the boundary rewrites it from the mutated ring, so it is
# hot.queue.seeds on this partition), and the boundary derives the next
# generation's seeds from the MetaRng cursor (meta_key/counter — the
# host MetaRng's murmur chain, deliberately disjoint from every lane's
# schedule key), the fresh-seed counter, and the corpus ring's seed
# column + row count (parent picks gather through them). All of these
# decide WHICH work runs next, never how any admission's trajectory
# unfolds — exactly the refill-queue argument. Everything else in the
# DevLoop carry (ring ctl rows, novelty bits, coverage union, dedup
# hashes, archives) stays STATE: those values flow into ctl rows and
# result buffers, and the rng-taint rule must keep proving they never
# reach a schedule mix.
DEVLOOP_NEUTRAL = frozenset({
    "hot.queue.seeds",
    "cold.loop.meta_key", "cold.loop.counter", "cold.loop.next_fresh",
    "cold.loop.ring_n", "cold.loop.ring_seed",
})


def _invar_masks(names: Sequence[str], time_leaves: Set[str]) -> List[int]:
    masks = []
    for n in names:
        if n in KEY0_LEAVES:
            masks.append(KEY)
        elif n in KEYCHAIN_LEAVES:
            masks.append(KEY2)
        elif n in NEUTRAL_LEAVES or n in REFILL_NEUTRAL or n in DEVLOOP_NEUTRAL:
            masks.append(0)
        elif n in time_leaves:
            masks.append(STATE | TIME)
        else:
            masks.append(STATE)
    return masks


# ------------------------------------------------------------------- rules


def check_callbacks(closed, where: str = "step") -> RuleResult:
    """No host-sync primitives anywhere in the program."""
    res = RuleResult("callbacks")
    for eqn, depth in iter_eqns(closed.jaxpr):
        res.checked += 1
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS or "callback" in name:
            res.add(
                where,
                f"host-sync primitive `{name}` at nesting depth {depth} — "
                "the jitted step must never round-trip to the host",
            )
    return res


def check_rng_taint(
    closed,
    invar_names: Sequence[str],
    time_leaves: Set[str],
    where: str = "step",
    key_out_index: Optional[int] = None,
    salt_values: Sequence[int] = (),
) -> RuleResult:
    """Schedule purity + funnel containment over the murmur mix eqns.

    The refill trace passes this check STRICTLY too: the admission
    inputs a refilled lane's chain root derives from (queue seed column,
    cursor, admission ids) are classified neutral (REFILL_NEUTRAL — they
    are schedule roots, like the occurrence counters), retirement FLAGS
    shed their taint at the bool boundary (control flow doesn't launder
    values; jaxprutil.TaintMap), and the re-init select then carries the
    chain key alone."""
    res = RuleResult("rng-taint")
    masks = _invar_masks(invar_names, time_leaves)
    # taint per mix eqn is ACCUMULATED across visits and judged after the
    # walk: loop bodies are re-propagated to a fixpoint, so the taint a
    # mix sees can GROW on pass >= 2 — gating on first visit would throw
    # the later, larger mask away and miss carry-borne violations
    mix_taint: Dict[int, Tuple[object, int, object]] = {}
    tm = TaintMap(closed, masks, salt_values=salt_values)

    def visit(eqn, read):
        if not is_mix_mul(eqn):
            return
        m = 0
        for iv in eqn.invars:
            m |= read(iv)
        prev = mix_taint.get(id(eqn))
        if prev is not None:
            m |= prev[1]
        # witness via the enclosing TOP-LEVEL eqn: an offending mix
        # inside an inline-jitted helper still names real leaves
        mix_taint[id(eqn)] = (eqn, m, tm.top_eqn)

    tm.run(visit)
    res.checked += len(mix_taint)
    flagged = [
        (eqn, m, top)
        for eqn, m, top in mix_taint.values()
        if (m & KEY) and (m & (STATE | TIME | KEY2 | SALT))
    ]
    for eqn, m, top in flagged:
        src = top if top is not None else eqn
        hits = backward_invars(closed.jaxpr, list(src.invars))
        offenders = [
            invar_names[i]
            for i in hits
            if masks[i] & (STATE | TIME)
        ][:6]
        res.add(
            where,
            "schedule-purity violation: a key0-rooted draw mixes "
            f"non-schedule material (taint {m:#x}; reaches "
            f"{offenders or ['<literal/chain>']}) — fault schedules must "
            "be pure functions of (seed, clause, occurrence)",
        )
    if key_out_index is not None:
        ov = closed.jaxpr.outvars[key_out_index]
        m = tm.read(ov)
        res.checked += 1
        if m & (STATE | TIME | SALT | KEY):
            res.add(
                where,
                f"RNG funnel contaminated: the step's key-chain update "
                f"carries taint {m:#x} (expected the chain key alone) — "
                "protocol/config state must never feed the PRNG carry",
            )
    return res


def check_dtype(
    closed,
    sim,
    hot,
    out_template,
    invar_names: Sequence[str],
    where: str = "step",
) -> RuleResult:
    """Narrow at-rest dtypes across the carry + no float-on-time math."""
    res = RuleResult("dtype")
    h2 = out_template[0]
    narrow = dict(sim.spec.narrow_fields or {})
    for f, dt in narrow.items():
        res.checked += 1
        want = str(jnp.dtype(dt))
        got_in = str(getattr(hot.node, f).dtype)
        got_out = str(getattr(h2.node, f).dtype)
        if got_in != want:
            res.add(
                where,
                f"node.{f} enters the carry as {got_in}, declared {want}",
            )
        if got_out != want:
            res.add(
                where,
                f"node.{f} leaves the step as {got_out}, declared {want} — "
                "the at-rest narrowing was silently widened in the carry",
            )
    for f in sim.spec.time_fields:
        res.checked += 1
        got = str(getattr(h2.node, f).dtype)
        if got != "int32":
            res.add(
                where,
                f"time field node.{f} is {got} in the carry — time-typed "
                "values must stay i32 (epoch-rebased offsets)",
            )

    # float-on-time: forward TIME taint; a floating-dtype output of an
    # ARITHMETIC/conversion eqn with a TIME-tainted operand is the
    # f32-skew bug class. Call primitives (their bodies are recursed
    # into, so real arithmetic inside is still seen) and dtype-preserving
    # data movement (a gather whose INDEX is time-derived moves float
    # data, it doesn't do float math on a time value) are excluded —
    # the refill step's cond/gather/select plumbing made the
    # every-primitive form fire on pure routing.
    time_leaves = _time_leaves(sim)
    masks = _invar_masks(invar_names, time_leaves)
    hits: List[Tuple[object, str]] = []
    from .jaxprutil import _sub_jaxprs

    move_prims = frozenset({
        "select_n", "gather", "scatter", "scatter-add", "concatenate",
        "broadcast_in_dim", "transpose", "reshape", "squeeze",
        "expand_dims", "slice", "dynamic_slice", "dynamic_update_slice",
        "copy", "rev",
    })

    def visit(eqn, read):
        if eqn.primitive.name in move_prims or _sub_jaxprs(eqn):
            return
        tainted = any(read(iv) & TIME for iv in eqn.invars)
        if not tainted:
            return
        for ov in eqn.outvars:
            dt = getattr(ov.aval, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                hits.append((eqn, str(dt)))

    TaintMap(closed, masks).run(visit)
    res.checked += 1
    for eqn, dt in hits:
        res.add(
            where,
            f"float arithmetic on a time-typed value: `{eqn.primitive.name}`"
            f" -> {dt} with TIME-tainted input — f32 loses integer "
            "microseconds past 2^24 us; use exact int math "
            "(scale_delay_ppm)",
        )
    return res


def check_lane_independence(
    closed,
    lanes: int = LANES,
    where: str = "step",
    allow: Sequence[str] = (),
) -> RuleResult:
    """No reduction over the lane axis anywhere in the step.

    A reduced/contracted/sorted dimension of size `lanes` is flagged in
    ANY axis position (not just axis 0): `lanes` is chosen as a small
    prime no structural dimension uses, so a transposed lane axis is
    still caught. dot_general is checked on BOTH contracted operands.
    `allow` names primitives permitted to cross lanes (empty by default:
    decode-side reductions live in `_summary_reduction`, outside the
    step)."""
    res = RuleResult("lane-independence")
    allowed = set(allow)
    for eqn, depth in iter_eqns(closed.jaxpr):
        entries = reduced_axes(eqn)
        if not entries:
            continue
        res.checked += 1
        for shape, axes in entries:
            hit = [
                a for a in axes if a < len(shape) and shape[a] == lanes
            ]
            if not hit:
                continue
            if eqn.primitive.name in allowed:
                continue
            res.add(
                where,
                f"cross-lane reduction: `{eqn.primitive.name}` over axis "
                f"{hit[0]} of {shape} (the lane-sized dim) at depth {depth}"
                " — lanes must stay independent for sharding/chunking "
                "bit-identity",
            )
            break
    return res


def check_collectives(
    closed,
    where: str = "sharded-segment",
    allow: Sequence[str] = SHARD_COLLECTIVE_ALLOW,
) -> RuleResult:
    """No cross-device collective primitive anywhere in the shard_map'd
    refill segment (recursing every sub-jaxpr: the shard_map body, its
    while_loop, the retire-and-admit cond). Folded into the
    lane-independence rule: a cross-device collective is exactly a
    cross-lane coupling lifted to the mesh axis, and it breaks the same
    bit-identity contract. `allow` names permitted primitives EXACTLY
    (empty in-tree)."""
    res = RuleResult("lane-independence")
    allowed = set(allow)
    for eqn, depth in iter_eqns(closed.jaxpr):
        res.checked += 1
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS and name not in allowed:
            res.add(
                where,
                f"cross-device collective `{name}` at nesting depth "
                f"{depth} inside the sharded refill segment — devices "
                "must stay independent between segment boundaries "
                "(allowlist by exact primitive in "
                "SHARD_COLLECTIVE_ALLOW if ever intended)",
            )
    return res


def check_step_donation(
    step_fn,
    hot,
    cold,
    const,
    hot_names: Sequence[str],
    cold_names: Sequence[str],
    const_names: Sequence[str],
    where: str = "step",
    res: Optional[RuleResult] = None,
) -> RuleResult:
    """Lower `step_fn(hot, cold, const)` with the carry donated and assert
    every hot+cold leaf is aliased to an output while no const leaf is."""
    res = res or RuleResult("donation")
    step = jax.jit(step_fn, donate_argnums=(0, 1))
    text = step.lower(hot, cold, const).as_text()
    flags = donated_arg_flags(text)
    names = list(hot_names) + list(cold_names) + list(const_names)
    res.checked += len(names)
    for i, n in enumerate(names):
        donated = flags.get(i, False)
        is_const = n.startswith("const.")
        if not is_const and not donated:
            res.add(
                where,
                f"carry leaf {n} is NOT donated/aliased in the lowered "
                "step — the sweep would allocate a second copy of it per "
                "dispatch segment",
            )
        if is_const and donated:
            # unreachable under current jax semantics (const is outside
            # donate_argnums here, and only donated args get aliasing
            # attributes) — kept as a sanity check of that lowering
            # assumption; the load-bearing const protection is the
            # while-carry check (check_run_carry) + the structural split
            res.add(
                where,
                f"ConstState leaf {n} IS donated/aliased — loop-invariant "
                "operands must never rotate through the donation",
            )
    return res


def check_run_carry(
    closed_run,
    hot,
    cold,
    const,
    where: str = "run",
    res: Optional[RuleResult] = None,
) -> RuleResult:
    """The sweep's while-loop carry must be hot+cold (+counter) exactly,
    with every const leaf entering as a loop-invariant operand."""
    from ..tpu.engine import named_leaves

    res = res or RuleResult("donation")
    res.checked += 1
    whiles = find_while_eqns(closed_run.jaxpr)
    if not whiles:
        res.add(where, "no while_loop found — sweep structure changed?")
        return res
    weqn = whiles[0]
    got = sorted(aval_sig(a) for a in while_carry_avals(weqn))
    want = sorted(
        [aval_sig(x) for _, x in named_leaves(hot)]
        + [aval_sig(x) for _, x in named_leaves(cold)]
        + [((), "int32")]  # the loop counter
    )
    if got != want:
        from collections import Counter

        extra = Counter(got) - Counter(want)
        missing = Counter(want) - Counter(got)
        # jax's while_loop itself moves a carry leaf that the body returns
        # untouched into the loop-invariant operands: such a leaf no
        # longer rotates through the carry, which is what this rule wants
        hoisted = Counter(aval_sig(a) for a in while_const_avals(weqn))
        hoisted.subtract(aval_sig(x) for _, x in named_leaves(const, "const"))
        missing -= +hoisted
        if extra or missing:
            res.add(
                where,
                "while-loop carry != hot+cold (+counter): extra "
                f"{sorted(extra.elements())}, missing "
                f"{sorted(missing.elements())} — a ConstState leaf leaked "
                "into (or a carry leaf fell out of) the sweep carry",
            )
    cdict: Dict[Tuple, int] = {}
    for a in while_const_avals(weqn):
        sig = aval_sig(a)
        cdict[sig] = cdict.get(sig, 0) + 1
    for n, x in named_leaves(const, "const"):
        sig = aval_sig(x)
        if cdict.get(sig, 0) <= 0:
            res.add(
                where,
                f"{n} is not a loop-invariant operand of the sweep "
                "while-loop (missing from the body consts)",
            )
        else:
            cdict[sig] -= 1
    return res


def check_donation(sim, state, hot, cold, const, where: str = "step") -> RuleResult:
    """Donated/aliased carry coverage + the hot/cold/const structural split.

    Three partitions are legal (engine.split_state): the plain sweep's
    const = {key0, ctl, skew_ppm}; the refill sweep's inverted split —
    key0/ctl/skew IN the carry (a refilled lane rewrites them from its
    new admission) with the admission queue as the only const; and the
    device-loop sweep, where NOTHING is loop-invariant — the generation
    boundary rewrites even the admission queue from the mutated corpus
    ring, so the queue rides the carry, the DevLoop search state rides
    cold, and const is EMPTY. Which one applies is read off the state's
    own structure."""
    from ..tpu.engine import carry_partition

    res = RuleResult("donation")
    devloop = getattr(state, "loop", None) is not None
    refill = state.refill is not None
    # the engine's own introspection hook IS the name source: if the
    # split and the hook ever disagree, this rule is checking the wrong
    # partition and should fail loudly with it
    part = carry_partition(state)
    hot_names = [f"hot.{n}" for n in part["hot"]]
    cold_names = [f"cold.{n}" for n in part["cold"]]
    const_names = [f"const.{n}" for n in part["const"]]

    res.checked += 1
    if devloop:
        # (1'') device-loop structural split: const is EMPTY (everything
        # the boundary can rewrite must be donated), the queue seed/ctl
        # rows ride hot (the boundary respawns them from the ring), and
        # the DevLoop search carry rides cold
        if const_names:
            res.add(
                where,
                "device-loop const must be empty — the generation "
                f"boundary rewrites everything, but found {const_names}",
            )
        if "hot.queue.seeds" not in hot_names:
            res.add(
                where,
                "device-loop carry without hot.queue.seeds — the "
                "boundary cannot respawn the next generation's queue",
            )
        if "hot.key0" not in hot_names:
            res.add(
                where,
                "device-loop carry without hot.key0 — a respawned lane "
                "cannot adopt its admission's schedule root",
            )
        if not any(n.startswith("cold.loop.") for n in cold_names):
            res.add(
                where,
                "device-loop state without cold.loop.* DevLoop leaves",
            )
    elif refill:
        # (1') refill structural split: the queue is const, the (now
        # per-admission) key0/ctl ride the carry, and no queue leaf may
        # leak into the donated carry
        if "const.queue.seeds" not in const_names:
            res.add(where, "refill state without a const admission queue")
        if "hot.key0" not in hot_names:
            res.add(
                where,
                "refill carry without hot.key0 — a refilled lane cannot "
                "adopt its admission's schedule root",
            )
        if sim.triage and not any(
            n.startswith("hot.ctl.") for n in hot_names
        ):
            res.add(where, "refill carry without per-lane TriageCtl rows")
        leaked = [
            n for n in hot_names + cold_names
            if n.split(".", 1)[1].startswith("queue")
        ]
        if leaked:
            res.add(where, f"queue leaves leaked into the carry: {leaked}")
    else:
        # (1) structural split: const is exactly key0 + ctl (+ skew_ppm)
        if sim.triage and not any(
            n.startswith("const.ctl.") for n in const_names
        ):
            res.add(where, "TriageCtl leaves missing from ConstState")
        if "const.key0" not in const_names:
            res.add(
                where,
                "key0 is not in ConstState — if it rides the carry, donation "
                "rotates the schedule root through fresh buffers every segment",
            )
        for n in ("key0", "ctl"):
            leaked = [
                h for h in hot_names + cold_names
                if h.split(".", 1)[1].startswith(n)
            ]
            if leaked:
                res.add(
                    where,
                    f"loop-invariant leaf leaked into the carry: {leaked}",
                )

    # (2) lowered donation flags on the real _step_split program
    check_step_donation(
        lambda h, c, k: sim._step_split(h, c, k),
        hot, cold, const, hot_names, cold_names, const_names, where, res,
    )

    # (3) the production `_run` while-loop carries hot+cold ONLY
    run_fn = getattr(type(sim)._run, "__wrapped__", None)
    if run_fn is not None:
        closed_run = jax.make_jaxpr(lambda st: run_fn(sim, st, 8))(state)
    else:  # trace through the jitted wrapper (shows up as a jit eqn)
        closed_run = jax.make_jaxpr(lambda st: sim._run(st, 8))(state)
    check_run_carry(closed_run, hot, cold, const, where, res)
    return res


# --------------------------------------------------- the shared trace


import dataclasses


@dataclasses.dataclass
class WorkloadTrace:
    """ONE abstract trace of a workload's real programs, shared by EVERY
    jaxpr-level rule (purity, taint, donation, dtype, lane, range).

    Tracing is the dominant cost of a Layer-1/Layer-3 run (seconds per
    workload; the rules themselves are milliseconds of jaxpr walking),
    so it is hoisted here and cached per (workload, lanes): the CLI, the
    range certifier and the test suite all reuse the same trace instead
    of re-tracing per rule. Donation additionally lowers the step — that
    stays inside check_donation, the only consumer of StableHLO."""

    name: str
    lanes: int
    sim: Any
    state: Any
    hot: Any
    cold: Any
    const: Any
    closed_step: Any  # jaxpr of the donated _step_split (the sweep body)
    out_template: Any  # eval_shape of _step_split: (h2, c2, rec)
    closed_init: Any  # jaxpr of _init (runs once, draws schedule roots)
    init_template: Any  # eval_shape of _init: the full SimState
    names: List[str]  # invar leaf names (hot./cold./const. prefixed)
    out_names: List[str]  # outvar leaf names (hot./cold./rec. prefixed)
    invars_avals: List[Any]
    time_leaves: Set[str]
    refill: bool = False  # tracing the continuous-batching partition?
    devloop: bool = False  # tracing the device-resident search partition?
    sharded: bool = False  # also tracing the shard_map'd segment?
    closed_sharded: Any = None  # jaxpr of the multi-chip segment program


_TRACE_CACHE: Dict[Tuple[str, int], WorkloadTrace] = {}


def get_trace(name: str, lanes: int = LANES, log=None) -> WorkloadTrace:
    """The per-workload trace, built once per process (abstract only:
    ShapeDtypeStructs, no XLA compile, no device). A `<workload>-refill`
    name traces the SAME workload's continuously batched step (the
    refill carry partition + a REFILL_ADMISSIONS-deep queue) — the
    target `make analyze` runs every rule against alongside the plain
    partitions."""
    from ..tpu.engine import named_leaves

    key = (name, lanes)
    cached = _TRACE_CACHE.get(key)
    if cached is not None:
        return cached
    sharded = name.endswith("-sharded")
    base = name[: -len("-sharded")] if sharded else name
    refill = base.endswith("-refill")
    base = base[: -len("-refill")] if refill else base
    devloop = base.endswith("-devloop")
    base = base[: -len("-devloop")] if devloop else base
    lineage = base.endswith("-lineage")
    base = base[: -len("-lineage")] if lineage else base
    if sharded and not refill:
        raise ValueError(
            f"{name!r}: only the refill step has a sharded trace target"
        )
    if log:
        log(f"[analysis] tracing {name} step program (L={lanes}) ...")
    sim, state, hot, cold, const = build_verified_sim(
        base, lanes=lanes, refill=refill, lineage=lineage, devloop=devloop,
    )
    closed_sharded = None
    if sharded:
        # the multi-chip segment: the EXACT engine._sharded_segment
        # program, traced abstractly over a 1-device mesh (the mesh size
        # changes block shapes, never the primitive vocabulary — a
        # collective would appear in this jaxpr at any device count)
        import numpy as _np

        mesh = jax.sharding.Mesh(_np.array(jax.devices()[:1]), ("devices",))
        stacked = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), state
        )
        closed_sharded = jax.make_jaxpr(
            lambda st: sim._sharded_segment(mesh, 8)(st)
        )(stacked)
    trace = _finish_trace(
        sim, state, hot, cold, const, name=name, lanes=lanes,
        refill=refill, devloop=devloop, sharded=sharded,
        closed_sharded=closed_sharded,
    )
    _TRACE_CACHE[key] = trace
    return trace


def _finish_trace(
    sim, state, hot, cold, const, name: str, lanes: int,
    refill: bool = False, devloop: bool = False, sharded: bool = False,
    closed_sharded=None,
) -> WorkloadTrace:
    """The shared trace-construction tail (abstract jaxprs + leaf-name
    registries) over an already-built sim/state partition — split out of
    get_trace so `trace_sim` can certify ARBITRARY (spec, config) pairs,
    not just the in-tree workload registry."""
    from ..tpu.engine import named_leaves

    closed = jax.make_jaxpr(sim._step_split)(hot, cold, const)
    out_template = jax.eval_shape(sim._step_split, hot, cold, const)
    seeds = jax.ShapeDtypeStruct((lanes,), jnp.uint32)
    closed_init = jax.make_jaxpr(sim._init)(seeds)
    init_template = jax.eval_shape(sim._init, seeds)
    h2, c2, rec = out_template
    out_names = (
        [n for n, _ in named_leaves(h2, "hot")]
        + [n for n, _ in named_leaves(c2, "cold")]
        + [n for n, _ in named_leaves(rec, "rec")]
    )
    return WorkloadTrace(
        name=name, lanes=lanes, sim=sim, state=state,
        hot=hot, cold=cold, const=const,
        closed_step=closed, out_template=out_template,
        closed_init=closed_init, init_template=init_template,
        names=_leaf_names(hot, cold, const),
        out_names=out_names,
        invars_avals=(
            [x for _, x in named_leaves(hot, "hot")]
            + [x for _, x in named_leaves(cold, "cold")]
            + [x for _, x in named_leaves(const, "const")]
        ),
        time_leaves=_time_leaves(sim),
        refill=refill,
        devloop=devloop,
        sharded=sharded,
        closed_sharded=closed_sharded,
    )


def trace_sim(sim, name: str = "custom", lanes: int = LANES) -> WorkloadTrace:
    """A WorkloadTrace over an ARBITRARY BatchedSim (uncached, abstract —
    ShapeDtypeStructs only, no compile, no device).

    The autotuner's Tier-B gate re-runs the range certifier on every
    TUNED config through this before it is cached
    (madsim_tpu/tune.py, docs/tuning.md): the in-tree `get_trace`
    registry pins the shipped configs, but a tuned pool layout is a new
    program and must re-earn its range certificate."""
    from ..tpu.engine import split_state

    seeds = jax.ShapeDtypeStruct((lanes,), jnp.uint32)
    state = jax.eval_shape(sim._init, seeds)
    hot, cold, const = split_state(state)
    return _finish_trace(sim, state, hot, cold, const, name=name, lanes=lanes)


def verify_workload(
    name: str, lanes: int = LANES, log=print,
    trace: Optional[WorkloadTrace] = None,
    rules: Optional[Sequence[str]] = None,
) -> List[RuleResult]:
    """Run the selected Layer-1 jaxpr rules over workload `name`'s shared
    trace (the lane-width reuse trick: a small fixed lane count keeps
    tracing seconds-fast and identifies the lane axis unambiguously).
    `rules=None` runs them all; a filter skips unselected checks
    entirely — notably `donation`, the only rule that LOWERS the step to
    StableHLO rather than just walking the trace."""
    from ..tpu.engine import COV_SALT, named_leaves

    trace = trace or get_trace(name, lanes=lanes, log=log)
    want = None if rules is None else set(rules)

    def on(rule: str) -> bool:
        return want is None or rule in want

    sim = trace.sim
    closed = trace.closed_step
    out_template = trace.out_template
    names = trace.names
    time_leaves = trace.time_leaves

    where = f"{name}:_step_split"
    results = []
    if on("callbacks"):
        results.append(check_callbacks(closed, where))
    if on("rng-taint"):
        # outvar index of the step's key-chain update (h2.key)
        h2_names = [n for n, _ in named_leaves(out_template[0], "hot")]
        key_out = h2_names.index("hot.key")
        results.append(check_rng_taint(
            closed, names, time_leaves, where,
            key_out_index=key_out, salt_values=(COV_SALT,),
        ))
    if on("dtype"):
        results.append(check_dtype(
            closed, sim, trace.hot, out_template, names, where,
        ))
    if on("lane-independence"):
        results.append(check_lane_independence(
            closed, trace.lanes, where,
            allow=(
                DEVLOOP_LANE_ALLOW if trace.devloop
                else REFILL_LANE_ALLOW if trace.refill
                else ()
            ),
        ))
        if trace.sharded:
            # the multi-chip face of the same rule: the whole shard_map'd
            # segment program must contain zero cross-device collectives
            # (exact-primitive allowlist, empty in-tree)
            results.append(check_collectives(
                trace.closed_sharded, f"{name}:_sharded_segment",
            ))
    if on("donation"):
        results.append(check_donation(
            sim, trace.state, trace.hot, trace.cold, trace.const,
            f"{name}:_run",
        ))
    # init runs once per sweep but draws the schedule roots: callbacks +
    # purity hold there too (seeds are the key root at init)
    closed_init = trace.closed_init
    init_names = ["const.key0"] + [
        f"const.ctl.{i}" for i in range(len(closed_init.jaxpr.invars) - 1)
    ]
    if on("callbacks"):
        results.append(check_callbacks(closed_init, f"{name}:_init"))
    if on("rng-taint"):
        results.append(check_rng_taint(
            closed_init,
            init_names[: len(closed_init.jaxpr.invars)],
            set(),
            f"{name}:_init",
            salt_values=(COV_SALT,),
        ))
    if log:
        bad = sum(len(r.violations) for r in results)
        log(
            f"[analysis] {name}: {len(closed.jaxpr.eqns)} step eqns, "
            f"{bad} violations"
        )
    return results
