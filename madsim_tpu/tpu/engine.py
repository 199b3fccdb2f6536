"""The batched discrete-event simulation engine: thousands of seeds per step.

This is the TPU-native re-design of the reference's executor + virtual clock +
network (SURVEY.md §3.1-3.2, §7): instead of one OS thread per seed
(runtime/builder.rs:118-136), the whole discrete-event loop is a single jitted
step function over lane-major state tensors:

    clock        [L]        virtual time per lane (int32 us OFFSET)
    epoch        [L]        rebase count: abs time = epoch * REBASE_US + off
    key          [L]        per-lane hash-chain PRNG word (see prng.py)
    alive        [L, N]     node liveness (crash/restart chaos)
    timer        [L, N]     per-node timer deadline
    node state   [L, N, ...]protocol pytree
    message pool [L, N, CK] validity bits + [L, CK] per-candidate ring

One step = (1) advance each lane to its next event WINDOW — the conservative
parallel-DES lookahead [t_next, t_next + latency_lo): messages emitted inside
the window arrive after it, so in-window events on different nodes are
causally independent, (2) per node, pick its earliest in-window event —
message delivery or timer fire, never both (per-node order is exact) — and
run `on_message`/`on_timer` with the node's own event time, (3) run
crash/restart + partition chaos (the window collapses to the exact chaos
instant on those steps), (4) roll loss + latency (+ the heavy-tail buggify
coin) for every emitted message (the `test_link` analog,
net/network.rs:261-269), stamped from the emitting node's event time, and
pack survivors into free pool slots, (5) check invariants, (6) rebase lanes
whose clock offset crossed REBASE_US (unbounded virtual time with int32
hot-path arithmetic; see spec.REBASE_US).

Pool layout (the round-4 redesign, iterated under measurement): a message's
(deliver time, kind, payload) lives ONCE in a per-candidate ring slot
(`[L, CK]`, CK = send positions x depth; see MsgPool), and only a validity
bit is kept per destination (`[L, N, CK]`). Consequences:
  * the DELIVERY side needs no destination matching at all — node n's
    pending set is the static slice `valid[:, n, :]` over the shared ring,
    and its earliest event is a plain min-reduce (the r3 layout's `[L,S,N]`
    one-hot expansions and `[L,N,S,P]` payload contraction, measured as the
    dominant step cost, are gone);
  * the PACK side is pure elementwise writes: a send takes the first of
    its K ring slots unreferenced by every destination, dst routing via a
    tiny `[L,C,N]` one-hot; with all K pending the send is dropped and
    counted (`overflow`) rather than corrupted;
  * the message's source is a compile-time constant per slot
    (`src_of_slot`), and pool bandwidth — the pool is rewritten every step,
    so its bytes are a top step cost — is ~N x smaller than materializing
    per-destination copies.

Heavy-tail (buggify) delays ride a small side pool with one region per
candidate position (`[L, C, K4]`): tail messages are rare, so the side
pool's dst-matching one-hots stay tiny while the main pool keeps its
latency bound (which is also the lookahead bound).

Lanes are embarrassingly parallel, so the lane axis shards cleanly over a
device mesh (`shard_state`); the node axis (dim 1 of every per-node tensor,
including the pool) can additionally be sharded for large clusters.

Determinism: jitted XLA programs are deterministic, and all randomness comes
from the per-lane hash-chain keys derived from the seed — one seed => one
bit-exact trajectory per backend (the per-backend determinism contract of
SURVEY.md §7 step 1). Lane-position independence: no draw ever folds the
lane INDEX, only the lane SEED, so a seed's trajectory is identical in any
batch, any chunk, any mesh sharding.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry
from . import bitpack, prng
from .spec import (
    EID_NONE,
    INF_GUARD,
    INF_US,
    Outbox,
    ProtocolSpec,
    REBASE_US,
    SimConfig,
    derate_horizon,
)
from ..nemesis import (
    COIN_DENOM,
    FIRE_INDEX,
    FIRE_KINDS,
    key_from_seed,
    META_SITE_DRAW,
    mutation_vocab,
    OCC_CLAUSES,
    OCC_ROW,
    RATE_CLAUSES,
    RATE_ROW,
    TRIAGE_BIT,
    NEM_SITE_CLOG_DST,
    NEM_SITE_CLOG_HEAL,
    NEM_SITE_CLOG_IV,
    NEM_SITE_CLOG_SRC,
    NEM_SITE_CRASH_DOWN,
    NEM_SITE_CRASH_IV,
    NEM_SITE_CRASH_VICTIM,
    NEM_SITE_CRASH_WIPE,
    NEM_SITE_DISK_DOWN,
    NEM_SITE_DISK_IV,
    NEM_SITE_DISK_SLOW,
    NEM_SITE_DISK_TORN,
    NEM_SITE_DISK_VICTIM,
    NEM_SITE_PART_HEAL,
    NEM_SITE_PART_IV,
    NEM_SITE_PART_SIDE,
    NEM_SITE_RECONF_DUR,
    NEM_SITE_RECONF_IV,
    NEM_SITE_RECONF_VICTIM,
    NEM_SITE_SKEW,
    NEM_SITE_SPIKE_DUR,
    NEM_SITE_SPIKE_IV,
    NET_SITE_DUP,
    NET_SITE_NEM_LOSS,
    NET_SITE_REORDER,
    NET_SITE_REORDER_EXTRA,
)


# --------------------------------------------------------------------------
# coverage instrumentation (the explorer's novelty signal; madsim_tpu/explore)
# --------------------------------------------------------------------------
# Each lane accumulates a fixed-width bitmap of EVENT CLASSES it exercised:
# one bit per hash of (node, event type, state-transition bucket), folded
# through the same murmur3 chain as every other draw. The encoding is a pure
# function of trace-visible event fields — (dst node, src, msg kind,
# payload[0] magnitude bucket) for deliveries, (node,) for timer fires — so
# the pure-Python mirror in explore.py can recompute a lane's exact bitmap
# from its TraceRecord stream (the coverage analog of the nemesis
# schedule-mirror invariant). 8192 bits ~ AFL's map scale for protocols of
# this size; collisions just merge two classes, which coverage search
# tolerates by design.

COV_WORDS = 256  # u32 words per lane bitmap
COV_BITS = COV_WORDS * 32  # 8192 coverage bits
COV_SALT = 0x5EEDC0DE  # base key of the event-class hash chain
# The event-class hash folds EXACTLY these fields, in this order, on BOTH
# faces: the in-jit chain in _step_traced (step 7b) and the pure trace
# mirror explore.cov_index. The analysis both-faces rule counts the fold
# chains in each face's source against this registry — adding a field to
# one face without the other (and without updating this tuple) is the
# silent mirror break that desyncs every recorded cov_digest downstream.
COV_FIELDS = ("node", "src", "kind", "bucket")

# the step's phases, in the order its sections run: each is a named
# scope (`step/<phase>`) over a group of _step_traced's numbered sections
# — select 0-3b, handlers 4, chaos 5-5e and 6b, network 6, invariants 7,
# finish 7b-10 (with the repack of the packed planes)
STEP_PHASES = ("select", "handlers", "chaos", "network", "invariants",
               "finish")

# chain turns per iteration of `_turn_key`'s loop
_KEY_TURNS = 128


def _chain_key(key: jnp.ndarray) -> jnp.ndarray:
    """One turn of the per-lane hash-chain key: the step's section 2. Every
    step turns it, a done lane's too, so the key is the one leaf of a done
    lane's state that still changes."""
    return prng.fold(key, 1)


def _turn_key(key: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """`key` after `n` (a traced i32) more turns of `_chain_key`."""

    def turns(_, k):
        for _ in range(_KEY_TURNS):
            k = _chain_key(k)
        return k

    key = jax.lax.fori_loop(0, n // _KEY_TURNS, turns, key)
    return jax.lax.fori_loop(
        0, n % _KEY_TURNS, lambda _, k: _chain_key(k), key
    )


class _Phases:
    """The step's phase marker: `phase(name)` closes the open phase scope
    and opens the next, all nested under one outer scope; `close()` ends
    both. Scopes change op metadata only — the jaxpr, the compiled
    arithmetic and every trajectory stay exactly as they are."""

    def __init__(self, outer: str) -> None:
        self._outer = contextlib.ExitStack()
        self._outer.enter_context(jax.named_scope(outer))
        self._open = contextlib.ExitStack()

    def __call__(self, name: str) -> None:
        assert name in STEP_PHASES, name
        self._open.close()
        self._open.enter_context(jax.named_scope(name))

    def close(self) -> None:
        self._open.close()
        self._outer.close()


# the sweep segment length: how many steps one device dispatch covers.
# ONE definition — run_batch, the autotuner's default assignment and the
# smoke gates all reference it, so re-tuning the engine default can
# never leave a caller pinned to a stale copy (it is also a Tier-A knob:
# madsim_tpu/tune.py searches it per device).
DEFAULT_DISPATCH_STEPS = 10_000


class Coverage(NamedTuple):
    """Per-lane coverage accumulators (present iff BatchedSim(coverage=True)).

    `bitmap` is the event-class bitmap above. The scalars ride along as
    extra novelty features the bitmap can't express: `hiwater` is the
    message-pool occupancy high-water mark (queue-pressure regimes),
    `transitions` counts delivered/timer events whose handler actually
    CHANGED the node's state (protocol progress vs idle traffic — e.g. a
    raft lane where every AppendEntries is a no-op heartbeat scores low).
    Chaos clause x occurrence coverage lives in SimState.occ_fired, which
    also feeds the per-occurrence chaos report.
    """

    bitmap: Any  # u32 [L, COV_WORDS]
    hiwater: Any  # i32 [L] pool-occupancy high-water (main + straggler)
    transitions: Any  # i32 [L] events that changed node state


class Lineage(NamedTuple):
    """Per-lane causal-lineage plane (present iff `BatchedSim(lineage=True)`;
    docs/causality.md).

    `lam` is a per-node Lamport clock over the lane's global event-id
    scale: a timer fire ticks `lam[n] += 1`, a delivery updates
    `lam[n] = max(lam[n], sender) + 1` where `sender` is the delivered
    message's send-event id (the classic Lamport update with the sent_eid
    stamp as the sender's value — eids are assigned in step order, so the
    eid order is itself consistent with happens-before and the clock law
    `lam(deliver) > lam(send-event owner's clock at send)` holds). `eid`
    is the lane's global event counter: every delivery/timer-fire gets
    the next id, assigned in node order within a step. Neither value
    feeds any draw or any protocol state — lineage is OBSERVE-ONLY, and
    all non-lineage outputs are bit-identical with lineage on/off (pinned
    like coverage was in r7; tests/test_causal.py)."""

    lam: Any  # i32 [L,N] per-node Lamport clock (event-id scale)
    eid: Any  # u32 [L] next event id (== events processed so far)


class MsgPool(NamedTuple):
    """In-flight messages: per-destination validity + per-candidate ring.

    A send event from candidate position c (static source node) carries
    ONE (deliver time, kind, payload) — latency is rolled per candidate —
    so those fields live once in a per-candidate ring slot (c, k), and only
    the validity bit is per destination. The destination slot (n, c, k)
    references ring slot (c, k) BY POSITION. A send takes the first of its
    K ring slots that no destination still references (globally free); if
    all K are pending, the send drops (counted in `overflow`) rather than
    corrupt one in flight. This keeps pool bandwidth ~N x smaller than
    materializing per-destination copies — the pool is rewritten every
    step, so its bytes are a top step cost — and first-free placement
    needs roughly half the depth of strict rotation for burst traffic
    (measured: raft reply bursts need K=4 rotating, K=2 first-free).

    r8 compaction (docs/state_layout.md): the validity plane is stored
    BIT-PACKED along the slot axis (bool costs a full byte in HBM and the
    pool is rewritten every step), and `kind` is u8 at rest for specs
    that DECLARE msg_kind_names (the dense [0, len) enum every in-tree
    spec uses; BatchedSim validates len <= 256). A spec without declared
    kind names might use sparse values >= 256, which a u8 cast would
    silently wrap — those keep i32 kinds (BatchedSim._kind_dtype). The
    step unpacks/widens on entry and repacks on exit; use the `valid`
    property for the bool view outside the step.
    """

    valid_p: Any  # u32 [L,N,ceil(CK/32)] packed validity bits over the ring
    deliver: Any  # i32 [L,CK] (offset us)
    kind: Any  # u8 [L,CK] (i32 when msg_kind_names is undeclared)
    payload: Any  # i32 [L,CK,P]
    # lineage stamp (BatchedSim(lineage=True) only, else None — zero
    # bytes off): the send event's global eid, stored NARROW per the r8
    # narrow-field rules — u16 at rest (pool bytes are a top step cost),
    # widened back to the full u32 eid at delivery by rolling-window
    # reconstruction against the lane's eid counter (exact while fewer
    # than 65536 lane events occur during any message's flight — the
    # same reconstruction idiom as the epoch rebase; the decoder
    # verifies the bound instead of trusting it, causal.graph_from_trace)
    sent_eid: Any = None  # u16 [L,CK] | None

    @property
    def valid(self):
        """bool [L,N,CK] validity view (unpacks valid_p)."""
        return bitpack.unpack_bits(self.valid_p, self.deliver.shape[-1])


class StragPool(NamedTuple):
    """Heavy-tail straggler side pool: one region of K4 slots per candidate
    position ([L, C, K4] flattened to [L, B]); dst is dynamic (stored).
    `valid` stays an unpacked bool plane — the side pool only exists while
    buggify_delay_rate > 0 and is ~N x smaller than the main pool; dst is
    u8 at rest (node ids < 32, engine-enforced) and kind follows the main
    pool's dtype rule (u8 iff msg_kind_names is declared)."""

    valid: Any  # bool [L,B]
    deliver: Any  # i32 [L,B]
    dst: Any  # u8 [L,B]
    kind: Any  # u8 [L,B] (i32 when msg_kind_names is undeclared)
    payload: Any  # i32 [L,B,P]
    sent_eid: Any = None  # u16 [L,B] | None (lineage stamp, see MsgPool)


class NemesisState(NamedTuple):
    """Per-lane nemesis bookkeeping (present iff a schedule-level clause
    is enabled; see SimConfig `nem_*` knobs and madsim_tpu/nemesis.py).

    The occurrence counters (`*_k`) are the whole trick: every nemesis
    draw — event time delta, crash victim, partition side, clog pair —
    is indexed by (lane base key, clause site, k), a pure function of the
    SEED, never of the trajectory clock. That is what makes the fault
    schedule identical on the host twin and replayable as
    `FaultPlan.schedule(seed, ...)` without running the engine at all.
    The crash clause shares `SimState.chaos_at`/`crashed` and the
    partition clause shares `part_at`/`partitioned`/`link_ok` with the
    legacy trajectory-coupled knobs (one machinery, two time sources);
    clog and spike windows carry their own next-toggle offsets here.
    """

    crash_k: Any  # i32 [L] crash/restart cycle counter
    wipe: Any  # bool [L] current down node restarts with wiped state
    part_k: Any  # i32 [L] split/heal cycle counter
    clog_at: Any  # i32 [L] next clog toggle (offset us; INF_US disabled)
    clogged: Any  # bool [L] a directed link is currently clogged
    clog_src: Any  # i32 [L]
    clog_dst: Any  # i32 [L]
    clog_k: Any  # i32 [L]
    spike_at: Any  # i32 [L] next latency-spike toggle
    spiking: Any  # bool [L]
    spike_k: Any  # i32 [L]
    reconfig_at: Any  # i32 [L] next membership toggle (INF_US disabled)
    reconf_node: Any  # i32 [L] node currently OUT of the membership (-1 =
    #           all in; the next reconfig event is a REMOVE, else a JOIN)
    reconfig_k: Any  # i32 [L] remove/join cycle counter
    disk_at: Any  # i32 [L] next disk-fault phase toggle (INF_US disabled)
    disk_phase: Any  # i32 [L] DiskFault 3-phase cursor: 0 = healthy (next
    #           event disk_slow), 1 = degraded window open (next event
    #           disk_crash), 2 = down (next event disk_recover). The
    #           victim and torn bit are NOT carried: both are pure draws
    #           at (key0, site, disk_k), recomputed identically at every
    #           phase of occurrence k — the schedule-purity discipline
    #           applied to the carry itself
    disk_k: Any  # i32 [L] disk-fault occurrence counter (bumps at recover)
    skew_ppm: Any  # i32 [L,N] per-node timer rate skew in ppm (0 = none)
    #           | None. Integer ppm, not an f32 rate: the r8 precision fix
    #           — f32 multiply loses integer microseconds above 2^24 us
    #           (~16.7 virtual seconds); scale_delay_ppm is exact for every
    #           i32 delay. Loop-invariant: drawn once per (seed, node) at
    #           init, hoisted out of the sweep carry by split_state.


class TriageCtl(NamedTuple):
    """Per-lane shrink controls (present iff `BatchedSim(..., triage=True)`).

    The triage subsystem (madsim_tpu/triage.py) evaluates every ddmin
    shrink candidate as a LANE of one batched dispatch: all lanes share
    the full plan's compiled knobs, and these tensors switch clauses,
    individual clause occurrences, message-coin rates and the time horizon
    off PER LANE. Disabling never perturbs anything else's draws — clause
    times/victims are indexed by (lane base key, clause site, occurrence)
    and a disabled occurrence still advances the timing machinery through
    its window — so a shrink candidate IS the original seed's trajectory
    minus exactly the suppressed faults, and one compiled step program
    serves every generation of the shrink.
    """

    off: Any  # i32 [L] clause-disable bitmask over nemesis.TRIAGE_CLAUSES
    occ: Any  # i32 [L, len(OCC_CLAUSES)] occurrence-disable bitmasks (OCC_CLAUSES
    #           rows; bit k suppresses occurrence k; occurrences past the
    #           mask are always enabled — triage.py caps atoms at bit 30,
    #           the int32 sign bit being unusable)
    rate_scale: Any  # f32 [L, 3] scales the loss/dup/reorder coin rates
    #           (nemesis.RATE_CLAUSES rows; the coin is `u < rate * scale`,
    #           so a scaled-down lane's fires are a SUBSET of the full run's)
    h_epoch: Any  # i32 [L] per-lane horizon, epoch part (see REBASE_US)
    h_off: Any  # i32 [L] per-lane horizon, offset part


class RefillQueue(NamedTuple):
    """The device-resident admission queue (continuous batching, r9).

    One row per ADMISSION — a (seed, ctl genome) unit of work. The queue
    is loop-INVARIANT (ConstState side): only the cursor in `RefillLog`
    moves. Admission a < L starts resident in lane a at init; admissions
    a >= L are admitted in retirement order — when a lane violates or
    reaches its per-lane horizon, it re-inits from the next queue row
    inside the jitted step, with no host round-trip until the queue
    drains. The ctl rows exist iff the sim is in triage mode (every
    admission then carries its own clause/occurrence/rate/horizon
    genome — the ddmin and explorer refill face); a plain sweep queues
    seeds only.
    """

    seeds: Any  # u32 [A] admission seeds
    off: Any  # i32 [A] | None (triage: per-admission TriageCtl rows)
    occ: Any  # i32 [A, len(OCC_CLAUSES)] | None
    rate_scale: Any  # f32 [A, len(RATE_CLAUSES)] | None
    h_epoch: Any  # i32 [A] | None
    h_off: Any  # i32 [A] | None


class RefillLog(NamedTuple):
    """Refill-mode carry: per-lane admission bookkeeping, the queue
    cursor, occupancy counters, and the per-ADMISSION result buffers the
    decode reads in admission order (the retirement-time harvest of the
    cold accumulators a re-init would otherwise wipe).

    Everything here is donated carry (cold side): the result buffers are
    written by a masked scatter exactly once per admission — at the step
    its lane retires — and `run_refill`'s decode performs one final
    host-side harvest for lanes still mid-admission when the step budget
    ran out (the chunked path's truncation semantics)."""

    cursor: Any  # i32 [] next queue row to admit (starts at L)
    admitted: Any  # i32 [L] lane's CURRENT admission index
    step_cap: Any  # i32 [] per-ADMISSION step budget == the chunked
    #            path's max_steps: an admission reaching it retires
    #            TRUNCATED (violated as-is, normally False) exactly like
    #            a chunked lane at its loop bound — without this, a
    #            violation past max_steps would be found by refill but
    #            not by the chunked twin (or vice versa under skewed
    #            retirement), breaking per-admission bit-identity
    iters: Any  # i32 [] sweep-loop iterations run (occupancy denominator)
    busy: Any  # i32 [L] per-lane active-step count (occupancy numerator)
    # -- per-admission result rows ([A, ...]; written at retirement) --
    retired: Any  # i32 [A] global step index at retirement (-1 = live)
    violated: Any  # bool [A]
    deadlocked: Any  # bool [A]
    violation_at: Any  # i32 [A] (offset us; INF_US = none)
    violation_epoch: Any  # i32 [A]
    violation_step: Any  # i32 [A] first violating step of the ADMISSION
    #            (admission-relative: its own `steps` counter, exactly
    #             what the chunked path records for the same seed)
    steps: Any  # i32 [A]
    events: Any  # i32 [A]
    overflow: Any  # i32 [A]
    dead_drops: Any  # i32 [A]
    nonmember_drops: Any  # i32 [A]
    unsynced_loss: Any  # i32 [A]
    clock: Any  # i32 [A] final clock offset at retirement
    epoch: Any  # i32 [A]
    fires: Any  # i32 [A, len(FIRE_KINDS)]
    occ_fired: Any  # u32 [A, len(OCC_CLAUSES)] | None
    cov_bitmap: Any  # u32 [A, COV_WORDS] | None (coverage mode)
    cov_hiwater: Any  # i32 [A] | None
    cov_transitions: Any  # i32 [A] | None


class DevLoopPlan(NamedTuple):
    """STATIC shape/vocabulary parameters of the device-resident search
    loop (r19, docs/explore.md): everything the traced generation-boundary
    program bakes in as Python constants. Fixed at `BatchedSim(...,
    devloop=plan)` construction — the jitted step caches on the sim, so a
    plan change needs a new sim (exactly like triage/coverage flags).

    The population split and mutation vocabulary MIRROR the host
    `Explorer` field-for-field (build both through `make_devloop_plan` so
    they cannot drift): `ops` is the weighted op menu `Explorer._mutate`
    draws from, `sched_rows`/`tog_bits`/`rate_rows` the per-op choice
    tables, and the fresh/mutant/swarm counts use the Explorer's exact
    integer-truncation arithmetic."""

    pop: int  # A — candidates per generation (== the admission queue)
    top_k: int  # K — corpus-ring capacity (the host's top_k)
    seen_cap: int  # S — dedup-table capacity (append-only rows)
    n_fresh: int
    n_mut: int
    n_swarm: int
    swarm_group: int
    fresh_stride: int
    full_h: int  # the config horizon (genome horizon 0 decodes to this)
    ops: Tuple[str, ...]  # weighted mutation-op menu, host order
    sched_rows: Tuple[int, ...]  # OCC_ROW of each enabled schedule clause
    tog_bits: Tuple[int, ...]  # TRIAGE_BIT of each togglable clause
    rate_rows: Tuple[int, ...]  # RATE_ROW of each scalable message clause


def make_devloop_plan(
    config: SimConfig, pop: int, top_k: int = 16,
    seen_cap: int = 1 << 17, fresh_frac: float = 0.5,
    mutant_frac: float = 0.3, swarm_group: int = 8,
    fresh_stride: int = 1,
) -> DevLoopPlan:
    """Derive the device-loop plan from a compiled SimConfig with the
    SAME vocabulary source (`nemesis.mutation_vocab`) and split
    arithmetic as `explore.Explorer.__init__` / `_population`, so the
    in-jit mutator and the host mirror can never disagree about which
    clauses are togglable or how a generation splits."""
    cfg = config
    sched, rate, togglable = mutation_vocab(cfg)
    ops: list = []
    if sched:
        ops += ["occ"] * 3
    if togglable:
        ops += ["clause"] * 2
    if rate:
        ops.append("rate")
    ops.append("horizon")
    L = int(pop)
    n_mut = int(L * float(mutant_frac))
    n_fresh = int(L * float(fresh_frac))
    n_swarm = L - n_mut - n_fresh if togglable else 0
    n_fresh = L - n_mut - n_swarm
    if seen_cap & (seen_cap - 1):
        raise ValueError(f"seen_cap must be a power of two, got {seen_cap}")
    return DevLoopPlan(
        pop=L,
        top_k=int(top_k),
        seen_cap=int(seen_cap),
        n_fresh=n_fresh,
        n_mut=n_mut,
        n_swarm=n_swarm,
        swarm_group=max(1, int(swarm_group)),
        fresh_stride=max(1, int(fresh_stride)),
        full_h=int(cfg.horizon_us),
        ops=tuple(ops),
        sched_rows=tuple(OCC_ROW[n] for n in sched),
        tog_bits=tuple(TRIAGE_BIT[n] for n in togglable),
        rate_rows=tuple(RATE_ROW[n] for n in rate),
    )


class DevLoop(NamedTuple):
    """Device-resident search-loop carry (r19): the corpus ring, the
    global coverage union, the genome-dedup table, the MetaRng cursor and
    the per-generation result archives — everything the host explorer
    used to rebuild between generations, now donated cold carry so a
    whole WINDOW of generations runs as one dispatch chain with zero
    host sync (decode happens once, in `devloop_results`).

    Capacities are array shapes (A = plan.pop admissions, K = plan.top_k
    ring rows, S = plan.seen_cap dedup rows, G = the window's generation
    count), so they are jit cache keys like every other shape.

    DETERMINISM: every value here is a pure function of (uploaded search
    state, meta-seed counter chain, admission results) — the boundary
    folds admissions in ADMISSION ORDER (the same order the host
    `_fold_part` replays), the ring is the host corpus's stable
    top-K-by-novelty exactly (insertion keeps ties in admission order),
    and dedup compares the SAME 64-bit genome hash both faces compute
    (nemesis.GENOME_H1/H2), so a hash collision — the only divergence a
    hash-based set can introduce — hits both loops identically."""

    # meta-rng cursor (the host MetaRng's (seed-key, counter) pair)
    meta_key: Any  # u32 [] key_from_seed(meta_seed)
    counter: Any  # i32 [] next MetaRng draw index
    next_fresh: Any  # u32 [] next fresh-seed value (advances by stride)
    gens_done: Any  # i32 [] generations fully executed + archived
    target_gens: Any  # i32 [] generations this window must run (== G)
    accepts: Any  # i32 [] corpus-ring admissions this window (telemetry)
    # corpus ring: top-K genomes by novelty, sorted desc, stable ties
    ring_n: Any  # i32 [] valid rows
    ring_bits: Any  # i32 [K] new_bits at admission (the sort key)
    ring_seed: Any  # u32 [K]
    ring_off: Any  # i32 [K]
    ring_occ: Any  # i32 [K, len(OCC_CLAUSES)]
    ring_rate: Any  # f32 [K, len(RATE_CLAUSES)]
    ring_h: Any  # i32 [K] raw genome horizon (0 = full)
    # global coverage union (the novelty reference)
    union: Any  # u32 [COV_WORDS]
    # genome-dedup table: append-only (h1, h2) rows; membership is an
    # exact masked compare over the valid prefix, so row ORDER never
    # affects a dedup decision — only set contents do
    seen_h1: Any  # u32 [S]
    seen_h2: Any  # u32 [S]
    seen_n: Any  # i32 []
    # current generation's provenance (the queue holds the ctl ENCODING,
    # which is lossy: genome horizon 0 encodes as the full horizon)
    gen_h_raw: Any  # i32 [A] raw genome horizons of the live generation
    gen_origin: Any  # i32 [A] 0 = fresh, 1 = mutant, 2 = swarm
    # per-generation archives, written at each generation boundary —
    # the ONE host sync per window decodes these
    arch_seed: Any  # u32 [G, A]
    arch_off: Any  # i32 [G, A]
    arch_occ: Any  # i32 [G, A, len(OCC_CLAUSES)]
    arch_rate: Any  # f32 [G, A, len(RATE_CLAUSES)]
    arch_h: Any  # i32 [G, A] raw genome horizons
    arch_origin: Any  # i32 [G, A]
    arch_violated: Any  # bool [G, A]
    arch_bitmap: Any  # u32 [G, A, COV_WORDS]
    arch_hiwater: Any  # i32 [G, A]
    arch_transitions: Any  # i32 [G, A]


# origin enum shared by DevLoop.gen_origin / arch_origin and the host
# decode (explore.Candidate.origin strings, in enum order)
DEVLOOP_ORIGINS = ("fresh", "mutant", "swarm")


def default_ctl(L: int, horizon_us: int) -> TriageCtl:
    """The no-op ctl: every clause and occurrence on, full horizon."""
    eh, oh = divmod(int(horizon_us), REBASE_US)
    return TriageCtl(
        off=jnp.zeros((L,), jnp.int32),
        occ=jnp.zeros((L, len(OCC_CLAUSES)), jnp.int32),
        rate_scale=jnp.ones((L, len(RATE_CLAUSES)), jnp.float32),
        h_epoch=jnp.full((L,), eh, jnp.int32),
        h_off=jnp.full((L,), oh, jnp.int32),
    )


def _clause_on(ctl: TriageCtl, name: str) -> jnp.ndarray:
    """bool [L]: clause `name` enabled per lane."""
    return (ctl.off & TRIAGE_BIT[name]) == 0


def _occ_on(ctl: TriageCtl, name: str, k) -> jnp.ndarray:
    """bool [L]: occurrence `k` of schedule clause `name` enabled per lane
    (k: i32 [L], the lane's current occurrence counter)."""
    bit = (
        ctl.occ[:, OCC_ROW[name]].astype(jnp.uint32)
        >> jnp.clip(k, 0, 31).astype(jnp.uint32)
    ) & jnp.uint32(1)
    return _clause_on(ctl, name) & ((bit == 0) | (k >= 32))


class TraceRecord(NamedTuple):
    """One step's observable events, for per-lane violation traces.

    The reference's DX promise is an exact, inspectable repro from the
    printed seed (runtime/mod.rs:194-199). On device the equivalent is this
    record stream: re-running one violating seed through the SAME jitted
    step function yields every delivery, timer fire, crash/restart and
    partition event with virtual timestamps — debuggable without the host
    twin. All leaves are [L, ...]; tracing runs use L=1. Times are offsets;
    absolute = epoch * REBASE_US + offset (trace.extract_trace combines).
    """

    clock: Any  # i32 [L]
    epoch: Any  # i32 [L]
    t_evt: Any  # i32 [L,N] virtual time of node n's event this step
    msg_fired: Any  # bool [L,N] message delivered to node n this step
    msg_src: Any  # i32 [L,N]
    msg_kind: Any  # i32 [L,N]
    msg_payload: Any  # i32 [L,N,P]
    timer_fired: Any  # bool [L,N]
    crash: Any  # i32 [L] node crashed this step, -1 = none
    restart: Any  # i32 [L] node restarted this step, -1 = none
    split: Any  # bool [L] partition split happened this step
    heal: Any  # bool [L] partition healed this step
    side_mask: Any  # i32 [L] bitmask of nodes on side A after a split, else 0
    violation: Any  # bool [L] invariant first violated this step
    deadlock: Any  # bool [L]
    clog_src: Any  # i32 [L] link clogged src this step, -1 = none
    clog_dst: Any  # i32 [L]
    unclog: Any  # bool [L] link unclogged this step
    spike_on: Any  # bool [L] latency spike opened this step
    spike_off: Any  # bool [L]
    remove: Any  # i32 [L] node removed from membership this step, -1 = none
    join: Any  # i32 [L] node (re)joined this step (fresh-init), -1 = none
    disk_slow: Any  # i32 [L] disk degraded-window opened on node, -1 = none
    disk_crash: Any  # i32 [L] disk died on node (unsynced loss), -1 = none
    disk_recover: Any  # i32 [L] node recovered from watermark, -1 = none
    disk_torn: Any  # bool [L] the occurrence's torn-write coin (marked on
    #           the crash and recover halves; the torn tail itself is a
    #           host-face FsSim effect and a device-face on_recover input)
    # -- lineage plane (BatchedSim(lineage=True) only, else None): the
    # device edge ring. Each step's events carry their global event id
    # and, for deliveries, the RECONSTRUCTED full send eid — so a traced
    # replay's record stream IS the (send_eid -> deliver_eid) edge list,
    # with zero extra carry (untraced callers discard the record and XLA
    # DCEs its construction like the rest of the trace).
    lam: Any = None  # i32 [L,N] post-step Lamport clocks
    evt_eid: Any = None  # u32 [L,N] this step's event id (EID_NONE = none)
    sent_eid: Any = None  # u32 [L,N] delivered msg's send eid (EID_NONE)


class SimState(NamedTuple):
    """The full per-lane state pytree (the sweep carry).

    r8 layout discipline (docs/state_layout.md, tests/test_state_layout.py):
    the fields split three ways for the sweep loop —

      HOT    mutated by (nearly) every step: clocks, keys, pools, timers,
             chaos cursors, node state. Carried through the while_loop.
      COLD   write-rarely / accumulate-only metadata (violation records,
             counters, fire masks, coverage): still carried (XLA aliases
             the carry in place) but grouped in ColdState so the layout
             lint can hold its growth separately.
      CONST  loop-invariant (key0, ctl, skew_ppm): split OUT of the
             while_loop carry entirely by split_state — the step reads
             them as invariant operands and never rewrites them, so they
             stop being re-materialized by every fused step.

    Bool planes (alive, link_ok, pool validity) are stored bit-packed
    (bitpack.py); the `alive` / `link_ok` properties give the bool view.
    """

    clock: Any  # i32 [L] (offset us; see epoch)
    epoch: Any  # i32 [L] rebase count (abs = epoch * REBASE_US + clock)
    key: Any  # u32 [L] (hash-chain, prng.py)
    key0: Any  # u32 [L] the lane's BASE key (constant; nemesis draws
    #           index off it so fault schedules are trajectory-free)
    done: Any  # bool [L]
    violated: Any  # bool [L]
    violation_at: Any  # i32 [L] (offset; INF_US = none)
    violation_epoch: Any  # i32 [L]
    violation_step: Any  # i32 [L] first violating step index (-1 = none;
    #            with run(max_steps=step+1) this is the run-to-step
    #            truncation handle the triage shrinker bisects to)
    deadlocked: Any  # bool [L]
    steps: Any  # i32 [L]
    events: Any  # i32 [L]
    overflow: Any  # i32 [L] (messages dropped: pool full)
    dead_drops: Any  # i32 [L] (messages dropped: destination node down —
    #            distinct from `overflow` so graceful-degradation
    #            assertions can tell pool pressure from crash fallout)
    nonmember_drops: Any  # i32 [L] (messages dropped: destination not a
    #            cluster MEMBER — removed by the reconfig clause. Checked
    #            before liveness, so the classes are disjoint: a crashed
    #            member counts in dead_drops, a removed node here)
    unsynced_loss: Any  # i32 [L] disk crashes that lost unsynced durable
    #            state: the victim's durable fields differed from its
    #            watermark at the crash instant (every disk crash counts
    #            when the spec declares no durable_fields — the whole
    #            state is then unsynced by definition). Always present,
    #            like nonmember_drops: a zero column when the DiskFault
    #            clause is off costs nothing and spares every consumer
    #            an Optional branch
    fires: Any  # i32 [L, len(FIRE_KINDS)] per-fault-kind chaos fire counts
    occ_fired: Any  # u32 [L, len(OCC_CLAUSES)] | None — bit k set when
    #            occurrence k of the schedule clause APPLIED in this lane
    #            (occurrences >= 31 fold into bit 31; triage caps its atoms
    #            at bit 30 so the fold never aliases a shrinkable atom).
    #            None unless a nemesis schedule clause is enabled. This is
    #            the clause x occurrence half of the coverage signal AND the
    #            raw data of the per-occurrence chaos report.
    alive_p: Any  # u32 [L,1] packed node-liveness bits (N <= 32)
    crashed: Any  # i32 [L] (node id currently down, -1 = none)
    chaos_at: Any  # i32 [L] (next crash/restart event)
    member_p: Any  # u32 [L,1] packed cluster-MEMBERSHIP bits (the reconfig
    #           clause's plane; all-ones when the clause is off). Liveness
    #           and membership are independent axes: a removed node keeps
    #           its alive bit state, but non-members receive nothing
    #           (sends to them count in nonmember_drops) and a join
    #           rebuilds the node from the real _init (fresh replica).
    member_epoch: Any  # i32 [L] membership-epoch counter: increments on
    #           every remove AND every join (the reconfig clause's
    #           configuration-change ordinal, exposed to traces/summaries)
    link_ok_p: Any  # u32 [L,N,1] packed directed-link-up bits, row = src
    partitioned: Any  # bool [L] (a partition is currently active)
    part_at: Any  # i32 [L] (next partition split/heal event)
    timer: Any  # i32 [L,N]
    node: Any  # protocol pytree, leaves [L,N,...] (fields named in
    #           spec.narrow_fields are stored at their narrow dtypes and
    #           widened to i32 before every handler call)
    dur: Any  # durable WATERMARK | None — the DiskFault clause's
    #           durability plane (None unless nem_disk is enabled AND the
    #           spec declares durable_fields). A namedtuple over
    #           spec.durable_fields with leaves [L,N,...] at the same
    #           at-rest (narrowed) dtypes as the node carry: the last
    #           value of each durable field the node made it to disk.
    #           Initialized from spec.init (boot is fsynced), re-snapshot
    #           whenever spec.sync_field increases (the spec's declared
    #           fsync points), reset to the node's fresh state on
    #           wipe / join / disk-recover. A disk crash recovery
    #           rebuilds the victim FROM this plane, not from live state
    msgs: MsgPool
    strag: Any  # StragPool | None (None unless buggify_delay_rate > 0)
    nem: Any  # NemesisState | None (None unless a nemesis clause is on)
    ctl: Any  # TriageCtl | None (None unless BatchedSim(triage=True))
    cov: Any  # Coverage | None (None unless BatchedSim(coverage=True))
    lin: Any  # Lineage | None (None unless BatchedSim(lineage=True)):
    #           per-node Lamport clocks + the global per-lane event
    #           counter — hot carry, rewritten every step
    queue: Any  # RefillQueue | None — loop-invariant admission queue
    #           (None unless the state was built by init_refill; see
    #           docs/continuous_batching.md)
    refill: Any  # RefillLog | None — refill carry: queue cursor, per-lane
    #           admission ids, occupancy counters, per-admission results
    loop: Any = None  # DevLoop | None — device-resident search carry
    #           (None unless the state was built by init_devloop; r19,
    #           docs/explore.md). Trailing with a default so every
    #           existing positional/keyword construction site stays
    #           valid. Requires refill mode: the generation boundary
    #           rides _refill_apply's retire path.

    @property
    def alive(self):
        """bool [L,N] node-liveness view (unpacks alive_p)."""
        return bitpack.unpack_bits(self.alive_p, self.timer.shape[1])

    @property
    def link_ok(self):
        """bool [L,N,N] directed-link view (unpacks link_ok_p)."""
        return bitpack.unpack_bits(self.link_ok_p, self.timer.shape[1])

    @property
    def member(self):
        """bool [L,N] cluster-membership view (unpacks member_p)."""
        return bitpack.unpack_bits(self.member_p, self.timer.shape[1])


class ColdState(NamedTuple):
    """The accumulate-only half of the sweep carry (see SimState). Grouped
    so the state-layout lint budgets hot and cold bytes separately and the
    split is visible in the compiled program's carry structure."""

    violation_at: Any
    violation_epoch: Any
    violation_step: Any
    deadlocked: Any
    steps: Any
    events: Any
    overflow: Any
    dead_drops: Any
    nonmember_drops: Any
    unsynced_loss: Any
    fires: Any
    occ_fired: Any
    cov: Any
    refill: Any  # RefillLog | None (refill mode only): the result
    #            buffers accumulate, the cursor advances rarely — cold
    loop: Any  # DevLoop | None (device-loop mode only): corpus ring,
    #            union bitmap, seen table, generation archives — touched
    #            once per generation boundary, cold by construction


COLD_FIELDS = ColdState._fields


class ConstState(NamedTuple):
    """Loop-invariant lane state, split OUT of the sweep carry: the step
    reads these but never writes them, so keeping them in the while_loop
    carry made every fused step re-emit them as outputs (copied bytes per
    step, and per-segment donation rotation). key0 feeds every
    schedule-pure nemesis draw; ctl is the triage shrinker's per-lane
    switchboard; skew_ppm the per-(seed, node) clock-skew assignment.

    REFILL mode inverts the first three: a refilled lane adopts a NEW
    seed's key0/ctl/skew mid-sweep, so those become carry and the only
    loop invariant left is the admission queue itself (the queue rows
    never change; only RefillLog's cursor moves)."""

    key0: Any
    ctl: Any
    skew_ppm: Any
    queue: Any  # RefillQueue | None (refill mode only)


def split_state(state: SimState):
    """SimState -> (hot, cold, const) for the sweep loop. Pure pytree
    restructuring: no data moves, the leaves are the same buffers.

    Two partitions, selected by the state's structure:
      * plain sweeps: const = (key0, ctl, skew_ppm) — the r8 split;
      * refill sweeps (state.refill is not None): key0/ctl/skew_ppm
        STAY IN THE CARRY (a refilled lane rewrites them from its new
        admission), and const = the admission queue alone;
      * device-loop sweeps (state.loop is not None): NOTHING is loop-
        invariant — the generation boundary rewrites even the admission
        queue from the mutated corpus ring, so the queue rides the
        carry and const is empty."""
    nem = state.nem
    cold = ColdState(*(getattr(state, f) for f in COLD_FIELDS))
    if state.loop is not None:
        hot = state._replace(**{f: None for f in COLD_FIELDS})
        const = ConstState(key0=None, ctl=None, skew_ppm=None, queue=None)
        return hot, cold, const
    if state.refill is not None:
        hot = state._replace(
            queue=None, **{f: None for f in COLD_FIELDS},
        )
        const = ConstState(
            key0=None, ctl=None, skew_ppm=None, queue=state.queue,
        )
        return hot, cold, const
    hot = state._replace(
        key0=None, ctl=None, queue=None,
        nem=None if nem is None else nem._replace(skew_ppm=None),
        **{f: None for f in COLD_FIELDS},
    )
    const = ConstState(
        key0=state.key0, ctl=state.ctl,
        skew_ppm=None if nem is None else nem.skew_ppm,
        queue=None,
    )
    return hot, cold, const


def merge_state(hot: SimState, cold: ColdState, const: ConstState) -> SimState:
    """(hot, cold, const) -> flat SimState (inverse of split_state)."""
    if cold.loop is not None:  # device-loop partition: const is empty,
        # the queue never left the hot carry — just graft cold back on
        return hot._replace(**dict(zip(COLD_FIELDS, cold)))
    if const.queue is not None:  # refill partition: key0/ctl/skew in hot
        return hot._replace(
            queue=const.queue, **dict(zip(COLD_FIELDS, cold)),
        )
    nem = hot.nem
    if nem is not None:
        nem = nem._replace(skew_ppm=const.skew_ppm)
    return hot._replace(
        key0=const.key0, ctl=const.ctl, nem=nem,
        **dict(zip(COLD_FIELDS, cold)),
    )


def named_leaves(tree: Any, prefix: str = "") -> list:
    """(dotted-path, leaf) pairs in jax flatten order, with NamedTuple
    FIELD NAMES instead of positional keys (tree_flatten_with_path only
    yields indices for namedtuples). None subtrees are dropped, matching
    tree_leaves. The analysis verifier keys its per-leaf rules (taint
    roots, donation coverage, narrow dtypes) on these names."""
    out: list = []

    def rec(name, obj):
        if obj is None:
            return
        if hasattr(obj, "_fields"):  # NamedTuple node
            for f in obj._fields:
                rec(f"{name}.{f}" if name else f, getattr(obj, f))
        elif isinstance(obj, (tuple, list)):
            for i, v in enumerate(obj):
                rec(f"{name}[{i}]" if name else f"[{i}]", v)
        elif isinstance(obj, dict):
            for k in sorted(obj):
                rec(f"{name}[{k!r}]" if name else f"[{k!r}]", obj[k])
        else:
            out.append((name, obj))

    rec(prefix, tree)
    return out


def carry_partition(state: SimState) -> dict:
    """{'hot'|'cold'|'const' -> [leaf path]} for the sweep-loop split.

    The donated-leaf introspection hook for the static verifier
    (madsim_tpu/analysis): hot + cold are the while_loop carry (donated
    across dispatch boundaries); const rides as a loop-invariant operand
    and must never be donated, rotated, or re-emitted per step."""
    hot, cold, const = split_state(state)
    return {
        "hot": [n for n, _ in named_leaves(hot)],
        "cold": [n for n, _ in named_leaves(cold)],
        "const": [n for n, _ in named_leaves(const)],
    }


def interval_hints(
    sim: "BatchedSim", refill: bool = False, devloop: bool = False,
) -> dict:
    """{carry leaf name -> (lo, hi, may_inf)} seed intervals for the
    ENGINE-OWNED leaves, keyed by the `named_leaves` hot/cold/const paths.

    `refill=True` keys the hints for the refill carry partition (key0 /
    ctl / skew_ppm live under `hot.`, the queue under `const.queue.`)
    and adds the RefillLog leaves — notably the queue cursor and the
    per-admission `retired` step rows the range certifier must bound.

    `devloop=True` (implies refill) keys the device-loop partition: the
    queue ALSO rides the carry (`hot.queue.*` — the generation boundary
    rewrites it from the mutated ring), and the `cold.loop.*` DevLoop
    leaves gain rows — notably the ring/seen cursors every dynamic
    ring-scatter index is clipped against.

    The introspection hook behind the Layer-3 range certifier
    (analysis/ranges.py): these are the engine's own documented value
    invariants — live time OFFSETS stay below INF_GUARD (the rebase
    guard `rb` relies on exactly this: values >= INF_GUARD are sentinels
    and are never rebased), node ids index [0, N), occurrence counters
    and diagnostic counters stay far from i32 overflow — stated where
    the invariants LIVE so the analyzer cannot drift from the engine.
    `may_inf` marks leaves that may additionally hold the INF_US
    sentinel exactly (disarmed timers, empty pool slots, disabled
    chaos). Leaves NOT named here are protocol-owned (node state,
    payloads) and are seeded by the analyzer from the spec's own
    declarations (narrow_fields / rate_floors / time_fields)."""
    cfg = sim.config
    N = sim.spec.n_nodes
    off_hi = int(INF_GUARD) - 1  # live-offset invariant (see rb())
    ctr_hi = 1 << 30  # diagnostics counters: far below i32 wrap
    ep_hi = 1 << 22  # epochs: ~35k virtual years of rebase headroom
    u32 = (0, (1 << 32) - 1, False)
    toff = (-1, off_hi, True)  # time offset; -1 = "keep/disarm" in flight
    hints = {
        "hot.clock": (0, off_hi, True),
        "hot.epoch": (0, ep_hi, False),
        "hot.key": u32,
        "hot.done": (0, 1, False),
        "hot.violated": (0, 1, False),
        "hot.alive_p": u32,
        "hot.crashed": (-1, N - 1, False),
        "hot.chaos_at": toff,
        "hot.link_ok_p": u32,
        "hot.partitioned": (0, 1, False),
        "hot.part_at": toff,
        "hot.timer": toff,
        "hot.msgs.valid_p": u32,
        "hot.msgs.deliver": toff,
        "hot.strag.valid": (0, 1, False),
        "hot.strag.deliver": toff,
        "hot.strag.dst": (0, N - 1, False),
        "hot.nem.crash_k": (0, ctr_hi, False),
        "hot.nem.wipe": (0, 1, False),
        "hot.nem.part_k": (0, ctr_hi, False),
        "hot.nem.clog_at": toff,
        "hot.nem.clogged": (0, 1, False),
        "hot.nem.clog_src": (0, N - 1, False),
        "hot.nem.clog_dst": (0, N - 1, False),
        "hot.nem.clog_k": (0, ctr_hi, False),
        "hot.nem.spike_at": toff,
        "hot.nem.spiking": (0, 1, False),
        "hot.nem.spike_k": (0, ctr_hi, False),
        "hot.nem.reconfig_at": toff,
        "hot.nem.reconf_node": (-1, N - 1, False),
        "hot.nem.reconfig_k": (0, ctr_hi, False),
        "hot.nem.disk_at": toff,
        "hot.nem.disk_phase": (0, 2, False),
        "hot.nem.disk_k": (0, ctr_hi, False),
        "hot.member_p": u32,
        "hot.member_epoch": (0, ctr_hi, False),
        "cold.violation_at": toff,
        "cold.violation_epoch": (0, ep_hi, False),
        "cold.violation_step": (-1, ctr_hi, False),
        "cold.deadlocked": (0, 1, False),
        "cold.steps": (0, ctr_hi, False),
        "cold.events": (0, ctr_hi, False),
        "cold.overflow": (0, ctr_hi, False),
        "cold.dead_drops": (0, ctr_hi, False),
        "cold.nonmember_drops": (0, ctr_hi, False),
        "cold.unsynced_loss": (0, ctr_hi, False),
        "cold.fires": (0, ctr_hi, False),
        "cold.occ_fired": u32,
        "cold.cov.bitmap": u32,
        "cold.cov.hiwater": (0, ctr_hi, False),
        "cold.cov.transitions": (0, ctr_hi, False),
        # causal-lineage plane (lineage=True): the eid counter gains one
        # per processed event, so it shares the diagnostics-counter
        # invariant (events << 2^31 per admission); Lamport clocks live
        # on the same event-id scale (max(local, send eid)+1 adds at most
        # one per event); the pool stamp is the send eid's low 16 bits
        "hot.lin.lam": (0, ctr_hi, False),
        "hot.lin.eid": (0, ctr_hi, False),
        "hot.msgs.sent_eid": (0, (1 << 16) - 1, False),
        "hot.strag.sent_eid": (0, (1 << 16) - 1, False),
        "const.key0": u32,
        "const.ctl.off": (0, (1 << 31) - 1, False),
        "const.ctl.occ": (0, (1 << 31) - 1, False),
        "const.ctl.rate_scale": (0, 1, False),
        "const.ctl.h_epoch": (0, ep_hi, False),
        "const.ctl.h_off": (0, REBASE_US - 1, False),
        "const.skew_ppm": (
            -cfg.nem_skew_max_ppm, cfg.nem_skew_max_ppm, False
        ),
    }
    n_kinds = (
        len(sim.spec.msg_kind_names)
        if sim.spec.msg_kind_names is not None else 256
    )
    hints["hot.msgs.kind"] = (0, n_kinds - 1, False)
    hints["hot.strag.kind"] = (0, n_kinds - 1, False)
    # absolute-time node fields (spec.time_fields) share the live-offset
    # invariant: they are rebased with the lane's epoch like every other
    # time tensor
    for f in sim.spec.time_fields:
        hints[f"hot.node.{f}"] = toff
    # the durability watermark mirrors node fields value-for-value: every
    # dur leaf is a SNAPSHOT of its node leaf (advance/reset both copy),
    # so it inherits the node field's interval — the certifier seeds
    # hot.dur.* from the same spec declarations as hot.node.* and these
    # engine-owned hints only exist for fields the engine itself bounds
    if refill or devloop:
        # the refill carry partition: key0/ctl/skew ride in hot (a
        # refilled lane rewrites them), only the queue is const
        ren = {
            "const.key0": "hot.key0",
            "const.skew_ppm": "hot.nem.skew_ppm",
        }
        hints = {
            ren.get(k, k.replace("const.ctl.", "hot.ctl.")): v
            for k, v in hints.items()
        }
        ctr = (0, ctr_hi, False)
        hints.update({
            # the queue cursor / admission ids are bounded by the queue
            # length at runtime; ctr_hi is the sound static envelope the
            # certifier needs (the gathers are clipped, the scatters
            # drop-moded — both provable/guarded from these seeds)
            "cold.refill.cursor": ctr,
            "cold.refill.admitted": ctr,
            "cold.refill.step_cap": ctr,
            "cold.refill.iters": ctr,
            "cold.refill.busy": ctr,
            "cold.refill.retired": (-1, ctr_hi, False),
            "cold.refill.violated": (0, 1, False),
            "cold.refill.deadlocked": (0, 1, False),
            "cold.refill.violation_at": toff,
            "cold.refill.violation_epoch": (0, ep_hi, False),
            "cold.refill.violation_step": (-1, ctr_hi, False),
            "cold.refill.steps": ctr,
            "cold.refill.events": ctr,
            "cold.refill.overflow": ctr,
            "cold.refill.dead_drops": ctr,
            "cold.refill.nonmember_drops": ctr,
            "cold.refill.unsynced_loss": ctr,
            "cold.refill.clock": (0, off_hi, True),
            "cold.refill.epoch": (0, ep_hi, False),
            "cold.refill.fires": ctr,
            "cold.refill.occ_fired": u32,
            "cold.refill.cov_bitmap": u32,
            "cold.refill.cov_hiwater": ctr,
            "cold.refill.cov_transitions": ctr,
            "const.queue.seeds": u32,
            "const.queue.off": (0, (1 << 31) - 1, False),
            "const.queue.occ": (0, (1 << 31) - 1, False),
            "const.queue.rate_scale": (0, 1, False),
            "const.queue.h_epoch": (0, ep_hi, False),
            "const.queue.h_off": (0, REBASE_US - 1, False),
        })
    if devloop:
        # device-loop partition: const is EMPTY — the boundary rewrites
        # the queue from the mutated ring, so its rows ride the carry
        hints = {
            k.replace("const.queue.", "hot.queue."): v
            for k, v in hints.items()
        }
        plan = sim.devloop
        K, S = plan.top_k, plan.seen_cap
        full_h = plan.full_h
        ctr = (0, ctr_hi, False)
        hints.update({
            "cold.loop.meta_key": u32,
            "cold.loop.counter": ctr,
            "cold.loop.next_fresh": u32,
            "cold.loop.gens_done": ctr,
            "cold.loop.target_gens": ctr,
            "cold.loop.accepts": ctr,
            # ring/seen cursors: the invariants every dynamic ring index
            # is clipped against (ring_n <= K, seen_n <= S by the host
            # pre-dispatch headroom check in Explorer._run_device_window)
            "cold.loop.ring_n": (0, K, False),
            "cold.loop.ring_bits": (0, COV_BITS, False),
            "cold.loop.ring_seed": u32,
            "cold.loop.ring_off": (0, (1 << 31) - 1, False),
            "cold.loop.ring_occ": (0, (1 << 31) - 1, False),
            "cold.loop.ring_rate": (0, 1, False),
            "cold.loop.ring_h": (0, full_h, False),
            "cold.loop.union": u32,
            "cold.loop.seen_h1": u32,
            "cold.loop.seen_h2": u32,
            "cold.loop.seen_n": (0, S, False),
            "cold.loop.gen_h_raw": (0, full_h, False),
            "cold.loop.gen_origin": (0, 2, False),
            "cold.loop.arch_seed": u32,
            "cold.loop.arch_off": (0, (1 << 31) - 1, False),
            "cold.loop.arch_occ": (0, (1 << 31) - 1, False),
            "cold.loop.arch_rate": (0, 1, False),
            "cold.loop.arch_h": (0, full_h, False),
            "cold.loop.arch_origin": (0, 2, False),
            "cold.loop.arch_violated": (0, 1, False),
            "cold.loop.arch_bitmap": u32,
            "cold.loop.arch_hiwater": ctr,
            "cold.loop.arch_transitions": ctr,
        })
    return hints


def scale_delay_ppm(d: jnp.ndarray, ppm) -> jnp.ndarray:
    """Stretch a non-negative i32 microsecond delay by (1 + ppm * 1e-6),
    EXACTLY, in pure int32 arithmetic: d + trunc(d * |ppm| / 1e6) * sign.

    Replaces the r1 `(d.astype(f32) * rate).astype(i32)` path, which
    loses integer precision once d exceeds 2^24 us (~16.7 virtual
    seconds — well inside a 30 s horizon). The 64-bit product d * ppm is
    decomposed into i32-safe partial products: with d = q * 1e6 + r,
    r = r1 * 1e3 + r0 and |ppm| = p1 * 1e3 + p0, every term below stays
    under 2^31 for d < 2^31 and |ppm| < 1e6 (the SimConfig validation
    bound). The host runtime mirrors the same truncation in
    core/vtime.skew_delay_ns (exact there via Python ints).
    """
    ppm = jnp.asarray(ppm, jnp.int32)
    mag = jnp.abs(ppm)
    q, r = d // 1_000_000, d % 1_000_000
    r1, r0 = r // 1000, r % 1000
    p1, p0 = mag // 1000, mag % 1000
    frac = ((r1 * p0 + r0 * p1) * 1000 + r0 * p0) // 1_000_000
    adj = q * mag + r1 * p1 + frac
    return jnp.where(ppm >= 0, d + adj, d - adj)


def _first_free(free: jnp.ndarray, K: int) -> jnp.ndarray:
    """First-free-slot mask along the last axis (length K, static).

    Unrolled prefix: K is tiny, and cumsum is a scan op that breaks XLA's
    elementwise fusion.
    """
    if K == 1:
        return free
    prev = jnp.zeros_like(free[..., 0])
    cols = []
    for k in range(K):
        cols.append(free[..., k] & ~prev)
        prev = prev | free[..., k]
    return jnp.stack(cols, axis=-1)


def _tree_where(mask: jnp.ndarray, a: Any, b: Any) -> Any:
    """Select pytree leaves by a [L,N]-shaped mask, broadcasting trailing dims."""

    def sel(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
        return jnp.where(m, x, y)

    return jax.tree_util.tree_map(sel, a, b)


class BatchedSim:
    """Vectorized multi-lane simulator for one ProtocolSpec."""

    def __init__(
        self, spec: ProtocolSpec, config: Optional[SimConfig] = None,
        triage: bool = False, coverage: bool = False,
        lineage: bool = False, devloop: Optional[DevLoopPlan] = None,
    ) -> None:
        """`triage=True` threads a per-lane `TriageCtl` through the state:
        the same compiled step program then evaluates shrink candidates
        (clauses / occurrences / rates / horizons switched off per lane)
        as lanes of one dispatch — see madsim_tpu/triage.py. `coverage=True`
        additionally accumulates the per-lane Coverage bitmap + scalars the
        explorer's novelty search feeds on (madsim_tpu/explore.py).
        `lineage=True` carries the causal-lineage plane — per-node Lamport
        clocks, the global per-lane event counter, and a u16 `sent_eid`
        stamp per pool slot — so a traced replay records exact
        happens-before (send_eid -> deliver_eid) edges for
        madsim_tpu/causal.py (docs/causality.md). All off by default:
        normal sweeps pay nothing for any of them, and every non-lineage
        output is bit-identical with lineage on/off."""
        self.spec = spec
        self.config = config or SimConfig()
        self.triage = bool(triage)
        self.coverage = bool(coverage)
        self.lineage = bool(lineage)
        # `devloop` arms the device-resident search loop (r19,
        # docs/explore.md): a DevLoopPlan whose STATIC vocabulary/split
        # parameters the generation-boundary program bakes in. The loop
        # mutates TriageCtl genomes and ranks coverage novelty in-jit,
        # so both planes must be threaded.
        if devloop is not None and not (triage and coverage):
            raise ValueError(
                "devloop needs BatchedSim(..., triage=True, coverage=True) "
                "— the device loop mutates ctl genomes and ranks coverage "
                "novelty in-jit"
            )
        self.devloop = devloop
        cfg = self.config
        N = spec.n_nodes
        # fail loudly at construction, not as shape errors deep inside jit
        if N < 2:
            raise ValueError(f"spec.n_nodes must be >= 2, got {N}")
        if N > 32:
            # the packed alive/link_ok planes keep one u32 word per row
            # (and spec.majority's bitmask already caps quorum specs at 31)
            raise ValueError(
                f"spec.n_nodes must be <= 32 (packed bool planes), got {N}"
            )
        if spec.msg_kind_names is not None and len(spec.msg_kind_names) > 256:
            raise ValueError(
                "message kinds must fit u8 (pool `kind` is stored narrow): "
                f"got {len(spec.msg_kind_names)} named kinds"
            )
        # pool `kind` narrows to u8 only for specs that DECLARE their kind
        # vocabulary (msg_kind_names = the dense [0, len) enum every
        # in-tree spec uses, validated <= 256 above); an undeclared spec
        # might use sparse kind values >= 256, which a blind u8 cast would
        # silently wrap — those keep i32 kinds.
        self._kind_dtype = (
            jnp.uint8 if spec.msg_kind_names is not None else jnp.int32
        )
        # node-state leaves the spec declares narrow (docs/state_layout.md):
        # stored at the narrow dtype in the carry, widened back to i32
        # before every handler call — handlers stay wall-to-wall i32.
        self._narrow = dict(spec.narrow_fields or {})
        bad = set(self._narrow) & set(spec.time_fields)
        if bad:
            raise ValueError(
                "time_fields hold absolute epoch-rebased times and must "
                f"stay i32 — remove {sorted(bad)} from narrow_fields"
            )
        # rate_floors entries are ANALYZER metadata (analysis/ranges.py
        # reads them per narrow field; entries for fields outside the
        # live narrow table are inert — `replace(spec, narrow_fields=
        # ...)` is a documented experimentation/escape idiom and must
        # not force re-deriving the floor table). Only the entry TYPES
        # are validated here, so a malformed declaration fails at
        # construction rather than silently un-certifying a field.
        from .spec import HardCap, RateFloor

        for fname, entry in (spec.rate_floors or {}).items():
            if not isinstance(entry, (RateFloor, HardCap)):
                raise ValueError(
                    f"rate_floors[{fname!r}] must be a RateFloor or "
                    f"HardCap, got {type(entry).__name__}"
                )
        if self._narrow and spec.narrow_horizon_us is not None:
            # rate-argument narrow bounds ("one tid per coordinator-timer
            # floor") only hold up to the spec-declared horizon; past it
            # a narrow counter would wrap SILENTLY — refuse instead.
            # The cap derates with the config's clock skew through the
            # SAME helper the range certifier uses (spec.derate_horizon),
            # so refusal and certificate can never disagree.
            cap = derate_horizon(
                spec.narrow_horizon_us,
                cfg.nem_skew_max_ppm if cfg.nem_skew_enabled else 0,
            )
            if cfg.horizon_us > cap:
                raise ValueError(
                    f"horizon_us={cfg.horizon_us} exceeds this spec's "
                    f"narrow-dtype safe horizon ({cap} us"
                    + (" after clock-skew derating"
                       if cfg.nem_skew_enabled else "")
                    + "): strip spec.narrow_fields (dataclasses.replace("
                    "spec, narrow_fields=None)) for long soaks, or "
                    "shorten the horizon"
                )
        if spec.payload_width < 1 or spec.max_out < 1 or spec.max_out_msg < 1:
            raise ValueError(
                "spec payload_width / max_out / max_out_msg must be >= 1 "
                f"(got {spec.payload_width}/{spec.max_out}/{spec.max_out_msg})"
            )
        if cfg.latency_lo_us < 0 or cfg.latency_hi_us < cfg.latency_lo_us:
            raise ValueError(
                f"latency range [{cfg.latency_lo_us}, {cfg.latency_hi_us}] "
                "must satisfy 0 <= lo <= hi"
            )
        if not (0.0 <= cfg.loss_rate < 1.0):
            raise ValueError(f"loss_rate must be in [0, 1), got {cfg.loss_rate}")
        if cfg.horizon_us <= 0:
            raise ValueError(f"horizon_us must be positive, got {cfg.horizon_us}")
        for name in ("msg_depth_msg", "msg_depth_timer"):
            v = getattr(cfg, name)
            if v is not None and v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if cfg.msg_spare_slots < 0:
            raise ValueError(
                f"msg_spare_slots must be >= 0, got {cfg.msg_spare_slots}"
            )
        if spec.on_event is None and cfg.msg_spare_slots > 0:
            raise ValueError(
                "msg_spare_slots only applies to fused (on_event) specs — "
                "the two-handler path places per-candidate rings; use "
                "msg_depth_msg/msg_depth_timer there"
            )
        # nemesis knobs: validate here with the same messages as the host
        # config layer, and reject legacy+nemesis combos for the same
        # machinery (the two time sources would fight over chaos_at)
        for name in (
            "nem_loss_rate", "nem_dup_rate", "nem_reorder_rate",
            "nem_crash_wipe_rate", "nem_disk_torn_rate",
        ):
            v = getattr(cfg, name)
            if not (0.0 <= v < 1.0):
                raise ValueError(f"{name} must be in [0, 1), got {v}")
        if cfg.nem_crash_enabled and cfg.chaos_enabled:
            raise ValueError(
                "nem_crash_* and crash_interval_* cannot both be enabled — "
                "one crash machinery, one time source (use the FaultPlan)"
            )
        if cfg.nem_partition_enabled and cfg.partition_enabled:
            raise ValueError(
                "nem_partition_* and partition_interval_* cannot both be "
                "enabled — one partition machinery, one time source"
            )
        for prefix, pairs in (
            ("nem_crash", (("interval", True), ("down", False))),
            ("nem_partition", (("interval", True), ("heal", False))),
            ("nem_clog", (("interval", True), ("heal", False))),
            ("nem_spike", (("interval", True), ("duration", False))),
            ("nem_reconfig", (("interval", True), ("down", False))),
            ("nem_disk", (("interval", True), ("slow", False), ("down", False))),
        ):
            if getattr(cfg, f"{prefix}_interval_hi_us") <= 0:
                continue  # clause disabled
            for part, _is_iv in pairs:
                lo = getattr(cfg, f"{prefix}_{part}_lo_us")
                hi = getattr(cfg, f"{prefix}_{part}_hi_us")
                if lo < 0 or hi < lo or hi <= 0:
                    raise ValueError(
                        f"{prefix}_{part} range [{lo}, {hi}] must satisfy "
                        "0 <= lo <= hi and hi > 0"
                    )
        if cfg.nem_reorder_rate > 0 and cfg.nem_reorder_window_us <= 0:
            raise ValueError(
                "nem_reorder_rate needs nem_reorder_window_us > 0, got "
                f"{cfg.nem_reorder_window_us}"
            )
        if cfg.nem_spike_enabled and cfg.nem_spike_extra_us <= 0:
            raise ValueError(
                f"nem_spike_extra_us must be > 0, got {cfg.nem_spike_extra_us}"
            )
        if not (0 <= cfg.nem_skew_max_ppm < 1_000_000):
            raise ValueError(
                "nem_skew_max_ppm must be in [0, 1e6) (the timer rate "
                f"1 + ppm*1e-6 must stay positive), got {cfg.nem_skew_max_ppm}"
            )
        # all latency lengtheners must keep deliver offsets far below the
        # sentinel guard (rebase arithmetic headroom)
        if (
            cfg.latency_hi_us + cfg.nem_spike_extra_us
            + cfg.nem_reorder_window_us
        ) >= int(INF_GUARD) // 4:
            raise ValueError(
                "latency_hi + nem_spike_extra + nem_reorder_window must stay "
                f"below {int(INF_GUARD) // 4} us"
            )
        if spec.on_event is not None and cfg.msg_depth_timer is not None and (
            cfg.msg_depth_timer != cfg.msg_depth_msg
        ):
            # covers both "3/2 mixed" and "timer set alone" — either way
            # the knob would be silently ignored on the fused path
            raise ValueError(
                "fused (on_event) specs have ONE candidate class: "
                "msg_depth_timer has no effect and must equal msg_depth_msg "
                f"(got {cfg.msg_depth_timer} vs {cfg.msg_depth_msg}); tune "
                "msg_depth_msg and msg_spare_slots instead"
            )
        import numpy as _np

        # Candidate positions: the fixed send sites of one step. Fused
        # (spec.on_event) specs have ONE event per node per step emitting up
        # to max_out rows => C = N * max_out; two-handler specs have each
        # node's max_out_msg on_message slots then its max_out on_timer
        # slots, in flat() order. Position c's source node is a
        # compile-time constant either way.
        self._fused = spec.on_event is not None
        if self._fused:
            self._C = N * spec.max_out
            self._src_of_c = _np.arange(self._C) // spec.max_out
        else:
            self._C = N * spec.max_out_msg + N * spec.max_out
            self._src_of_c = _np.concatenate(
                [
                    _np.arange(N * spec.max_out_msg) // spec.max_out_msg,
                    _np.arange(N * spec.max_out) // spec.max_out,
                ]
            )
        # nemesis duplication doubles the candidate axis: position 2c is
        # the original send, 2c+1 its (coin-gated) duplicate with an
        # independent latency/loss roll. Interleaving (repeat, not tile)
        # keeps each node's candidate block contiguous, so the fused pack's
        # [L, N, E] reshape and the two-handler segment split both survive
        # unchanged with E and the segment bounds doubled. Pool sizing
        # scales with the doubled axis — paid only when the clause is on.
        self._dup = cfg.nem_dup_rate > 0
        self._Cb = self._C  # base (pre-duplication) candidate count
        if self._dup:
            self._C *= 2
            self._src_of_c = _np.repeat(self._src_of_c, 2)
        _mult = 2 if self._dup else 1
        # Main pool: candidate position c owns K consecutive ring slots;
        # msg_capacity is the TOTAL ring-slot budget per lane (C * K ~
        # msg_capacity, the r3 semantics — per-destination state is just
        # validity bits over the shared ring, so it doesn't divide the
        # budget). Handler-reply and timer-broadcast positions can get
        # separate depths — see SimConfig.
        uniform = max(1, cfg.msg_capacity // self._C)
        self._Km = cfg.msg_depth_msg or uniform
        if self._fused:
            # NODE-POOLED slots: node n owns the SK = E*K (+ spare)
            # contiguous slots [n*SK, (n+1)*SK), shared by ALL its sends —
            # a send takes the i-th free slot of its node's pool, not a
            # fixed per-row ring. Bursts that cluster on one row (an ack
            # burst plus a broadcast in one latency window) then borrow
            # slack from quiet rows: depth 2 + 2 spare absorbs election
            # storms that per-row rings drop, at 2 extra slots instead of
            # a whole extra depth level (+E slots).
            self._Kt = self._Km
            self._E_pack = spec.max_out * _mult  # candidate rows per node
            self._SK = self._E_pack * self._Km + cfg.msg_spare_slots
            self._CK = N * self._SK
            self._src_of_slot = jnp.asarray(
                _np.repeat(_np.arange(N), self._SK), jnp.int32
            )  # [CK]
            self._segs = None
        else:
            self._Kt = cfg.msg_depth_timer or uniform
            self._Cm = N * spec.max_out_msg * _mult
            self._Ct = N * spec.max_out * _mult
            self._Sm = self._Cm * self._Km  # slots of the msg-position segment
            self._CK = self._Sm + self._Ct * self._Kt
            self._src_of_slot = jnp.asarray(
                _np.concatenate([
                    _np.repeat(self._src_of_c[: self._Cm], self._Km),
                    _np.repeat(self._src_of_c[self._Cm :], self._Kt),
                ]),
                jnp.int32,
            )  # [CK]
            # pack segments: (cand lo, cand hi, depth, slot lo, slot hi).
            # Equal depths collapse to ONE segment: the per-segment path
            # concatenates full pool-sized parts (extra HBM copies), so the
            # uniform case must not pay for the split.
            if self._Km == self._Kt:
                self._segs = ((0, self._C, self._Km, 0, self._CK),)
            else:
                self._segs = (
                    (0, self._Cm, self._Km, 0, self._Sm),
                    (self._Cm, self._C, self._Kt, self._Sm, self._CK),
                )
        # Straggler side pool (only when the heavy tail is on)
        if cfg.buggify_delay_rate > 0:
            self._K4 = max(1, cfg.buggify_depth)
            self._B = self._C * self._K4
            self._src_of_b = jnp.asarray(
                _np.repeat(self._src_of_c, self._K4), jnp.int32
            )  # [B]
        else:
            self._K4 = 0
            self._B = 0
        # nemesis per-lane bookkeeping exists iff a schedule-level clause
        # (or skew) is on; message-level coins (loss/dup/reorder) need none
        self._nem_state = (
            cfg.nem_crash_enabled or cfg.nem_partition_enabled
            or cfg.nem_clog_enabled or cfg.nem_spike_enabled
            or cfg.nem_skew_enabled or cfg.nem_reconfig_enabled
            or cfg.nem_disk_enabled
        )
        # occurrence-fire tracking exists iff a nemesis SCHEDULE clause is
        # on (legacy trajectory-coupled chaos has no occurrence index):
        # clause x occurrence coverage + the per-occurrence chaos report
        self._occ_track = (
            cfg.nem_crash_enabled or cfg.nem_partition_enabled
            or cfg.nem_clog_enabled or cfg.nem_spike_enabled
            or cfg.nem_reconfig_enabled or cfg.nem_disk_enabled
        )
        # durability plane (DiskFault clause, docs/nemesis.md r18): carried
        # iff the clause can fire AND the spec declares what is durable —
        # a disk-faulted spec without durable_fields recovers like a wipe
        # (nothing survives), and a durable contract without the clause
        # costs nothing
        if spec.on_recover is not None and not spec.durable_fields:
            raise ValueError(
                "spec.on_recover requires spec.durable_fields — the hook "
                "receives the durable watermark, and without declared "
                "durable fields there is nothing durable to recover from"
            )
        if spec.durable_fields and spec.sync_field is None:
            raise ValueError(
                "spec.durable_fields requires spec.sync_field — the i32 "
                "node-state counter the spec's handlers bump at their "
                "fsync points; without it the watermark could never "
                "advance past boot"
            )
        if spec.durable_fields and spec.sync_field in spec.durable_fields:
            raise ValueError(
                "spec.sync_field must not itself be durable: the watermark "
                "advance compares its live value against the PREVIOUS "
                "step's, not against the snapshot"
            )
        bad_dur = set(spec.durable_fields) & set(spec.time_fields)
        if bad_dur:
            raise ValueError(
                "durable_fields cannot include time_fields (the watermark "
                "snapshot is not epoch-rebased; an absolute time in it "
                f"would go stale): remove {sorted(bad_dur)}"
            )
        self._dur_state = cfg.nem_disk_enabled and bool(spec.durable_fields)
        if spec.durable_fields:
            import collections

            # a stable namedtuple type (created once per sim) so the dur
            # pytree structure is identical across every jitted call
            self._DurTuple = collections.namedtuple(
                "DurState", spec.durable_fields
            )
        else:
            self._DurTuple = None
        # scalar-style handlers -> [L,N] batched. `now` is per-(lane,node):
        # under the lookahead window, nodes in one step process events at
        # different virtual times.
        self._v_init = jax.vmap(jax.vmap(spec.init, in_axes=(0, 0)), in_axes=(0, None))
        if self._fused:
            self._v_on_event = jax.vmap(
                jax.vmap(spec.on_event, in_axes=(0, 0, 0, 0, 0, 0, 0)),
                in_axes=(0, 0, 0, 0, 0, 0, 0),
            )
        else:
            self._v_on_message = jax.vmap(
                jax.vmap(spec.on_message, in_axes=(0, 0, 0, 0, 0, 0, 0)),
                in_axes=(0, 0, 0, 0, 0, 0, 0),
            )
            self._v_on_timer = jax.vmap(
                jax.vmap(spec.on_timer, in_axes=(0, 0, 0, 0)),
                in_axes=(0, 0, 0, 0),
            )
        self._v_on_restart = jax.vmap(
            jax.vmap(spec.on_restart, in_axes=(0, 0, None, 0)), in_axes=(0, 0, 0, 0)
        )
        if spec.on_recover is not None:
            # on_recover(durable_state, node_id, now_us, torn, key):
            # now_us and the torn bit are per-LANE (the disk clause's
            # crash instant and schedule coin), everything else per-node
            self._v_on_recover = jax.vmap(
                jax.vmap(spec.on_recover, in_axes=(0, 0, None, None, 0)),
                in_axes=(0, 0, 0, 0, 0),
            )
        else:
            self._v_on_recover = None
        self._v_check = jax.vmap(spec.check_invariants, in_axes=(0, 0, 0))
        self.step = jax.jit(self._step)
        # jitted: eager init is dozens of small ops, each paying a host
        # dispatch (measured ~1.4 s per 32k-lane sweep before PR 1) —
        # comparable to the entire 1,270-step simulation it precedes.
        # One jitted call collapses it to one dispatch.
        self.init = jax.jit(self._init)
        # tiny scalar reduction for the chunked sweep's early-stop check:
        # dispatched BEFORE the next segment so reading it never leaves
        # the device idle for a host round-trip (see run())
        self._any_alive = jax.jit(lambda s: jnp.any(~s.done))
        # per-(mesh, segment-length) compiled shard_map'd refill segment
        # programs (see _sharded_segment): at most two lengths compile
        # per mesh (chunk + tail), exactly like the unsharded run_state
        self._sharded_cache: Dict[Tuple[Any, int], Any] = {}
        # device program launches made by this sim's run paths (init +
        # sweep segments + early-stop reductions + sharding device_put).
        # run_batch snapshots the counter around a sweep to fill
        # BatchResult.dispatches, and the dispatch-budget regression test
        # pins it: an eager-init-style regression (the r5 ~1.4 s/sweep
        # bug: dozens of per-op dispatches where one jitted program
        # should be) blows the budget loudly instead of silently eating
        # the sweep.
        self.dispatch_count = 0

    # ------------------------------------------------ node-state narrowing
    # spec.narrow_fields: {field -> narrow dtype}. The carry stores those
    # leaves narrow; the step widens them back to i32 before every handler
    # call, so spec handler arithmetic is untouched (and the narrowing is
    # value-preserving by the spec's declared bound — a field that can go
    # negative must declare a SIGNED narrow dtype). The layout lint
    # (tests/test_state_layout.py) pins the narrowing-invariance: a spec
    # run with narrow_fields stripped must produce bit-identical
    # trajectories.

    def _narrow_node(self, node):
        if not self._narrow:
            return node
        return node._replace(**{
            f: getattr(node, f).astype(dt) for f, dt in self._narrow.items()
        })

    def _widen_node(self, node):
        if not self._narrow:
            return node
        return node._replace(**{
            f: getattr(node, f).astype(jnp.int32) for f in self._narrow
        })

    def _check_narrow(self, node) -> None:
        for f, dt in self._narrow.items():
            if not hasattr(node, f):
                raise ValueError(
                    f"narrow_fields names unknown node-state field {f!r}"
                )
            if getattr(node, f).dtype != jnp.int32:
                raise ValueError(
                    f"narrow_fields[{f!r}]: only i32 fields can be "
                    f"narrowed (field is {getattr(node, f).dtype})"
                )
            if jnp.dtype(dt).itemsize >= 4:
                raise ValueError(
                    f"narrow_fields[{f!r}] = {jnp.dtype(dt)} is not "
                    "narrower than i32"
                )

    # ----------------------------------------------- durability watermark
    # spec.durable_fields: the DiskFault clause's at-rest plane. The
    # watermark stores each durable field at the SAME narrowed dtype as
    # the node carry (it is a snapshot of those exact leaves), and widens
    # back to i32 only at recovery — symmetric with _narrow_node.

    def _check_durable(self, node) -> None:
        for f in self.spec.durable_fields:
            if not hasattr(node, f):
                raise ValueError(
                    f"durable_fields names unknown node-state field {f!r}"
                )
        sf = self.spec.sync_field
        if sf is not None and not hasattr(node, sf):
            raise ValueError(
                f"sync_field names unknown node-state field {sf!r}"
            )

    def _dur_of(self, node):
        """Snapshot the durable fields of a WIDE node pytree, narrowed to
        their at-rest dtypes (the watermark's storage form)."""
        return self._DurTuple(**{
            f: (
                getattr(node, f).astype(self._narrow[f])
                if f in self._narrow else getattr(node, f)
            )
            for f in self.spec.durable_fields
        })

    def _widen_dur(self, dur):
        return dur._replace(**{
            f: getattr(dur, f).astype(jnp.int32)
            for f in self.spec.durable_fields
            if f in self._narrow
        })

    # ------------------------------------------------------------------ init

    def _init(self, seeds: jnp.ndarray, ctl=None) -> SimState:
        """Build lane state for a batch of seeds (int array [L]).

        `ctl` (triage mode only) carries the per-lane shrink controls; by
        default every clause is on and the horizon is the config's."""
        spec, cfg = self.spec, self.config
        seeds = jnp.asarray(seeds, jnp.uint32)
        L, N, CK = seeds.shape[0], spec.n_nodes, self._CK
        if ctl is not None and not self.triage:
            raise ValueError(
                "a TriageCtl requires BatchedSim(..., triage=True)"
            )
        if self.triage and ctl is None:
            ctl = default_ctl(L, cfg.horizon_us)

        key = prng.key_from(seeds)  # u32 [L]
        node_keys = prng.fold(key[:, None], jnp.arange(N, dtype=jnp.uint32))
        node_state, timer = self._v_init(node_keys, jnp.arange(N, dtype=jnp.int32))
        timer = jnp.asarray(timer, jnp.int32)
        self._check_narrow(node_state)
        if self.spec.durable_fields:
            self._check_durable(node_state)

        # per-node clock skew (nemesis): timer rate drawn once per
        # (seed, node) — the same formula FaultPlan.skew_ppm mirrors.
        # Stored as integer ppm; delays stretch via scale_delay_ppm (exact
        # int32 math — the f32 rate multiply lost microseconds past 2^24).
        fires = jnp.zeros((L, len(FIRE_KINDS)), jnp.int32)
        skew_ppm = None
        if cfg.nem_skew_enabled:
            ppm = prng.randint(
                key[:, None], NEM_SITE_SKEW, -cfg.nem_skew_max_ppm,
                cfg.nem_skew_max_ppm + 1,
                index=jnp.arange(N, dtype=jnp.uint32)[None, :],
            )  # [L,N]
            skew_applied = ppm != 0
            if self.triage:
                # a skew-disabled lane runs every node at ppm 0; the ppm
                # draws still happen (sites untouched), they just don't apply
                en_skew = _clause_on(ctl, "skew")
                ppm = jnp.where(en_skew[:, None], ppm, jnp.int32(0))
                skew_applied = skew_applied & en_skew[:, None]
            skew_ppm = ppm
            fires = fires.at[:, FIRE_INDEX["skew"]].set(
                skew_applied.sum(axis=1, dtype=jnp.int32)
            )
            # initial timers are armed at local t=0: scale the delay
            sk_ok = (timer >= 0) & (timer < INF_GUARD)
            timer = jnp.where(sk_ok, scale_delay_ppm(timer, skew_ppm), timer)

        if cfg.nem_crash_enabled:
            # occurrence-indexed: the first crash interval is draw k=0 of
            # the pure schedule (key here IS the lane base key)
            chaos_at = prng.randint(
                key, NEM_SITE_CRASH_IV, cfg.nem_crash_interval_lo_us,
                cfg.nem_crash_interval_hi_us, index=0,
            )
        elif cfg.chaos_enabled:
            chaos_at = prng.randint(
                key, 11, cfg.crash_interval_lo_us, cfg.crash_interval_hi_us
            )
        else:
            chaos_at = jnp.full((L,), INF_US, jnp.int32)
        if cfg.nem_partition_enabled:
            part_at = prng.randint(
                key, NEM_SITE_PART_IV, cfg.nem_partition_interval_lo_us,
                cfg.nem_partition_interval_hi_us, index=0,
            )
        elif cfg.partition_enabled:
            part_at = prng.randint(
                key, 12, cfg.partition_interval_lo_us, cfg.partition_interval_hi_us
            )
        else:
            part_at = jnp.full((L,), INF_US, jnp.int32)

        if self._nem_state:
            zi = jnp.zeros((L,), jnp.int32)
            zb = jnp.zeros((L,), jnp.bool_)
            nem = NemesisState(
                crash_k=zi, wipe=zb, part_k=zi,
                clog_at=(
                    prng.randint(
                        key, NEM_SITE_CLOG_IV, cfg.nem_clog_interval_lo_us,
                        cfg.nem_clog_interval_hi_us, index=0,
                    )
                    if cfg.nem_clog_enabled
                    else jnp.full((L,), INF_US, jnp.int32)
                ),
                clogged=zb, clog_src=zi, clog_dst=zi, clog_k=zi,
                spike_at=(
                    prng.randint(
                        key, NEM_SITE_SPIKE_IV, cfg.nem_spike_interval_lo_us,
                        cfg.nem_spike_interval_hi_us, index=0,
                    )
                    if cfg.nem_spike_enabled
                    else jnp.full((L,), INF_US, jnp.int32)
                ),
                spiking=zb, spike_k=zi,
                reconfig_at=(
                    prng.randint(
                        key, NEM_SITE_RECONF_IV,
                        cfg.nem_reconfig_interval_lo_us,
                        cfg.nem_reconfig_interval_hi_us, index=0,
                    )
                    if cfg.nem_reconfig_enabled
                    else jnp.full((L,), INF_US, jnp.int32)
                ),
                reconf_node=jnp.full((L,), -1, jnp.int32),
                reconfig_k=zi,
                disk_at=(
                    prng.randint(
                        key, NEM_SITE_DISK_IV, cfg.nem_disk_interval_lo_us,
                        cfg.nem_disk_interval_hi_us, index=0,
                    )
                    if cfg.nem_disk_enabled
                    else jnp.full((L,), INF_US, jnp.int32)
                ),
                disk_phase=zi,
                disk_k=zi,
                skew_ppm=skew_ppm,
            )
        else:
            nem = None

        if self._B:
            strag = StragPool(
                valid=jnp.zeros((L, self._B), jnp.bool_),
                deliver=jnp.full((L, self._B), INF_US, jnp.int32),
                dst=jnp.zeros((L, self._B), jnp.uint8),
                kind=jnp.zeros((L, self._B), self._kind_dtype),
                payload=jnp.zeros((L, self._B, spec.payload_width), jnp.int32),
                sent_eid=(
                    jnp.zeros((L, self._B), jnp.uint16)
                    if self.lineage else None
                ),
            )
        else:
            strag = None

        return SimState(
            clock=jnp.zeros((L,), jnp.int32),
            epoch=jnp.zeros((L,), jnp.int32),
            key=key,
            key0=key,
            done=jnp.zeros((L,), jnp.bool_),
            violated=jnp.zeros((L,), jnp.bool_),
            violation_at=jnp.full((L,), INF_US, jnp.int32),
            violation_epoch=jnp.zeros((L,), jnp.int32),
            violation_step=jnp.full((L,), -1, jnp.int32),
            deadlocked=jnp.zeros((L,), jnp.bool_),
            steps=jnp.zeros((L,), jnp.int32),
            events=jnp.zeros((L,), jnp.int32),
            overflow=jnp.zeros((L,), jnp.int32),
            dead_drops=jnp.zeros((L,), jnp.int32),
            nonmember_drops=jnp.zeros((L,), jnp.int32),
            unsynced_loss=jnp.zeros((L,), jnp.int32),
            fires=fires,
            occ_fired=(
                jnp.zeros((L, len(OCC_CLAUSES)), jnp.uint32)
                if self._occ_track else None
            ),
            alive_p=jnp.full(
                (L, 1), bitpack.full_mask_word(N), jnp.uint32
            ),
            crashed=jnp.full((L,), -1, jnp.int32),
            chaos_at=chaos_at,
            member_p=jnp.full(
                (L, 1), bitpack.full_mask_word(N), jnp.uint32
            ),
            member_epoch=jnp.zeros((L,), jnp.int32),
            link_ok_p=jnp.full(
                (L, N, 1), bitpack.full_mask_word(N), jnp.uint32
            ),
            partitioned=jnp.zeros((L,), jnp.bool_),
            part_at=part_at,
            timer=timer,
            node=self._narrow_node(node_state),
            # boot is fsynced: the watermark starts as the init snapshot
            dur=self._dur_of(node_state) if self._dur_state else None,
            msgs=MsgPool(
                valid_p=jnp.zeros(
                    (L, N, bitpack.packed_words(CK)), jnp.uint32
                ),
                deliver=jnp.full((L, CK), INF_US, jnp.int32),
                kind=jnp.zeros((L, CK), self._kind_dtype),
                payload=jnp.zeros((L, CK, spec.payload_width), jnp.int32),
                sent_eid=(
                    jnp.zeros((L, CK), jnp.uint16) if self.lineage else None
                ),
            ),
            strag=strag,
            nem=nem,
            ctl=ctl,
            cov=(
                Coverage(
                    bitmap=jnp.zeros((L, COV_WORDS), jnp.uint32),
                    hiwater=jnp.zeros((L,), jnp.int32),
                    transitions=jnp.zeros((L,), jnp.int32),
                )
                if self.coverage else None
            ),
            lin=(
                Lineage(
                    lam=jnp.zeros((L, N), jnp.int32),
                    eid=jnp.zeros((L,), jnp.uint32),
                )
                if self.lineage else None
            ),
            queue=None,
            refill=None,
        )

    # ------------------------------------------------------------------ step

    def _step(self, state: SimState) -> SimState:
        return self._step_scoped(state)[0]

    def _step_split(self, hot: SimState, cold: ColdState, const: ConstState):
        """One step in the sweep loop's (hot, cold | const) form: const is
        an invariant OPERAND, not part of the returned carry — the compiled
        loop body reads key0/ctl/skew_ppm but never re-emits them. This is
        the program benches/roofline.py accounts bytes for (the step the
        sweep actually runs); merge/split are free pytree restructuring."""
        s2, rec = self._step_scoped(merge_state(hot, cold, const))
        h2, c2, _ = split_state(s2)
        return h2, c2, rec

    def _step_scoped(self, state: SimState) -> Tuple[SimState, TraceRecord]:
        """`_step_traced` with every op under the named scope
        `step/<phase>`: one phase per group of its numbered sections
        (`STEP_PHASES`), so a device trace splits the step by phase."""
        phase = _Phases("step")
        try:
            return self._step_traced(state, phase)
        finally:
            phase.close()

    def _step_traced(
        self, state: SimState, phase: "_Phases"
    ) -> Tuple[SimState, TraceRecord]:
        """One engine step + the step's TraceRecord.

        Untraced callers discard the record; XLA dead-code-eliminates its
        construction, so the trace costs nothing unless collected.
        `phase(name)` opens each section group's named scope."""
        spec, cfg = self.spec, self.config
        N, CK, P = spec.n_nodes, self._CK, spec.payload_width
        L = state.clock.shape[0]
        msgs = state.msgs
        strag: Optional[StragPool] = state.strag
        narange = jnp.arange(N, dtype=jnp.int32)

        phase("select")
        # -- 0. unpack the compacted carry (r8, docs/state_layout.md):
        # bit-packed bool planes -> bool tensors, narrow node leaves ->
        # i32. Pure elementwise shifts/converts that fuse into the step;
        # the wide forms live only inside this kernel and are repacked at
        # the end, so the HBM-resident carry stays narrow.
        valid = bitpack.unpack_bits(msgs.valid_p, CK)  # bool [L,N,CK]
        alive = bitpack.unpack_bits(state.alive_p, N)  # bool [L,N]
        link_ok = bitpack.unpack_bits(state.link_ok_p, N)  # bool [L,N,N]
        node0 = self._widen_node(state.node)

        # -- 1. advance each lane to its next event window -----------------
        # (the advance_to_next_event analog, time/mod.rs:45-60, batched).
        # Node n's pending messages are the static slice valid[:, n, :]
        # over the shared ring — no destination matching (see MsgPool).
        t_pend = jnp.where(valid, msgs.deliver[:, None, :], INF_US)  # [L,N,CK]
        tmsg_n = t_pend.min(axis=2)  # [L,N]
        if self._B:
            sd_oh = strag.dst[:, :, None] == narange[None, None, :]  # [L,B,N]
            ts_b = jnp.where(strag.valid, strag.deliver, INF_US)  # [L,B]
            t_sn = jnp.where(sd_oh, ts_b[:, :, None], INF_US)  # [L,B,N]
            tmsg_strag = t_sn.min(axis=1)  # [L,N]
            tmsg_n = jnp.minimum(tmsg_n, tmsg_strag)
        tmsg_n = jnp.where(alive, tmsg_n, INF_US)
        ttmr_n = jnp.where(alive, state.timer, INF_US)  # [L,N]
        t_next = jnp.minimum(
            jnp.minimum(jnp.minimum(tmsg_n.min(axis=1), ttmr_n.min(axis=1)),
                        state.chaos_at),
            state.part_at,
        )
        # nemesis clog/spike toggles are events too: lanes must advance to
        # them even when the protocol is quiet (chaos_at/part_at already
        # carry the crash and partition clauses, legacy or nemesis)
        if cfg.nem_clog_enabled:
            t_next = jnp.minimum(t_next, state.nem.clog_at)
        if cfg.nem_spike_enabled:
            t_next = jnp.minimum(t_next, state.nem.spike_at)
        if cfg.nem_reconfig_enabled:
            t_next = jnp.minimum(t_next, state.nem.reconfig_at)
        if cfg.nem_disk_enabled:
            t_next = jnp.minimum(t_next, state.nem.disk_at)

        deadlocked = (~state.done) & (t_next >= INF_US)
        active = (~state.done) & (t_next < INF_US)

        # conservative-DES lookahead window [t_next, t_next + latency_lo):
        # any message EMITTED by an in-window event arrives at
        # >= t_next + latency_lo, so in-window events on different nodes are
        # causally independent and each node may process its earliest one
        # this step (classic PDES lookahead; see SimConfig.lookahead).
        # Whenever the next crash/partition instant falls anywhere inside
        # the window, the window shrinks to the exact instant t_next (the
        # chaos itself fires only once it IS t_next), so chaos state never
        # applies to sends from earlier virtual times. The buggify tail only
        # LENGTHENS latencies, so latency_lo remains the lookahead bound.
        lo_w = max(0, cfg.latency_lo_us - 1) if cfg.lookahead else 0
        w_end = jnp.minimum(t_next, INF_US - lo_w - 1) + lo_w
        if lo_w and (
            cfg.any_crash_enabled or cfg.any_partition_enabled
            or cfg.nem_clog_enabled or cfg.nem_spike_enabled
            or cfg.nem_reconfig_enabled or cfg.nem_disk_enabled
        ):
            next_chaos = jnp.minimum(state.chaos_at, state.part_at)
            if cfg.nem_clog_enabled:
                next_chaos = jnp.minimum(next_chaos, state.nem.clog_at)
            if cfg.nem_spike_enabled:
                next_chaos = jnp.minimum(next_chaos, state.nem.spike_at)
            if cfg.nem_reconfig_enabled:
                next_chaos = jnp.minimum(next_chaos, state.nem.reconfig_at)
            if cfg.nem_disk_enabled:
                next_chaos = jnp.minimum(next_chaos, state.nem.disk_at)
            chaos_in_w = next_chaos <= w_end
            w_end = jnp.where(chaos_in_w, t_next, w_end)

        # -- 2. advance per-lane keys (cheap hash chain, see prng.py) ------
        key = _chain_key(state.key)
        node_key = prng.fold(key[:, None], jnp.arange(N, dtype=jnp.uint32))  # [L,N]
        mkeys = prng.fold(node_key, 101)
        tkeys = prng.fold(node_key, 102)
        rkeys = prng.fold(node_key, 103)
        ckey = prng.fold(key, 104)  # [L]

        # -- 3. pick each node's event: earliest in-window message or timer
        # (one event per node per step keeps per-node order exact)
        msg_due = active[:, None] & (tmsg_n <= w_end[:, None])  # [L,N]
        tmr_due = active[:, None] & (ttmr_n <= w_end[:, None])  # [L,N]
        if cfg.sched_randomize:
            # message-vs-timer order: when both are due at the SAME instant,
            # half the time the timer fires first (the message waits a step;
            # its deliver time has passed so it stays due) — same-instant
            # event reordering, the utils/mpsc.rs:71-84 analog
            timer_first = prng.bernoulli(prng.fold(node_key, 108), 1, 0.5)
        else:
            timer_first = jnp.zeros((L, N), jnp.bool_)
        tie = msg_due & tmr_due & (tmsg_n == ttmr_n)
        has_msg = msg_due & (
            ~tmr_due | (tmsg_n < ttmr_n) | (tie & ~timer_first)
        )
        due_t = tmr_due & (
            ~msg_due | (ttmr_n < tmsg_n) | (tie & timer_first)
        )
        # per-node event time; inactive nodes default to the window start
        t_evt = jnp.where(has_msg, tmsg_n, jnp.where(due_t, ttmr_n, t_next[:, None]))

        # main-pool slot choice: among this node's earliest-time slots
        head = valid & (t_pend == tmsg_n[:, :, None])  # [L,N,CK]
        if cfg.sched_randomize:
            # random tie-break among equal-timestamp due messages — the
            # scheduling-nondeterminism amplifier (utils/mpsc.rs:71-84):
            # seeds that share a chaos schedule still explore different
            # delivery orders, the reference's biggest bug-finding lever.
            # Priorities are drawn per RING SLOT and shared across
            # destination nodes (measured ~4% of the step to draw per
            # (node, slot)): two nodes tying over the SAME slot set pick
            # the same winner that step, but the draw refolds from the
            # lane key every step and per-seed variation is unaffected —
            # the per-node event ORDER stays randomized across steps/seeds
            slot_idx = jnp.arange(CK, dtype=jnp.uint32)
            prio = prng.bits(
                prng.fold(key, 107)[:, None], 1, index=slot_idx[None]
            )[:, None, :]  # u32 [L,1,CK]
            prio_m = jnp.where(head, prio, jnp.uint32(0xFFFFFFFF))
            slot = jnp.argmin(prio_m, axis=2)  # [L,N]
        else:
            slot = jnp.argmin(jnp.where(head, t_pend, INF_US), axis=2)  # [L,N]

        # straggler beats the main pool only with a strictly earlier time
        # (same-instant cross-pool ties go to the main pool; tail events are
        # rare enough that the ordering bias is negligible)
        if self._B:
            strag_win = has_msg & (tmsg_strag < t_pend.min(axis=2))
            s_head = jnp.where(
                t_sn == tmsg_strag[:, None, :], ts_b[:, :, None], INF_US
            )  # [L,B,N]
            s_slot = jnp.argmin(
                jnp.where(t_sn == tmsg_strag[:, None, :], t_sn, INF_US), axis=1
            )  # [L,N]
            del s_head
        else:
            strag_win = jnp.zeros((L, N), jnp.bool_)

        # field extraction via one-hot multiply-reduce over the node's OWN
        # slot region [L,N,CK] — small because the pool is dest-major.
        # (NOT gathers: take_along_axis here measured ~8x slower end-to-end
        # on TPU v5e — XLA lowers batched small-domain gathers poorly, while
        # the one-hot form fuses into the surrounding elementwise work.)
        pick_oh = jnp.arange(CK)[None, None, :] == slot[:, :, None]  # [L,N,CK]
        pick_ohi = pick_oh.astype(jnp.int32)
        m_src = (self._src_of_slot[None, None, :] * pick_ohi).sum(2)
        m_kind = (msgs.kind.astype(jnp.int32)[:, None, :] * pick_ohi).sum(2)
        m_pay = (msgs.payload[:, None, :, :] * pick_ohi[:, :, :, None]).sum(2)
        if self._B:
            s_pick = (
                jnp.arange(self._B)[None, None, :] == s_slot[:, :, None]
            ).astype(jnp.int32)  # [L,N,B]
            sm_src = (self._src_of_b[None, None, :] * s_pick).sum(2)
            sm_kind = (strag.kind.astype(jnp.int32)[:, None, :] * s_pick).sum(2)
            sm_pay = (strag.payload[:, None, :, :] * s_pick[:, :, :, None]).sum(2)
            m_src = jnp.where(strag_win, sm_src, m_src)
            m_kind = jnp.where(strag_win, sm_kind, m_kind)
            m_pay = jnp.where(strag_win[:, :, None], sm_pay, m_pay)
        node_ids = jnp.broadcast_to(narange, (L, N))

        # -- 3b. causal lineage (BatchedSim(lineage=True); docs/causality.md)
        # Event ids: every delivery/timer-fire gets the lane's next global
        # id, assigned in node order within the step (the same order the
        # host-side decoder and the host-runtime mirror use). The delivered
        # slot's u16 sent_eid stamp widens back to the full u32 send eid by
        # rolling-window reconstruction against the lane's event counter:
        # every in-flight message was sent at an earlier step, so its eid
        # is the largest value <= eid-1 congruent to the stamp mod 2^16 —
        # exact while < 65536 lane events happen during any flight (the
        # decoder verifies this, never trusts it). Lamport clocks update
        # max(local, sender)+1 on delivery with the send eid as the
        # sender's value, +1 on timer fires. OBSERVE-ONLY: nothing here
        # feeds a draw, a handler, or any non-lineage output.
        lin: Optional[Lineage] = state.lin
        if lin is not None:
            evt_lin = has_msg | due_t  # [L,N]
            acc_e = jnp.zeros((L,), jnp.uint32)
            rank_cols = []
            for n_i in range(N):  # N is small + static: unrolled prefix
                rank_cols.append(acc_e)
                acc_e = acc_e + evt_lin[:, n_i].astype(jnp.uint32)
            evt_eid_full = lin.eid[:, None] + jnp.stack(rank_cols, axis=1)
            new_lin_eid = lin.eid + acc_e
            # delivered slot's stamp (same one-hot extraction as m_kind)
            m_seid16 = (
                msgs.sent_eid.astype(jnp.int32)[:, None, :] * pick_ohi
            ).sum(2)
            if self._B:
                sm_seid16 = (
                    strag.sent_eid.astype(jnp.int32)[:, None, :] * s_pick
                ).sum(2)
                m_seid16 = jnp.where(strag_win, sm_seid16, m_seid16)
            prev_e = (lin.eid - jnp.uint32(1))[:, None]  # eids in flight <= this
            m_seid = prev_e - (
                (prev_e - m_seid16.astype(jnp.uint32)) & jnp.uint32(0xFFFF)
            )  # u32 [L,N] full send eid (garbage where ~has_msg, masked below)
            new_lam = jnp.where(
                has_msg,
                jnp.maximum(lin.lam, m_seid.astype(jnp.int32)) + 1,
                jnp.where(due_t, lin.lam + 1, lin.lam),
            )
            tr_lam = new_lam
            tr_evt_eid = jnp.where(evt_lin, evt_eid_full, EID_NONE)
            tr_sent_eid = jnp.where(has_msg, m_seid, EID_NONE)
        else:
            evt_eid_full = None
            tr_lam = tr_evt_eid = tr_sent_eid = None

        phase("handlers")
        # -- 4. run handlers + fused state select. The three masks are
        # pairwise DISJOINT: at most one event per node per step (msg vs
        # timer), and a restarting node was dead all step (dead nodes'
        # queues and timers are masked out of the event pick), so its event
        # masks are false. One tree pass merges all three outcomes instead
        # of three full-state passes.
        any_crash = cfg.any_crash_enabled
        ctl: Optional[TriageCtl] = state.ctl
        if any_crash:
            chaos_due = active & (state.chaos_at <= t_next)
            is_restart_evt = state.crashed >= 0
            do_crash = chaos_due & ~is_restart_evt
            do_restart = chaos_due & is_restart_evt
            if cfg.nem_crash_enabled:
                # nemesis: victim is draw k of the pure schedule — a
                # function of the SEED, not of when the crash fires
                victim = prng.randint(
                    state.key0, NEM_SITE_CRASH_VICTIM, 0, N,
                    index=state.nem.crash_k,
                )
            else:
                victim = prng.randint(ckey, 1, 0, N)
            # triage: a suppressed occurrence keeps the timing machinery
            # (chaos_at / crashed / crash_k advance through the window as
            # always — do_crash/do_restart below) but applies NO effect:
            # ap_* gate the kill, the restart handler, the pool drops, the
            # trace rows and the fire counts. Later occurrences keep their
            # schedule-pure times, so one dropped atom never moves another.
            if self.triage:
                k_idx = (
                    state.nem.crash_k if cfg.nem_crash_enabled
                    else jnp.zeros((L,), jnp.int32)
                )
                crash_en = _occ_on(ctl, "crash", k_idx)
            else:
                crash_en = jnp.ones((L,), jnp.bool_)
            ap_crash = do_crash & crash_en
            ap_restart = do_restart & crash_en
            crash_mask = ap_crash[:, None] & (node_ids == victim[:, None])
            restart_node = jnp.clip(state.crashed, 0, N - 1)
            restart_mask = ap_restart[:, None] & (node_ids == restart_node[:, None])
        else:
            restart_mask = None

        if any_crash:
            # `now` for a restarting node is the chaos instant t_next (the
            # window collapses to it on chaos steps), never an earlier
            # clock — a restart timer must not be armed in the past
            ns_r, timer_r = self._v_on_restart(
                node0, node_ids, t_next, rkeys
            )
            if cfg.nem_crash_enabled and cfg.nem_crash_wipe_rate > 0:
                # crash-with-state-wipe: the marked node restarts from
                # `init` (durable state gone too), its declared absolute
                # time fields and first timer shifted to the restart
                # instant. The wipe flag was drawn at crash time and rides
                # state.nem.wipe through the down window.
                ns_w, timer_w = self._v_init(rkeys, narange)
                timer_w = jnp.asarray(timer_w, jnp.int32)
                w_ok = (timer_w >= 0) & (timer_w < INF_GUARD)
                timer_w = jnp.where(w_ok, timer_w + t_next[:, None], timer_w)
                if spec.time_fields:
                    ns_w = ns_w._replace(**{
                        f: getattr(ns_w, f)
                        + t_next.reshape((L,) + (1,) * (getattr(ns_w, f).ndim - 1))
                        for f in spec.time_fields
                    })
                wipe_mask = restart_mask & state.nem.wipe[:, None]
                if self.triage:
                    # wipe is its own triage atom: with it off, the crash
                    # occurrence still happens but restarts via on_restart
                    wipe_mask = wipe_mask & _clause_on(ctl, "wipe")[:, None]
                ns_r = _tree_where(wipe_mask, ns_w, ns_r)
                timer_r = jnp.where(wipe_mask, timer_w, timer_r)

        if self._fused:
            # ONE handler invocation per node per step: kind == -1 encodes
            # "your timer fired" (see ProtocolSpec.on_event). This avoids
            # materializing two full candidate states and the 3-way merge —
            # the dual-handler tax measured larger than either handler body.
            evt = has_msg | due_t
            evt_kind = jnp.where(has_msg, m_kind, jnp.int32(-1))
            ns_e, out_e, timer_e = self._v_on_event(
                node0, node_ids, m_src, evt_kind, m_pay, t_evt, mkeys
            )

            def merge(old, e, r):
                ek = evt.reshape(evt.shape + (1,) * (old.ndim - 2))
                out = jnp.where(ek, e, old)
                if r is not None:
                    rk = restart_mask.reshape(ek.shape)
                    out = jnp.where(rk, r, out)
                return out

            if any_crash:
                node = jax.tree_util.tree_map(merge, node0, ns_e, ns_r)
            else:
                node = jax.tree_util.tree_map(
                    lambda old, e: merge(old, e, None), node0, ns_e
                )
            timer_m = timer_t = timer_e
        else:
            ns_m, out_m, timer_m = self._v_on_message(
                node0, node_ids, m_src, m_kind, m_pay, t_evt, mkeys
            )
            ns_t, out_t, timer_t = self._v_on_timer(
                node0, node_ids, t_evt, tkeys
            )

            def merge(old, m, t, r):
                mk = has_msg.reshape(has_msg.shape + (1,) * (old.ndim - 2))
                tk = due_t.reshape(mk.shape)
                out = jnp.where(tk, t, jnp.where(mk, m, old))
                if r is not None:
                    rk = restart_mask.reshape(mk.shape)
                    out = jnp.where(rk, r, out)
                return out

            if any_crash:
                node = jax.tree_util.tree_map(
                    merge, node0, ns_m, ns_t, ns_r
                )
            else:
                node = jax.tree_util.tree_map(
                    lambda old, m, t: merge(old, m, t, None),
                    node0, ns_m, ns_t,
                )
        # message handlers return a negative timer to keep the current
        # deadline; timer handlers return a negative value to disarm
        if cfg.nem_skew_enabled:
            # per-node clock skew: a handler's ABSOLUTE deadline encodes a
            # relative delay from its own event time — stretch/shrink that
            # delay by the node's ppm rate (sentinels and keep/disarm
            # negatives pass through untouched). Integer ppm math
            # (scale_delay_ppm) is EXACT for every i32 delay; the old f32
            # rate multiply dropped microseconds once deadlines passed
            # 2^24 us, i.e. ~16.7 s into any lane's virtual time.
            skew_ppm_now = state.nem.skew_ppm  # i32 [L,N]

            def skew_deadline(deadline, now):
                d = deadline - now
                stretched = now + scale_delay_ppm(d, skew_ppm_now)
                ok = (deadline >= 0) & (deadline < INF_GUARD) & (d > 0)
                return jnp.where(ok, stretched, deadline)

            if self._fused:
                timer_m = timer_t = skew_deadline(timer_e, t_evt)
            else:
                timer_m = skew_deadline(timer_m, t_evt)
                timer_t = skew_deadline(timer_t, t_evt)
            if any_crash:
                timer_r = skew_deadline(
                    timer_r, jnp.broadcast_to(t_next[:, None], (L, N))
                )
        timer = jnp.where(has_msg & (timer_m >= 0), timer_m, state.timer)
        timer = jnp.where(
            due_t, jnp.where(timer_t >= 0, timer_t, INF_US), timer
        )
        if any_crash:
            timer = jnp.where(restart_mask, timer_r, timer)
        # consume the delivered slot (reusing the extraction one-hots)
        consumed_main = has_msg & ~strag_win  # [L,N]
        valid = valid & ~(pick_oh & consumed_main[:, :, None])
        if self._B:
            s_oh = (s_pick > 0) & strag_win[:, :, None]  # [L,N,B]
            svalid = strag.valid & ~s_oh.any(axis=1)
        # lane clock: the latest event time processed this step (chaos-only
        # steps advance to the chaos instant t_next)
        clock = jnp.where(
            active,
            jnp.maximum(state.clock, t_evt.max(axis=1)),
            state.clock,
        )

        phase("chaos")
        # -- 5. crash/restart chaos (Handle::kill/restart analog) ----------
        # (`alive` was unpacked from the carry at step 0)
        crashed, chaos_at = state.crashed, state.chaos_at
        tr_crash = jnp.full((L,), -1, jnp.int32)
        tr_restart = jnp.full((L,), -1, jnp.int32)
        nem_crash_k, nem_wipe = None, None
        if any_crash:
            alive = (alive & ~crash_mask) | restart_mask
            if cfg.nem_crash_enabled:
                # schedule arithmetic: next toggle = PREVIOUS toggle time
                # plus an occurrence-indexed delta — never `clock + delta`,
                # which would couple the schedule to the trajectory
                ck_n = state.nem.crash_k
                restart_delay = prng.randint(
                    state.key0, NEM_SITE_CRASH_DOWN, cfg.nem_crash_down_lo_us,
                    cfg.nem_crash_down_hi_us, index=ck_n,
                )
                next_crash = prng.randint(
                    state.key0, NEM_SITE_CRASH_IV, cfg.nem_crash_interval_lo_us,
                    cfg.nem_crash_interval_hi_us, index=ck_n + 1,
                )
                chaos_at = jnp.where(
                    do_crash,
                    state.chaos_at + restart_delay,
                    jnp.where(
                        do_restart, state.chaos_at + next_crash, state.chaos_at
                    ),
                )
                nem_crash_k = ck_n + do_restart.astype(jnp.int32)
                wipe_coin = (
                    prng.bits(state.key0, NEM_SITE_CRASH_WIPE, index=ck_n)
                    % jnp.uint32(COIN_DENOM)
                ) < jnp.uint32(round(cfg.nem_crash_wipe_rate * COIN_DENOM))
                nem_wipe = jnp.where(
                    do_crash, wipe_coin,
                    jnp.where(do_restart, False, state.nem.wipe),
                )
            else:
                restart_delay = prng.randint(
                    ckey, 2, cfg.restart_delay_lo_us, cfg.restart_delay_hi_us
                )
                next_crash = prng.randint(
                    ckey, 3, cfg.crash_interval_lo_us, cfg.crash_interval_hi_us
                )
                chaos_at = jnp.where(
                    do_crash,
                    clock + restart_delay,
                    jnp.where(do_restart, clock + next_crash, state.chaos_at),
                )
            crashed = jnp.where(
                do_crash, victim, jnp.where(do_restart, -1, state.crashed)
            )
            tr_crash = jnp.where(ap_crash, victim, -1)
            tr_restart = jnp.where(ap_restart, restart_node, -1)
            # in-flight messages to a crashed node are lost (reset_node closes
            # sockets, network.rs:142-147): its pool slice simply empties
            valid = valid & ~crash_mask[:, :, None]
            if self._B:
                svalid = svalid & ~(
                    ap_crash[:, None] & (strag.dst == victim[:, None])
                )

        # -- 5b. partition chaos: random bipartition splits, later heals ----
        # (the clog_link masks of network.rs:261-269, lane-batched;
        # `link_ok` was unpacked from the carry at step 0)
        partitioned, part_at = state.partitioned, state.part_at
        tr_split = jnp.zeros((L,), jnp.bool_)
        tr_heal = jnp.zeros((L,), jnp.bool_)
        tr_side = jnp.zeros((L,), jnp.int32)
        nem_part_k = None
        if cfg.any_partition_enabled:
            part_due = active & (state.part_at <= t_next)
            do_split = part_due & ~state.partitioned
            do_heal = part_due & state.partitioned
            if cfg.nem_partition_enabled:
                pk_n = state.nem.part_k
                # per-node side bit at occurrence k: index = k * 64 + node
                # (pure in the seed; FaultPlan.schedule draws the same bit)
                side = (
                    prng.bits(
                        state.key0[:, None], NEM_SITE_PART_SIDE,
                        index=pk_n[:, None].astype(jnp.uint32) * 64
                        + jnp.arange(N, dtype=jnp.uint32)[None, :],
                    )
                    & 1
                ) == 1  # [L,N]
                heal_delay = prng.randint(
                    state.key0, NEM_SITE_PART_HEAL, cfg.nem_partition_heal_lo_us,
                    cfg.nem_partition_heal_hi_us, index=pk_n,
                )
                next_split = prng.randint(
                    state.key0, NEM_SITE_PART_IV,
                    cfg.nem_partition_interval_lo_us,
                    cfg.nem_partition_interval_hi_us, index=pk_n + 1,
                )
                part_at = jnp.where(
                    do_split,
                    state.part_at + heal_delay,
                    jnp.where(do_heal, state.part_at + next_split, state.part_at),
                )
                nem_part_k = pk_n + do_heal.astype(jnp.int32)
            else:
                pkey = prng.fold(key, 106)
                # each node draws a side; links crossing the cut go down
                # both ways
                side = (
                    prng.uniform(
                        pkey[:, None], 7,
                        index=jnp.arange(N, dtype=jnp.uint32)[None, :],
                    )
                    < 0.5
                )  # [L,N]
                heal_delay = prng.randint(
                    pkey, 8, cfg.partition_heal_lo_us, cfg.partition_heal_hi_us
                )
                next_split = prng.randint(
                    pkey, 9, cfg.partition_interval_lo_us,
                    cfg.partition_interval_hi_us,
                )
                part_at = jnp.where(
                    do_split,
                    clock + heal_delay,
                    jnp.where(do_heal, clock + next_split, state.part_at),
                )
            if self.triage:
                pk_idx = (
                    state.nem.part_k if cfg.nem_partition_enabled
                    else jnp.zeros((L,), jnp.int32)
                )
                part_en = _occ_on(ctl, "partition", pk_idx)
            else:
                part_en = jnp.ones((L,), jnp.bool_)
            # a suppressed occurrence toggles `partitioned` (timing) but
            # never touches link_ok: its heal is then a no-op on links that
            # were never cut (part_k is the same k at split and heal)
            ap_split = do_split & part_en
            ap_heal = do_heal & part_en
            same_side = side[:, :, None] == side[:, None, :]  # [L,N,N]
            link_ok = jnp.where(
                ap_split[:, None, None],
                same_side,
                jnp.where(ap_heal[:, None, None], True, link_ok),
            )
            partitioned = (state.partitioned | do_split) & ~do_heal
            tr_split, tr_heal = ap_split, ap_heal
            # the sides on the split's own step only: the legacy path draws
            # `side` from the chain key every step, done lanes' included
            tr_side = jnp.where(tr_split, (
                side.astype(jnp.int32) * (1 << jnp.arange(N, dtype=jnp.int32))
            ).sum(-1), 0)

        # -- 5c. nemesis link-clog + latency-spike windows ------------------
        # (toggle machinery like crash/partition, schedule-timed; the clog
        # is ASYMMETRIC — src->dst only — unlike the bipartition masks)
        tr_clog_src = jnp.full((L,), -1, jnp.int32)
        tr_clog_dst = jnp.full((L,), -1, jnp.int32)
        tr_unclog = jnp.zeros((L,), jnp.bool_)
        clogged = clog_src = clog_dst = None
        clog_en = None
        nem_clog_at = nem_clog_k = None
        if cfg.nem_clog_enabled:
            nst = state.nem
            clog_due = active & (nst.clog_at <= t_next)
            do_clog = clog_due & ~nst.clogged
            do_unclog = clog_due & nst.clogged
            kk = nst.clog_k
            # triage: clog_k names the window open (or opening) this step,
            # so one gate covers the toggle trace rows AND every in-window
            # send filtered below (the window still opens/closes on time)
            clog_en = (
                _occ_on(ctl, "clog", kk) if self.triage
                else jnp.ones((L,), jnp.bool_)
            )
            src_d = prng.randint(state.key0, NEM_SITE_CLOG_SRC, 0, N, index=kk)
            dst_d = prng.randint(
                state.key0, NEM_SITE_CLOG_DST, 0, N - 1, index=kk
            )
            dst_d = dst_d + (dst_d >= src_d).astype(jnp.int32)  # skip src
            clog_src = jnp.where(do_clog, src_d, nst.clog_src)
            clog_dst = jnp.where(do_clog, dst_d, nst.clog_dst)
            clogged = (nst.clogged | do_clog) & ~do_unclog
            heal_d = prng.randint(
                state.key0, NEM_SITE_CLOG_HEAL, cfg.nem_clog_heal_lo_us,
                cfg.nem_clog_heal_hi_us, index=kk,
            )
            next_d = prng.randint(
                state.key0, NEM_SITE_CLOG_IV, cfg.nem_clog_interval_lo_us,
                cfg.nem_clog_interval_hi_us, index=kk + 1,
            )
            nem_clog_at = jnp.where(
                do_clog, nst.clog_at + heal_d,
                jnp.where(do_unclog, nst.clog_at + next_d, nst.clog_at),
            )
            nem_clog_k = kk + do_unclog.astype(jnp.int32)
            tr_clog_src = jnp.where(do_clog & clog_en, src_d, -1)
            tr_clog_dst = jnp.where(do_clog & clog_en, dst_d, -1)
            tr_unclog = do_unclog & clog_en
        tr_spike_on = jnp.zeros((L,), jnp.bool_)
        tr_spike_off = jnp.zeros((L,), jnp.bool_)
        spiking = None
        spike_en = None
        nem_spike_at = nem_spike_k = None
        if cfg.nem_spike_enabled:
            nst = state.nem
            spike_due = active & (nst.spike_at <= t_next)
            do_spike = spike_due & ~nst.spiking
            do_unspike = spike_due & nst.spiking
            sk = nst.spike_k
            spike_en = (
                _occ_on(ctl, "spike", sk) if self.triage
                else jnp.ones((L,), jnp.bool_)
            )
            spiking = (nst.spiking | do_spike) & ~do_unspike
            dur_d = prng.randint(
                state.key0, NEM_SITE_SPIKE_DUR, cfg.nem_spike_duration_lo_us,
                cfg.nem_spike_duration_hi_us, index=sk,
            )
            next_d = prng.randint(
                state.key0, NEM_SITE_SPIKE_IV, cfg.nem_spike_interval_lo_us,
                cfg.nem_spike_interval_hi_us, index=sk + 1,
            )
            nem_spike_at = jnp.where(
                do_spike, nst.spike_at + dur_d,
                jnp.where(do_unspike, nst.spike_at + next_d, nst.spike_at),
            )
            nem_spike_k = sk + do_unspike.astype(jnp.int32)
            tr_spike_on = do_spike & spike_en
            tr_spike_off = do_unspike & spike_en

        # -- 5d. nemesis membership reconfiguration (remove/join windows) --
        # Same toggle machinery as crash's down-window, on the MEMBERSHIP
        # plane: a remove takes the schedule-drawn victim out of the
        # cluster (member + alive bits cleared, in-flight messages to it
        # lost), the paired join brings the SAME node back as a FRESH
        # replica — rebuilt through the real spec.init like wipe-restart,
        # never from its pre-removal state. member_epoch counts every
        # applied configuration change. reconf_node doubles as the
        # open/closed discriminator (-1 = all members, next event is a
        # remove), exactly like `crashed` does for the crash clause.
        tr_remove = jnp.full((L,), -1, jnp.int32)
        tr_join = jnp.full((L,), -1, jnp.int32)
        member = None
        member_epoch = state.member_epoch
        nem_reconfig_at = nem_reconf_node = nem_reconfig_k = None
        if cfg.nem_reconfig_enabled:
            # the remove and the join under their own scope in chaos
            with jax.named_scope("membership"):
                nst = state.nem
                member = bitpack.unpack_bits(state.member_p, N)  # bool [L,N]
                reconf_due = active & (nst.reconfig_at <= t_next)
                do_remove = reconf_due & (nst.reconf_node < 0)
                do_join = reconf_due & (nst.reconf_node >= 0)
                rk = nst.reconfig_k
                # one gate per occurrence covers BOTH halves (k increments at
                # the join, like clog/spike close their windows): a suppressed
                # occurrence advances the timing machinery through its window
                # but applies no membership change at all
                reconf_en = (
                    _occ_on(ctl, "reconfig", rk) if self.triage
                    else jnp.ones((L,), jnp.bool_)
                )
                victim_d = prng.randint(
                    state.key0, NEM_SITE_RECONF_VICTIM, 0, N, index=rk
                )
                join_node = jnp.clip(nst.reconf_node, 0, N - 1)
                ap_remove = do_remove & reconf_en
                ap_join = do_join & reconf_en
                remove_mask = ap_remove[:, None] & (node_ids == victim_d[:, None])
                join_mask = ap_join[:, None] & (node_ids == join_node[:, None])
                member = (member & ~remove_mask) | join_mask
                # liveness and membership stay INDEPENDENT planes (a crashed
                # member is dead_drops, a removed node nonmember_drops), but a
                # remove also downs the node and a join revives it: a removed
                # replica must not keep firing timers against the cluster
                alive = (alive & ~remove_mask) | join_mask
                member_epoch = member_epoch + (ap_remove | ap_join).astype(jnp.int32)
                # in-flight messages to the removed node are lost, like a
                # crash (its pool slice empties; not counted as drops either)
                valid = valid & ~remove_mask[:, :, None]
                if self._B:
                    svalid = svalid & ~(
                        ap_remove[:, None] & (strag.dst == victim_d[:, None])
                    )
                # the joining node is a fresh replica: rebuilt through the
                # real spec.init (the wipe-restart idiom), its first timer and
                # declared absolute-time fields shifted to the join instant
                ns_j, timer_j = self._v_init(rkeys, narange)
                timer_j = jnp.asarray(timer_j, jnp.int32)
                j_ok = (timer_j >= 0) & (timer_j < INF_GUARD)
                timer_j = jnp.where(j_ok, timer_j + t_next[:, None], timer_j)
                if cfg.nem_skew_enabled:
                    dj = timer_j - t_next[:, None]
                    sk_j = j_ok & (dj > 0)
                    timer_j = jnp.where(
                        sk_j,
                        t_next[:, None] + scale_delay_ppm(dj, state.nem.skew_ppm),
                        timer_j,
                    )
                if spec.time_fields:
                    ns_j = ns_j._replace(**{
                        f: getattr(ns_j, f)
                        + t_next.reshape((L,) + (1,) * (getattr(ns_j, f).ndim - 1))
                        for f in spec.time_fields
                    })
                node = _tree_where(join_mask, ns_j, node)
                timer = jnp.where(join_mask, timer_j, timer)
                # schedule arithmetic: next toggle = previous toggle time plus
                # an occurrence-indexed delta (never clock + delta)
                down_d = prng.randint(
                    state.key0, NEM_SITE_RECONF_DUR, cfg.nem_reconfig_down_lo_us,
                    cfg.nem_reconfig_down_hi_us, index=rk,
                )
                next_d = prng.randint(
                    state.key0, NEM_SITE_RECONF_IV,
                    cfg.nem_reconfig_interval_lo_us,
                    cfg.nem_reconfig_interval_hi_us, index=rk + 1,
                )
                nem_reconfig_at = jnp.where(
                    do_remove, nst.reconfig_at + down_d,
                    jnp.where(do_join, nst.reconfig_at + next_d, nst.reconfig_at),
                )
                nem_reconf_node = jnp.where(
                    do_remove, victim_d, jnp.where(do_join, -1, nst.reconf_node)
                )
                nem_reconfig_k = rk + do_join.astype(jnp.int32)
                tr_remove = jnp.where(ap_remove, victim_d, -1)
                tr_join = jnp.where(ap_join, join_node, -1)

        # durability watermark ADVANCE (DiskFault plane, half 1 of 2):
        # re-snapshot the durable fields of every node whose sync counter
        # increased this step — the spec's declared fsync points. Done
        # BEFORE the disk clause below, so the ordering is the safety
        # argument for correct specs: the handler ran, THEN the watermark
        # advanced, THEN the disk crash measures its loss — a spec that
        # syncs before acking can never lose an acked write to this
        # clause, even when the sync and the crash land on one step.
        dur_mid = state.dur
        if self._dur_state:
            sf = spec.sync_field
            dur_adv = getattr(node, sf) > getattr(node0, sf)  # [L,N]
            dur_mid = _tree_where(dur_adv, self._dur_of(node), state.dur)

        # -- 5e. nemesis disk-fault cycle (slow -> crash -> recover) --------
        # The durability clause (docs/nemesis.md r18): occurrence k opens
        # a DEGRADED window at the schedule-drawn victim (host face:
        # writes pay extra latency, fsync raises EIO; device face: a pure
        # fire/trace marker), then the disk DIES — the victim is killed
        # and, at recovery, rebuilt from its durable WATERMARK instead of
        # live state: exactly the unsynced-tail-lost middle regime that
        # crash-preserve (on_restart keeps everything) and wipe (init
        # keeps nothing) both structurally miss. All three phases of
        # occurrence k share ONE triage gate at k (like a reconfig's
        # remove/join pair), and the victim + torn bit are recomputed
        # pure draws at index k, never carried state.
        tr_dslow = jnp.full((L,), -1, jnp.int32)
        tr_dcrash = jnp.full((L,), -1, jnp.int32)
        tr_drecover = jnp.full((L,), -1, jnp.int32)
        tr_dtorn = jnp.zeros((L,), jnp.bool_)
        ap_dslow = ap_dcrash = ap_drecover = None
        drec_mask = None
        unsynced_lost = jnp.zeros((L,), jnp.int32)
        nem_disk_at = nem_disk_phase = nem_disk_k = None
        if cfg.nem_disk_enabled:
            nst = state.nem
            disk_due = active & (nst.disk_at <= t_next)
            dk = nst.disk_k
            do_dslow = disk_due & (nst.disk_phase == 0)
            do_dcrash = disk_due & (nst.disk_phase == 1)
            do_drecover = disk_due & (nst.disk_phase == 2)
            disk_en = (
                _occ_on(ctl, "disk", dk) if self.triage
                else jnp.ones((L,), jnp.bool_)
            )
            dvictim = prng.randint(
                state.key0, NEM_SITE_DISK_VICTIM, 0, N, index=dk
            )
            if cfg.nem_disk_torn_rate > 0:
                torn = (
                    prng.bits(state.key0, NEM_SITE_DISK_TORN, index=dk)
                    % jnp.uint32(COIN_DENOM)
                ) < jnp.uint32(round(cfg.nem_disk_torn_rate * COIN_DENOM))
            else:
                torn = jnp.zeros((L,), jnp.bool_)
            ap_dslow = do_dslow & disk_en
            ap_dcrash = do_dcrash & disk_en
            ap_drecover = do_drecover & disk_en
            dcrash_mask = ap_dcrash[:, None] & (node_ids == dvictim[:, None])
            drec_mask = ap_drecover[:, None] & (node_ids == dvictim[:, None])
            # the disk crash kills the victim like a crash-clause kill:
            # liveness bit down, in-flight messages to it lost
            alive = (alive & ~dcrash_mask) | drec_mask
            valid = valid & ~dcrash_mask[:, :, None]
            if self._B:
                svalid = svalid & ~(
                    ap_dcrash[:, None] & (strag.dst == dvictim[:, None])
                )
            # unsynced loss: the victim's durable fields differ from its
            # watermark at the crash instant — everything acked since the
            # last sync point is about to vanish (no durable contract =
            # the whole node state is unsynced by definition)
            if self._dur_state:
                differs = jnp.zeros((L, N), jnp.bool_)
                for f in spec.durable_fields:
                    d = (
                        getattr(dur_mid, f).astype(jnp.int32)
                        != getattr(node, f)
                    )
                    differs = differs | d.reshape(L, N, -1).any(axis=2)
                unsynced_lost = (
                    (dcrash_mask & differs).any(axis=1).astype(jnp.int32)
                )
            else:
                unsynced_lost = ap_dcrash.astype(jnp.int32)
            # RECOVERY: rebuild from what the disk durably holds — a fresh
            # init state with the durable fields replaced by the (widened)
            # watermark, optionally refined by spec.on_recover (which sees
            # the torn bit); no durable contract degenerates to a wipe.
            # The hook's returned timer is a RELATIVE delay from the
            # recovery instant (init semantics), shifted + skew-rescaled
            # exactly like a join's.
            ns_d, timer_d = self._v_init(rkeys, narange)
            timer_d = jnp.asarray(timer_d, jnp.int32)
            if self._dur_state:
                wm = self._widen_dur(dur_mid)
                ns_d = ns_d._replace(**{
                    f: getattr(wm, f) for f in spec.durable_fields
                })
            if self._v_on_recover is not None:
                ns_d, timer_d = self._v_on_recover(
                    ns_d, node_ids, t_next, torn, rkeys
                )
                timer_d = jnp.asarray(timer_d, jnp.int32)
            d_ok = (timer_d >= 0) & (timer_d < INF_GUARD)
            timer_d = jnp.where(d_ok, timer_d + t_next[:, None], timer_d)
            if cfg.nem_skew_enabled:
                dd = timer_d - t_next[:, None]
                sk_d = d_ok & (dd > 0)
                timer_d = jnp.where(
                    sk_d,
                    t_next[:, None] + scale_delay_ppm(dd, state.nem.skew_ppm),
                    timer_d,
                )
            if spec.time_fields:
                ns_d = ns_d._replace(**{
                    f: getattr(ns_d, f)
                    + t_next.reshape((L,) + (1,) * (getattr(ns_d, f).ndim - 1))
                    for f in spec.time_fields
                })
            node = _tree_where(drec_mask, ns_d, node)
            timer = jnp.where(drec_mask, timer_d, timer)
            # schedule arithmetic: next toggle = previous toggle time plus
            # an occurrence-indexed delta (never clock + delta)
            slow_d = prng.randint(
                state.key0, NEM_SITE_DISK_SLOW, cfg.nem_disk_slow_lo_us,
                cfg.nem_disk_slow_hi_us, index=dk,
            )
            down_d = prng.randint(
                state.key0, NEM_SITE_DISK_DOWN, cfg.nem_disk_down_lo_us,
                cfg.nem_disk_down_hi_us, index=dk,
            )
            next_d = prng.randint(
                state.key0, NEM_SITE_DISK_IV, cfg.nem_disk_interval_lo_us,
                cfg.nem_disk_interval_hi_us, index=dk + 1,
            )
            nem_disk_at = jnp.where(
                do_dslow, nst.disk_at + slow_d,
                jnp.where(
                    do_dcrash, nst.disk_at + down_d,
                    jnp.where(
                        do_drecover, nst.disk_at + next_d, nst.disk_at
                    ),
                ),
            )
            nem_disk_phase = jnp.where(
                do_dslow, 1,
                jnp.where(
                    do_dcrash, 2, jnp.where(do_drecover, 0, nst.disk_phase)
                ),
            )
            nem_disk_k = dk + do_drecover.astype(jnp.int32)
            tr_dslow = jnp.where(ap_dslow, dvictim, -1)
            tr_dcrash = jnp.where(ap_dcrash, dvictim, -1)
            tr_drecover = jnp.where(ap_drecover, dvictim, -1)
            tr_dtorn = (ap_dcrash | ap_drecover) & torn

        # durability watermark RESET (half 2 of 2, node now final): where
        # wipe / join / disk-recover just installed a fresh node state,
        # that state IS the new on-disk truth (a wiped or joining node
        # boots fsynced like init; a recovered node's durable fields were
        # just read FROM the disk). Reset targets are disjoint from the
        # advance targets above — an event-processing node is never also
        # restarting — so the reset simply layers on dur_mid.
        new_dur = dur_mid
        if self._dur_state:
            reset = drec_mask
            if (
                any_crash and cfg.nem_crash_enabled
                and cfg.nem_crash_wipe_rate > 0
            ):
                reset = reset | wipe_mask
            if cfg.nem_reconfig_enabled:
                reset = reset | join_mask
            new_dur = _tree_where(reset, self._dur_of(node), dur_mid)

        phase("network")
        # -- 6. collect outboxes, roll the network, pack into pool ---------
        def flat(out: Outbox, emitting, e):  # [L,N,e,...] -> [L, N*e, ...]
            v = (out.valid & emitting[:, :, None]).reshape(L, N * e)
            return (
                v,
                out.dst.reshape(L, N * e),
                out.kind.reshape(L, N * e),
                out.payload.reshape(L, N * e, P),
            )

        C = self._C
        if self._fused:
            cand_valid, cd, cand_kind, cand_pay = flat(out_e, evt, spec.max_out)
            cand_dst = jnp.clip(cd, 0, N - 1)
        else:
            E_m, E_t = spec.max_out_msg, spec.max_out
            mv, md, mk, mp = flat(out_m, has_msg, E_m)
            tv, td, tk, tp = flat(out_t, due_t, E_t)
            cand_valid = jnp.concatenate([mv, tv], axis=1)  # [L,Cb]
            cand_dst = jnp.clip(jnp.concatenate([md, td], axis=1), 0, N - 1)
            cand_kind = jnp.concatenate([mk, tk], axis=1)
            cand_pay = jnp.concatenate([mp, tp], axis=1)

        net_key = prng.fold(key, 105)[:, None]
        if self._dup:
            # nemesis duplication: interleave a coin-gated copy of every
            # candidate (position 2c+1 mirrors 2c); the copy rolls its own
            # loss/latency below, so it can arrive reordered or die alone
            bidx = jnp.arange(self._Cb, dtype=jnp.uint32)[None, :]
            if self.triage:
                # per-lane scaled rate on the SAME uniform stream
                # (bernoulli is `uniform < p`): a scaled-down lane's dup
                # set is a strict subset of the full-rate lane's
                p_dup = (
                    jnp.float32(cfg.nem_dup_rate)
                    * ctl.rate_scale[:, RATE_ROW["dup"]]
                    * _clause_on(ctl, "dup").astype(jnp.float32)
                )[:, None]
            else:
                p_dup = cfg.nem_dup_rate
            dcoin = prng.uniform(net_key, NET_SITE_DUP, index=bidx) < p_dup
            dup_fires = (cand_valid & dcoin).sum(axis=1, dtype=jnp.int32)

            def il(x):
                if x.ndim == 2:
                    return jnp.stack([x, x], axis=2).reshape(L, C)
                return jnp.stack([x, x], axis=2).reshape(L, C, P)

            cand_valid = jnp.stack(
                [cand_valid, cand_valid & dcoin], axis=2
            ).reshape(L, C)
            cand_dst, cand_kind, cand_pay = il(cand_dst), il(cand_kind), il(cand_pay)
        else:
            dup_fires = jnp.zeros((L,), jnp.int32)

        # network rolls: loss + latency (+ buggify heavy-tail coin)
        cidx = jnp.arange(C, dtype=jnp.uint32)[None, :]
        u = prng.uniform(net_key, 1, index=cidx)
        lat = prng.randint(
            net_key, 2, cfg.latency_lo_us,
            max(cfg.latency_hi_us, cfg.latency_lo_us + 1), index=cidx,
        )
        cand_dst_oh = cand_dst[:, :, None] == narange[None, None, :]  # [L,C,N]
        keep = cand_valid & (u >= cfg.loss_rate)
        # sends to currently-dead nodes are dropped (clogged-node
        # semantics) and counted in their OWN lane counter: pool-overflow
        # drops mean back-pressure, dead-node drops mean crash fallout,
        # and graceful-degradation assertions need to tell them apart
        if cfg.nem_reconfig_enabled:
            # membership filter FIRST, so the two drop classes stay
            # disjoint: a send to a REMOVED node counts here (whatever its
            # alive bit says), a send to a crashed member in dead_dropped;
            # the filter has its own scope in network
            with jax.named_scope("membership"):
                member_dst = (cand_dst_oh & member[:, None, :]).any(-1)
                nonmember_dropped = (keep & ~member_dst).sum(axis=1, dtype=jnp.int32)
                keep = keep & member_dst
        else:
            nonmember_dropped = jnp.zeros((L,), jnp.int32)
        alive_dst = (cand_dst_oh & alive[:, None, :]).any(-1)
        dead_dropped = (keep & ~alive_dst).sum(axis=1, dtype=jnp.int32)
        keep = keep & alive_dst
        if cfg.any_partition_enabled:
            # link test at send time (test_link, network.rs:261-269): the
            # candidate's source node is static per position, so the link row
            # is a constant-index gather, then matched against the dst one-hot
            src_rows = link_ok[:, self._src_of_c, :]  # [L,C,N]
            keep = keep & (cand_dst_oh & src_rows).any(-1)
        if cfg.nem_clog_enabled:
            # asymmetric clog: drop candidates whose (static source,
            # dynamic dst) match the lane's clogged directed link
            src_const = jnp.asarray(self._src_of_c, jnp.int32)  # [C]
            clog_hit = (
                clogged[:, None]
                & (src_const[None, :] == clog_src[:, None])
                & (cand_dst == clog_dst[:, None])
            )
            if self.triage:
                clog_hit = clog_hit & clog_en[:, None]
            keep = keep & ~clog_hit
        if cfg.nem_loss_rate > 0:
            # nemesis extra loss coin, rolled LAST — only on messages that
            # survived base loss, dead destinations, partitions and clogs.
            # fires_loss therefore counts the clause's own coin on traffic
            # that would otherwise have been delivered, which is what the
            # host NetSim counts too (its clog check precedes the coin);
            # the coverage report reads the same on both backends
            u2 = prng.uniform(net_key, NET_SITE_NEM_LOSS, index=cidx)
            if self.triage:
                p_loss = (
                    jnp.float32(cfg.nem_loss_rate)
                    * ctl.rate_scale[:, RATE_ROW["loss"]]
                    * _clause_on(ctl, "loss").astype(jnp.float32)
                )[:, None]
            else:
                p_loss = cfg.nem_loss_rate
            nem_lost = keep & (u2 < p_loss)
            loss_drops = nem_lost.sum(axis=1, dtype=jnp.int32)
            keep = keep & ~nem_lost
        else:
            loss_drops = jnp.zeros((L,), jnp.int32)
        if cfg.nem_reorder_rate > 0:
            # bounded reordering: an extra uniform delay in [0, window] —
            # latency only LENGTHENS, so the conservative lookahead bound
            # (latency_lo) is untouched while later sends overtake
            if self.triage:
                p_ro = (
                    jnp.float32(cfg.nem_reorder_rate)
                    * ctl.rate_scale[:, RATE_ROW["reorder"]]
                    * _clause_on(ctl, "reorder").astype(jnp.float32)
                )[:, None]
            else:
                p_ro = cfg.nem_reorder_rate
            rcoin = keep & (
                prng.uniform(net_key, NET_SITE_REORDER, index=cidx) < p_ro
            )
            extra = prng.randint(
                net_key, NET_SITE_REORDER_EXTRA, 0,
                cfg.nem_reorder_window_us + 1, index=cidx,
            )
            lat = jnp.where(rcoin, lat + extra, lat)
            reorder_fires = rcoin.sum(axis=1, dtype=jnp.int32)
        else:
            reorder_fires = jnp.zeros((L,), jnp.int32)
        if cfg.nem_spike_enabled:
            spike_open = spiking & spike_en if self.triage else spiking
            lat = jnp.where(
                spike_open[:, None], lat + jnp.int32(cfg.nem_spike_extra_us),
                lat,
            )
        if self._B:
            # the rand_delay buggify tail (net/mod.rs:287-295): a surviving
            # message occasionally takes seconds instead of milliseconds
            bug = keep & prng.bernoulli(net_key, 3, cfg.buggify_delay_rate,
                                        index=cidx)
            tail = prng.randint(
                net_key, 4, cfg.buggify_delay_lo_us,
                max(cfg.buggify_delay_hi_us, cfg.buggify_delay_lo_us + 1),
                index=cidx,
            )
            lat = jnp.where(bug, tail, lat)
        else:
            bug = jnp.zeros((L, C), jnp.bool_)
        # stamp each send from its EMITTING node's event time (candidate
        # positions map statically to their source node), so latency is
        # measured from the send instant, not the lane's window maximum
        deliver_at = t_evt[:, self._src_of_c] + lat.astype(jnp.int32)  # [L,C]

        send = keep & ~bug  # [L,C] candidate sends this step
        if self._fused:
            # NODE-POOLED pack (fused specs): the i-th valid send of node n
            # takes the i-th free slot of n's SK-slot pool — rank matching,
            # fully parallel (no sequential first-free over rows), and
            # bursts that cluster on one outbox row borrow slack from quiet
            # rows. A send ranks past the free count => DROPPED (counted):
            # overwriting a pending slot would corrupt a message in flight.
            E, SK = self._E_pack, self._SK  # E doubles under duplication
            send_n = send.reshape(L, N, E)
            free = (~valid.any(1)).reshape(L, N, SK)  # [L,Nsrc,SK]

            def prefix_counts(m):
                # exclusive prefix count, UNROLLED on purpose: cumsum is a
                # scan op that breaks XLA's elementwise fusion in this
                # context (measured for the first-free masks, see
                # docs/perf_notes.md "dtypes and ops"); the trailing dims
                # here are tiny statics (E, SK)
                out = []
                acc = jnp.zeros(m.shape[:-1], jnp.int32)
                for k in range(m.shape[-1]):
                    out.append(acc)
                    acc = acc + m[..., k].astype(jnp.int32)
                return jnp.stack(out, -1), acc

            r_send, _ = prefix_counts(send_n)  # [L,N,E]
            r_free, n_free = prefix_counts(free)  # [L,N,SK], [L,N]
            place = (
                send_n[:, :, :, None]
                & free[:, :, None, :]
                & (r_send[:, :, :, None] == r_free[:, :, None, :])
            )  # [L,N,E,SK]
            ring_w = place.any(2).reshape(L, CK)
            overflow = state.overflow + (
                send_n & (r_send >= n_free[:, :, None])
            ).sum(axis=(1, 2), dtype=jnp.int32)
            place_i = place.astype(jnp.int32)

            def put(ring_vals, cand_vals):
                # the one-hot multiply runs in i32 (u8 products could wrap);
                # the result narrows back to the ring's at-rest dtype
                cv = cand_vals.astype(jnp.int32).reshape(
                    (L, N, E) + cand_vals.shape[2:]
                )
                if cand_vals.ndim == 2:
                    inc = (place_i * cv[:, :, :, None]).sum(2)
                    return jnp.where(
                        ring_w,
                        inc.reshape(L, CK).astype(ring_vals.dtype),
                        ring_vals,
                    )
                inc = (place_i[:, :, :, :, None] * cv[:, :, :, None, :]).sum(2)
                return jnp.where(
                    ring_w[:, :, None],
                    inc.reshape(L, CK, P).astype(ring_vals.dtype),
                    ring_vals,
                )

            # validity bits: dst d references slot s iff the send that
            # took s targets d
            dsts = cand_dst_oh.reshape(L, N, E, N)
            written = (
                place[:, :, :, :, None] & dsts[:, :, :, None, :]
            ).any(2).transpose(0, 3, 1, 2).reshape(L, N, CK)
        else:
            # per-candidate rings: candidate c's message takes the FIRST of
            # its K ring slots that no destination still references; if all
            # K are pending the send is DROPPED (counted). Everything is
            # elementwise on [L,c,K] / [L,N,c,K] masks, per depth segment
            # (see SimConfig).
            dst_major = cand_dst_oh.transpose(0, 2, 1)  # [L,N,C]
            ring_w_parts = []  # [L, nc*K] ring-slot write masks
            place_parts = []  # [L, N, nc*K] validity-bit writes
            ovf = jnp.zeros((L,), jnp.int32)
            for c0, c1, K, s0, s1 in self._segs:
                nc = c1 - c0
                send_seg = send[:, c0:c1]  # [L,nc]
                free = ~valid[:, :, s0:s1].reshape(L, N, nc, K).any(1)
                ring_w = send_seg[:, :, None] & _first_free(free, K)
                placed = ring_w.any(2)  # [L,nc]
                ovf = ovf + (send_seg & ~placed).sum(axis=1, dtype=jnp.int32)
                ring_w_parts.append(ring_w.reshape(L, nc * K))
                place_parts.append(
                    (dst_major[:, :, c0:c1, None] & ring_w[:, None]).reshape(
                        L, N, nc * K
                    )
                )
            ring_w = (
                ring_w_parts[0] if len(ring_w_parts) == 1
                else jnp.concatenate(ring_w_parts, axis=1)
            )  # [L,CK]
            written = (
                place_parts[0] if len(place_parts) == 1
                else jnp.concatenate(place_parts, axis=2)
            )  # [L,N,CK]
            overflow = state.overflow + ovf

            def ring_expand(cand_vals):  # [L,C(,P)] -> [L,CK(,P)] per segment
                outs = []
                for c0, c1, K, s0, s1 in self._segs:
                    nc = c1 - c0
                    seg = cand_vals[:, c0:c1]
                    if cand_vals.ndim == 2:
                        outs.append(
                            jnp.broadcast_to(
                                seg[:, :, None], (L, nc, K)
                            ).reshape(L, nc * K)
                        )
                    else:
                        outs.append(
                            jnp.broadcast_to(
                                seg[:, :, None, :], (L, nc, K, P)
                            ).reshape(L, nc * K, P)
                        )
                return (
                    outs[0] if len(outs) == 1
                    else jnp.concatenate(outs, axis=1)
                )

            def put(ring_vals, cand_vals):
                inc = ring_expand(cand_vals)
                if cand_vals.ndim == 2:
                    return jnp.where(ring_w, inc, ring_vals)
                return jnp.where(ring_w[:, :, None], inc, ring_vals)

        new_valid = valid | written
        # slots no destination references anymore reset their deliver
        # offset to INF_US: a stale offset would be rebased epoch after
        # epoch (rb() below) and eventually wrap int32 — benign for current
        # readers (validity-gated) but a trap, and it makes long-soak state
        # non-canonical (ADVICE r4)
        new_deliver = put(
            jnp.where(valid.any(1), msgs.deliver, INF_US), deliver_at
        )
        new_kind = put(msgs.kind, cand_kind.astype(self._kind_dtype))
        new_payload = put(msgs.payload, cand_pay)
        if lin is not None:
            # lineage stamp: a send carries its emitting EVENT's id — the
            # candidate's source node is static per position, so this is a
            # constant-index gather; duplicates share their original's
            # send event (one cause, two deliveries). Freed slots reset to
            # 0 like deliver resets to INF_US (canonical at-rest state).
            cand_seid16 = (
                evt_eid_full[:, self._src_of_c] & jnp.uint32(0xFFFF)
            ).astype(jnp.uint16)  # [L,C]
            new_sent_eid = put(
                jnp.where(valid.any(1), msgs.sent_eid, jnp.uint16(0)),
                cand_seid16,
            )
        else:
            cand_seid16 = None
            new_sent_eid = None

        # straggler pack: region c owns K4 slots of the side pool
        if self._B:
            K4, B = self._K4, self._B
            sb = keep & bug  # [L,C]
            sfree = ~svalid.reshape(L, C, K4)
            splace = sb[:, :, None] & _first_free(sfree, K4)  # [L,C,K4]
            swritten = splace.reshape(L, B)
            overflow = overflow + (sb & ~splace.any(2)).sum(axis=1, dtype=jnp.int32)

            def sput(pool_vals, cand_vals):
                if cand_vals.ndim == 2:
                    inc = jnp.broadcast_to(
                        cand_vals[:, :, None], (L, C, K4)
                    ).reshape(L, B)
                    return jnp.where(swritten, inc, pool_vals)
                inc = jnp.broadcast_to(
                    cand_vals[:, :, None, :], (L, C, K4, P)
                ).reshape(L, B, P)
                return jnp.where(swritten[:, :, None], inc, pool_vals)

            new_strag = StragPool(
                valid=svalid | swritten,
                deliver=sput(jnp.where(svalid, strag.deliver, INF_US), deliver_at),
                dst=sput(strag.dst, cand_dst.astype(jnp.uint8)),
                kind=sput(strag.kind, cand_kind.astype(self._kind_dtype)),
                payload=sput(strag.payload, cand_pay),
                sent_eid=(
                    None if lin is None
                    else sput(
                        jnp.where(svalid, strag.sent_eid, jnp.uint16(0)),
                        cand_seid16,
                    )
                ),
            )
        else:
            new_strag = None

        phase("chaos")
        # -- 6b. chaos fire counts (the coverage report's raw data) --------
        # every enabled clause must show nonzero fires over a seed batch;
        # an enabled clause with zero fires is dead chaos (nemesis.py)
        zl = jnp.zeros((L,), jnp.int32)
        cols = [zl] * len(FIRE_KINDS)

        def _count(kind, arr):
            cols[FIRE_INDEX[kind]] = cols[FIRE_INDEX[kind]] + (
                arr.astype(jnp.int32) if arr.dtype == jnp.bool_ else arr
            )

        if any_crash:
            _count("crash", ap_crash)
            _count("restart", ap_restart)
            if cfg.nem_crash_enabled and cfg.nem_crash_wipe_rate > 0:
                ap_wipe = ap_crash & wipe_coin
                if self.triage:
                    ap_wipe = ap_wipe & _clause_on(ctl, "wipe")
                _count("wipe", ap_wipe)
        if cfg.any_partition_enabled:
            _count("partition", ap_split)
            _count("heal", ap_heal)
        if cfg.nem_clog_enabled:
            _count("clog", do_clog & clog_en)
        if cfg.nem_spike_enabled:
            _count("spike", do_spike & spike_en)
        if cfg.nem_reconfig_enabled:
            _count("remove", ap_remove)
            _count("join", ap_join)
        if cfg.nem_disk_enabled:
            _count("disk_slow", ap_dslow)
            _count("disk_crash", ap_dcrash)
            _count("disk_recover", ap_drecover)
        _count("loss", loss_drops)
        _count("dup", dup_fires)
        _count("reorder", reorder_fires)
        fires = state.fires + jnp.stack(cols, axis=1)

        # clause x occurrence fire bitmasks (the occurrence dimension of the
        # chaos report and of the explorer's novelty signal). A window's bit
        # is set when its OPEN half applies; suppressed (triage) occurrences
        # stay unset, so a shrunk lane's occ_fired is the survivors only.
        occ_fired = state.occ_fired
        if occ_fired is not None:
            ocols = [occ_fired[:, i] for i in range(len(OCC_CLAUSES))]

            def _occ_mark(row, fired, k):
                bit = jnp.uint32(1) << jnp.clip(k, 0, 31).astype(jnp.uint32)
                ocols[row] = jnp.where(fired, ocols[row] | bit, ocols[row])

            if cfg.nem_crash_enabled:
                _occ_mark(OCC_ROW["crash"], ap_crash, state.nem.crash_k)
            if cfg.nem_partition_enabled:
                _occ_mark(OCC_ROW["partition"], ap_split, state.nem.part_k)
            if cfg.nem_clog_enabled:
                _occ_mark(OCC_ROW["clog"], do_clog & clog_en, state.nem.clog_k)
            if cfg.nem_spike_enabled:
                _occ_mark(
                    OCC_ROW["spike"], do_spike & spike_en, state.nem.spike_k
                )
            if cfg.nem_reconfig_enabled:
                # the OPEN half marks the occurrence, like every clause
                # (k is shared by the remove and its paired join)
                _occ_mark(
                    OCC_ROW["reconfig"], ap_remove, state.nem.reconfig_k
                )
            if cfg.nem_disk_enabled:
                # the OPEN half (disk_slow) marks the occurrence; k is
                # shared by all three phases of the cycle
                _occ_mark(OCC_ROW["disk"], ap_dslow, state.nem.disk_k)
            occ_fired = jnp.stack(ocols, axis=1)

        phase("invariants")
        # -- 7. invariants + lane lifecycle --------------------------------
        ok = self._v_check(node, alive, clock)
        new_violation = active & ~ok & ~state.violated
        violated = state.violated | new_violation
        violation_at = jnp.where(new_violation, clock, state.violation_at)
        violation_epoch = jnp.where(new_violation, state.epoch,
                                    state.violation_epoch)
        # first violating step index: state.steps is the count of completed
        # active steps BEFORE this one, i.e. this step's 0-based index —
        # run(max_steps=violation_step + 1) re-reaches the violation
        violation_step = jnp.where(
            new_violation, state.steps, state.violation_step
        )
        # horizon in (epoch, offset) space: horizon_us may exceed int32
        if self.triage:
            # per-lane horizon: the shrinker's time-truncation axis
            reached_horizon = (state.epoch > ctl.h_epoch) | (
                (state.epoch == ctl.h_epoch) & (clock >= ctl.h_off)
            )
        else:
            eh, oh = divmod(int(cfg.horizon_us), REBASE_US)
            reached_horizon = (state.epoch > eh) | (
                (state.epoch == eh) & (clock >= oh)
            )
        done = state.done | deadlocked | reached_horizon | violated

        phase("finish")
        # -- 7b. coverage accumulation (BatchedSim(coverage=True) only) ----
        # One bit per exercised event class: hash(dst node, src, msg kind,
        # payload[0] magnitude bucket) for deliveries, hash(node, -1, -1, 0)
        # for timer fires — all trace-visible fields, so explore.py's pure
        # mirror recomputes the exact bitmap from a TraceRecord stream.
        # Computed BEFORE the epoch rebase: the transition compare below
        # must not see time_fields shifts as state changes.
        cov: Optional[Coverage] = state.cov
        if cov is not None:
            evt_cov = has_msg | due_t  # [L,N] (active-gated via the picks)
            src_w = jnp.where(has_msg, m_src, jnp.int32(-1))
            kind_w = jnp.where(has_msg, m_kind, jnp.int32(-1))
            p0 = jnp.where(has_msg, m_pay[:, :, 0], 0).astype(jnp.uint32)
            # magnitude bucket = bit_length(payload[0] as u32): state-bearing
            # payload words (terms, indices) contribute ~log2 buckets, not a
            # fresh bit per value — AFL-style bucketing so high-cardinality
            # counters can't drown structural novelty
            bucket = jnp.where(
                has_msg, jnp.int32(32) - jax.lax.clz(p0).astype(jnp.int32), 0
            )
            ck = prng.fold(jnp.uint32(COV_SALT), node_ids)  # [L,N]
            ck = prng.fold(ck, src_w)
            ck = prng.fold(ck, kind_w)
            ck = prng.fold(ck, bucket)
            idx = prng.mix(ck) % jnp.uint32(COV_BITS)  # [L,N]
            word = (idx // 32).astype(jnp.int32)
            wbit = jnp.uint32(1) << (idx % 32)
            bm = cov.bitmap
            warange = jnp.arange(COV_WORDS, dtype=jnp.int32)[None, :]
            for ni in range(N):  # N is small + static: unrolled OR-scatter
                sel = evt_cov[:, ni : ni + 1] & (
                    warange == word[:, ni : ni + 1]
                )
                bm = bm | jnp.where(sel, wbit[:, ni : ni + 1], jnp.uint32(0))
            # scalar features: pool-occupancy high water + state-changing
            # event count (protocol progress vs idle traffic)
            occupancy = new_valid.any(axis=1).sum(axis=1, dtype=jnp.int32)
            if self._B:
                occupancy = occupancy + new_strag.valid.sum(
                    axis=1, dtype=jnp.int32
                )
            changed = jnp.zeros((L, N), jnp.bool_)
            for old_leaf, new_leaf in zip(
                jax.tree_util.tree_leaves(node0),
                jax.tree_util.tree_leaves(node),
            ):
                changed = changed | (old_leaf != new_leaf).reshape(
                    L, N, -1
                ).any(axis=2)
            cov = Coverage(
                bitmap=bm,
                hiwater=jnp.maximum(cov.hiwater, occupancy),
                transitions=cov.transitions
                + (evt_cov & changed).sum(axis=1, dtype=jnp.int32),
            )

        # -- 8. epoch rebase: unbounded virtual time, int32 arithmetic -----
        # (see spec.REBASE_US). Done lanes freeze as-is; sentinel values
        # (INF_US timers / disabled chaos) are never rebased.
        do_shift = (~done) & (clock >= REBASE_US)
        shift = jnp.where(do_shift, jnp.int32(REBASE_US), 0)  # [L]

        def rb(x, s):  # rebase a live-offset tensor, guarding sentinels
            s = s.reshape(s.shape + (1,) * (x.ndim - 1))
            return jnp.where(x < INF_GUARD, x - s, x)

        clock = clock - shift
        epoch = state.epoch + do_shift.astype(jnp.int32)
        timer = rb(timer, shift)
        chaos_at = rb(chaos_at, shift)
        part_at = rb(part_at, shift)
        new_deliver = rb(new_deliver, shift)
        if state.nem is not None:
            nst = state.nem
            new_nem = NemesisState(
                crash_k=nem_crash_k if nem_crash_k is not None else nst.crash_k,
                wipe=nem_wipe if nem_wipe is not None else nst.wipe,
                part_k=nem_part_k if nem_part_k is not None else nst.part_k,
                clog_at=rb(
                    nem_clog_at if nem_clog_at is not None else nst.clog_at,
                    shift,
                ),
                clogged=clogged if clogged is not None else nst.clogged,
                clog_src=clog_src if clog_src is not None else nst.clog_src,
                clog_dst=clog_dst if clog_dst is not None else nst.clog_dst,
                clog_k=nem_clog_k if nem_clog_k is not None else nst.clog_k,
                spike_at=rb(
                    nem_spike_at if nem_spike_at is not None else nst.spike_at,
                    shift,
                ),
                spiking=spiking if spiking is not None else nst.spiking,
                spike_k=nem_spike_k if nem_spike_k is not None else nst.spike_k,
                reconfig_at=rb(
                    nem_reconfig_at if nem_reconfig_at is not None
                    else nst.reconfig_at,
                    shift,
                ),
                reconf_node=(
                    nem_reconf_node if nem_reconf_node is not None
                    else nst.reconf_node
                ),
                reconfig_k=(
                    nem_reconfig_k if nem_reconfig_k is not None
                    else nst.reconfig_k
                ),
                disk_at=rb(
                    nem_disk_at if nem_disk_at is not None else nst.disk_at,
                    shift,
                ),
                disk_phase=(
                    nem_disk_phase if nem_disk_phase is not None
                    else nst.disk_phase
                ),
                disk_k=nem_disk_k if nem_disk_k is not None else nst.disk_k,
                skew_ppm=nst.skew_ppm,
            )
        else:
            new_nem = None
        if self._B:
            new_strag = new_strag._replace(
                deliver=rb(new_strag.deliver, shift)
            )
        if spec.time_fields:
            node = node._replace(**{
                f: getattr(node, f)
                - shift.reshape((L,) + (1,) * (getattr(node, f).ndim - 1))
                for f in spec.time_fields
            })

        new_state = SimState(
            clock=clock,
            epoch=epoch,
            key=key,
            key0=state.key0,
            done=done,
            violated=violated,
            violation_at=violation_at,
            violation_epoch=violation_epoch,
            violation_step=violation_step,
            deadlocked=state.deadlocked | deadlocked,
            steps=state.steps + active.astype(jnp.int32),
            events=state.events
            + has_msg.sum(axis=1, dtype=jnp.int32)
            + due_t.sum(axis=1, dtype=jnp.int32),
            overflow=overflow,
            dead_drops=state.dead_drops + dead_dropped,
            nonmember_drops=state.nonmember_drops + nonmember_dropped,
            unsynced_loss=state.unsynced_loss + unsynced_lost,
            fires=fires,
            occ_fired=occ_fired,
            alive_p=bitpack.pack_bits(alive),
            crashed=crashed,
            chaos_at=chaos_at,
            member_p=(
                bitpack.pack_bits(member) if member is not None
                else state.member_p
            ),
            member_epoch=member_epoch,
            link_ok_p=bitpack.pack_bits(link_ok),
            partitioned=partitioned,
            part_at=part_at,
            timer=timer,
            node=self._narrow_node(node),
            dur=new_dur,
            msgs=MsgPool(
                valid_p=bitpack.pack_bits(new_valid),
                deliver=new_deliver,
                kind=new_kind,
                payload=new_payload,
                sent_eid=new_sent_eid,
            ),
            strag=new_strag,
            nem=new_nem,
            ctl=state.ctl,
            cov=cov,
            lin=(
                None if lin is None
                else Lineage(lam=new_lam, eid=new_lin_eid)
            ),
            queue=state.queue,
            refill=state.refill,
            loop=state.loop,
        )
        # -- 9. continuous batching: retire finished lanes, admit the next
        # queued seed/genome in-jit (docs/continuous_batching.md). A no-op
        # branch (lax.cond) on steps where no lane retires, so plain sweep
        # steps pay one lane-axis any() and nothing else.
        if state.refill is not None:
            new_state = self._refill_apply(state, new_state, active)
        # -- 10. device-resident search (r19, docs/explore.md): when the
        # whole generation has retired, fold its coverage into the corpus
        # ring, mutate the next population from the meta-rng chain, and
        # rewrite the admission queue — all under a lax.cond that stays a
        # no-op until the LAST admission of a generation retires.
        if state.loop is not None:
            new_state = self._devloop_apply(new_state)
        record = TraceRecord(
            clock=clock,
            epoch=epoch,
            # report event times in the post-rebase basis, consistent with
            # the record's epoch (extract_trace adds epoch * REBASE_US)
            t_evt=t_evt - shift[:, None],
            msg_fired=has_msg,
            msg_src=m_src,
            msg_kind=m_kind,
            msg_payload=m_pay,
            timer_fired=due_t,
            crash=tr_crash,
            restart=tr_restart,
            split=tr_split,
            heal=tr_heal,
            side_mask=tr_side,
            violation=new_violation,
            deadlock=deadlocked,
            clog_src=tr_clog_src,
            clog_dst=tr_clog_dst,
            unclog=tr_unclog,
            spike_on=tr_spike_on,
            spike_off=tr_spike_off,
            remove=tr_remove,
            join=tr_join,
            disk_slow=tr_dslow,
            disk_crash=tr_dcrash,
            disk_recover=tr_drecover,
            disk_torn=tr_dtorn,
            lam=tr_lam,
            evt_eid=tr_evt_eid,
            sent_eid=tr_sent_eid,
        )
        return new_state, record

    # ------------------------------------------------- continuous batching

    def _refill_apply(
        self, state: SimState, ns: SimState, active: jnp.ndarray
    ) -> SimState:
        """Retire lanes that finished THIS step and admit queued work.

        Runs at the end of every refill-mode step: (1) occupancy counters
        tick unconditionally; (2) under `lax.cond` (taken only on steps
        where some lane retired — each admission retires exactly once, so
        this branch runs at most A times per sweep): harvest the retiring
        lanes' cold accumulators into the per-admission result buffers
        (masked scatter at their admission index, drop-moded), then admit
        the next queue rows — retiring lanes take queue slots in LANE
        ORDER (the exclusive prefix count over the retire mask), re-init
        from the admitted seed (and ctl genome, in triage mode), and the
        cursor advances by the number admitted.

        DETERMINISM: the admitted-seed assignment is the ONLY cross-lane
        coupling in the engine, and it never touches a surviving lane's
        draws — a refilled lane's state is exactly `_init(seed)`'s row,
        so every admission's trajectory is the pure per-seed function the
        chunked path computes, and results are a pure function of
        (admission order, seeds): bit-identical to the chunked sweep for
        any fixed admission order. The lane-axis cumsum/any/sum here are
        the engine's one sanctioned exception to the lane-independence
        rule (see analysis REFILL_LANE_ALLOW)."""
        rf: RefillLog = state.refill
        q: RefillQueue = state.queue
        L = ns.done.shape[0]
        A = q.seeds.shape[0]
        rf = rf._replace(
            iters=rf.iters + jnp.int32(1),
            busy=rf.busy + active.astype(jnp.int32),
        )
        # per-admission step budget: an admission at step_cap retires
        # truncated — the exact state a chunked lane holds when its
        # run(max_steps=cap) loop ends (steps counts active steps, and a
        # live lane is active every iteration, so the cut lands on the
        # same step)
        expired = ~ns.done & (ns.steps >= rf.step_cap)
        ns = ns._replace(done=ns.done | expired)
        just = ns.done & ~state.done  # lanes whose admission retired now

        def retire_and_admit(ns: SimState, rf: RefillLog) -> SimState:
            # -- harvest: one masked scatter per result buffer. idx = A
            # for non-retiring lanes — out of bounds, dropped by mode.
            idx = jnp.where(just, rf.admitted, jnp.int32(A))

            def put(dst, src):
                return dst.at[idx].set(src, mode="drop")

            rf2 = rf._replace(
                retired=put(
                    rf.retired,
                    jnp.broadcast_to(rf.iters - 1, (L,)),
                ),
                violated=put(rf.violated, ns.violated),
                deadlocked=put(rf.deadlocked, ns.deadlocked),
                violation_at=put(rf.violation_at, ns.violation_at),
                violation_epoch=put(rf.violation_epoch, ns.violation_epoch),
                violation_step=put(rf.violation_step, ns.violation_step),
                steps=put(rf.steps, ns.steps),
                events=put(rf.events, ns.events),
                overflow=put(rf.overflow, ns.overflow),
                dead_drops=put(rf.dead_drops, ns.dead_drops),
                nonmember_drops=put(
                    rf.nonmember_drops, ns.nonmember_drops
                ),
                unsynced_loss=put(rf.unsynced_loss, ns.unsynced_loss),
                clock=put(rf.clock, ns.clock),
                epoch=put(rf.epoch, ns.epoch),
                fires=put(rf.fires, ns.fires),
                occ_fired=(
                    None if rf.occ_fired is None
                    else put(rf.occ_fired, ns.occ_fired)
                ),
                cov_bitmap=(
                    None if rf.cov_bitmap is None
                    else put(rf.cov_bitmap, ns.cov.bitmap)
                ),
                cov_hiwater=(
                    None if rf.cov_hiwater is None
                    else put(rf.cov_hiwater, ns.cov.hiwater)
                ),
                cov_transitions=(
                    None if rf.cov_transitions is None
                    else put(rf.cov_transitions, ns.cov.transitions)
                ),
            )

            # -- admit: retiring lane r takes queue row cursor + rank(r),
            # rank = exclusive prefix count over the retire mask in lane
            # order (admission order is therefore deterministic given the
            # retirement schedule, which is itself a pure function of the
            # admitted seeds)
            ji = just.astype(jnp.int32)
            rank = jnp.cumsum(ji) - ji
            adm = rf.cursor + rank
            take = just & (adm < A)
            n_take = jnp.sum(take.astype(jnp.int32))
            adm_c = jnp.clip(adm, 0, A - 1)  # provably in-bounds gathers
            seeds_new = jnp.take(q.seeds, adm_c, axis=0)
            ctl_new = None
            if self.triage:
                ctl_new = TriageCtl(
                    off=jnp.take(q.off, adm_c, axis=0),
                    occ=jnp.take(q.occ, adm_c, axis=0),
                    rate_scale=jnp.take(q.rate_scale, adm_c, axis=0),
                    h_epoch=jnp.take(q.h_epoch, adm_c, axis=0),
                    h_off=jnp.take(q.h_off, adm_c, axis=0),
                )
            # full-width re-init (the REAL _init: same draws, same
            # schedule roots as a fresh chunked lane), then a lane-masked
            # select: non-refilled lanes keep their post-step state
            # bit-for-bit — the schedule-purity half of the contract
            fresh = self._init(seeds_new, ctl_new)
            # strip the non-lane planes before the masked merge (loop too:
            # _init builds loop=None, and the devloop carry is per-window,
            # not per-lane — reattached below untouched)
            base = ns._replace(queue=None, refill=None, loop=None)
            fresh = fresh._replace(queue=None, refill=None, loop=None)

            def sel(f, b):
                m = take.reshape(take.shape + (1,) * (f.ndim - 1))
                return jnp.where(m, f, b)

            merged = jax.tree_util.tree_map(sel, fresh, base)
            rf2 = rf2._replace(
                cursor=rf.cursor + n_take,
                admitted=jnp.where(take, adm, rf.admitted),
            )
            return merged._replace(queue=q, refill=rf2, loop=ns.loop)

        def tick_only(ns: SimState, rf: RefillLog) -> SimState:
            return ns._replace(refill=rf)

        return jax.lax.cond(jnp.any(just), retire_and_admit, tick_only,
                            ns, rf)

    # ------------------------------------------- device-resident search

    def _devloop_apply(self, ns: SimState) -> SimState:
        """Fire the generation boundary once the live generation has
        fully retired (r19, docs/explore.md).

        Runs at the end of every device-loop step, AFTER `_refill_apply`
        (so the final retirements of a generation are already harvested
        into the RefillLog result buffers). The `lax.cond` is a no-op on
        every other step: the predicate — queue drained AND every lane
        done AND the window unfinished — holds exactly once per
        generation, on the step its last admission retires, and the
        boundary both folds the finished generation and (if the window
        has generations left) respawns all lanes on the next population,
        so the sweep never spends an idle step between generations."""
        dl: DevLoop = ns.loop
        rf: RefillLog = ns.refill
        A = int(ns.queue.seeds.shape[0])
        fire = (
            jnp.all(ns.done)
            & (rf.cursor >= jnp.int32(A))
            & (dl.gens_done < dl.target_gens)
        )
        return jax.lax.cond(
            fire, self._devloop_boundary, lambda s: s, ns
        )

    def _devloop_boundary(self, ns: SimState) -> SimState:
        """One in-jit generation boundary: archive -> fold -> mutate ->
        respawn. The traced mirror of what `Explorer` does on the host
        between dispatches, drawing the SAME murmur3 counter chain at
        META_SITE_DRAW so the two faces are draw-for-draw identical
        (explore._run_device_window replays the host face per window and
        asserts exactly that).

          1. ARCHIVE: the finished generation's genomes + per-admission
             results land in the DevLoop arch_* row `gens_done` (the one
             host decode per window reads these).
          2. FOLD (admission order — the order `_fold_part` replays):
             novelty = popcount(bitmap & ~union); a novel admission ORs
             its bitmap into the union and stable-inserts into the
             corpus ring at position = #{rows with bits >= new_bits},
             which keeps the ring equal to the host's
             sorted-by-(-new_bits, dispatch) top-K exactly (ties keep
             admission order; a displaced row has >= K permanent
             dominators, so it can never re-enter on either face).
          3. MUTATE/RESPAWN (only when the window has generations left):
             build the next population with the host `_population`'s
             exact draw schedule — fresh block (no draws), mutants
             (parent choice + `_mutate`'s op draws, genome-hash dedup
             against the seen table with single fresh fallback), swarm
             groups (one coin per togglable clause per group) — then
             encode it into the admission queue and re-`_init` every
             lane on the head rows.

        The seen-table append discipline matches the host claim order
        (mutants at choice time, fresh/swarm at population end; exactly
        one append per candidate), so `seen_n` tracks `len(_seen)` and
        membership — an EXACT masked compare over the valid prefix, not
        a probabilistic filter — diverges from the host only on a 64-bit
        hash collision, which by construction both faces resolve the
        same way."""
        from . import nemesis as tpun

        plan: DevLoopPlan = self.devloop
        dl: DevLoop = ns.loop
        rf: RefillLog = ns.refill
        q: RefillQueue = ns.queue
        L = int(ns.done.shape[0])
        A, K, S = plan.pop, plan.top_k, plan.seen_cap
        G = int(dl.arch_seed.shape[0])
        n_occ = len(OCC_CLAUSES)
        n_rate = len(RATE_CLAUSES)
        meta_key = dl.meta_key

        # -- 1. archive the finished generation at row gens_done (clipped
        # so the dynamic row index is provably in-bounds)
        g = jnp.clip(dl.gens_done, 0, G - 1)

        def arch(dst, src):
            return jax.lax.dynamic_update_slice(
                dst, src[None].astype(dst.dtype),
                (g,) + (jnp.int32(0),) * src.ndim,
            )

        # -- 2. fold admissions into union + ring, in admission order
        kidx = jnp.arange(K, dtype=jnp.int32)

        def fold_body(i, carry):
            union, rb, rs, ro, rocc, rrate, rh, rn, acc = carry
            bm = rf.cov_bitmap[i]
            new = bm & ~union
            nb = jnp.sum(jax.lax.population_count(new).astype(jnp.int32))
            accept = nb > 0
            union2 = jnp.where(accept, union | bm, union)
            # stable top-K insert: after every row with bits >= nb
            pos = jnp.sum((rb >= nb).astype(jnp.int32))
            do = accept & (pos < K)

            def ins(dst, val):
                shifted = jnp.roll(dst, 1, axis=0)
                m = kidx.reshape((K,) + (1,) * (dst.ndim - 1))
                out = jnp.where(
                    m < pos, dst, jnp.where(m == pos, val, shifted)
                )
                return jnp.where(do, out, dst)

            return (
                union2,
                ins(rb, nb),
                ins(rs, q.seeds[i]),
                ins(ro, q.off[i]),
                ins(rocc, q.occ[i]),
                ins(rrate, q.rate_scale[i]),
                ins(rh, dl.gen_h_raw[i]),
                jnp.where(do, jnp.minimum(rn + 1, K), rn),
                acc + accept.astype(jnp.int32),
            )

        (union, ring_bits, ring_seed, ring_off, ring_occ, ring_rate,
         ring_h, ring_n, accepts) = jax.lax.fori_loop(
            0, A, fold_body,
            (dl.union, dl.ring_bits, dl.ring_seed, dl.ring_off,
             dl.ring_occ, dl.ring_rate, dl.ring_h, dl.ring_n,
             dl.accepts),
        )
        gens_done = dl.gens_done + jnp.int32(1)
        folded_loop = dl._replace(
            gens_done=gens_done, accepts=accepts, union=union,
            ring_n=ring_n, ring_bits=ring_bits, ring_seed=ring_seed,
            ring_off=ring_off, ring_occ=ring_occ, ring_rate=ring_rate,
            ring_h=ring_h,
            arch_seed=arch(dl.arch_seed, q.seeds),
            arch_off=arch(dl.arch_off, q.off),
            arch_occ=arch(dl.arch_occ, q.occ),
            arch_rate=arch(dl.arch_rate, q.rate_scale),
            arch_h=arch(dl.arch_h, dl.gen_h_raw),
            arch_origin=arch(dl.arch_origin, dl.gen_origin),
            arch_violated=arch(dl.arch_violated, rf.violated),
            arch_bitmap=arch(dl.arch_bitmap, rf.cov_bitmap),
            arch_hiwater=arch(dl.arch_hiwater, rf.cov_hiwater),
            arch_transitions=arch(
                dl.arch_transitions, rf.cov_transitions
            ),
        )

        # -- 3. next population (only when the window continues)
        stride = jnp.uint32(plan.fresh_stride)
        sarange = jnp.arange(S, dtype=jnp.int32)
        op_code = {"occ": 0, "clause": 1, "rate": 2, "horizon": 3}
        menu = jnp.asarray([op_code[o] for o in plan.ops], jnp.int32)
        # meta draws consumed per op (parent choice + op choice + the
        # op's own draws — Explorer._mutate's exact schedule)
        adv_of = jnp.asarray([4, 3, 4, 3], jnp.int32)
        n_sched = max(1, len(plan.sched_rows))
        n_tog = max(1, len(plan.tog_bits))
        n_rateops = max(1, len(plan.rate_rows))
        sched_rows = jnp.asarray(plan.sched_rows or (0,), jnp.int32)
        tog_bits = jnp.asarray(plan.tog_bits or (0,), jnp.int32)
        rate_rows = jnp.asarray(plan.rate_rows or (0,), jnp.int32)
        scale_menu = jnp.asarray([0.25, 0.5, 1.0], jnp.float32)
        full_h = jnp.int32(plan.full_h)
        occ_cols = jnp.arange(n_occ, dtype=jnp.int32)
        rate_cols = jnp.arange(n_rate, dtype=jnp.int32)

        def fresh_hash(seed):
            return tpun.genome_hash64(
                seed, jnp.int32(0), jnp.zeros((n_occ,), jnp.int32),
                jnp.ones((n_rate,), jnp.float32), jnp.int32(0),
            )

        def build_mixed(c0, nf0, sh1, sh2, sn):
            nF, nM, nS_ = plan.n_fresh, plan.n_mut, plan.n_swarm
            seeds = jnp.zeros((A,), jnp.uint32)
            offs = jnp.zeros((A,), jnp.int32)
            occs = jnp.zeros((A, n_occ), jnp.int32)
            rates = jnp.ones((A, n_rate), jnp.float32)
            hs = jnp.zeros((A,), jnp.int32)
            origins = jnp.zeros((A,), jnp.int32)
            # fresh block: sequential seeds, NO meta draws
            if nF:
                seeds = seeds.at[:nF].set(
                    nf0 + stride * jnp.arange(nF, dtype=jnp.uint32)
                )
            nf = nf0 + stride * jnp.uint32(nF)

            def mut_body(i, carry):
                (c, nf, sh1, sh2, sn,
                 seeds, offs, occs, rates, hs, origins) = carry
                d0 = prng.bits(meta_key, META_SITE_DRAW, c)
                pidx = jnp.clip(
                    (d0 % jnp.maximum(ring_n, 1).astype(jnp.uint32))
                    .astype(jnp.int32),
                    0, K - 1,
                )
                p_seed = ring_seed[pidx]
                p_off = ring_off[pidx]
                p_occ = ring_occ[pidx]
                p_rate = ring_rate[pidx]
                p_h = ring_h[pidx]
                d1 = prng.bits(meta_key, META_SITE_DRAW, c + 1)
                op = menu[
                    (d1 % jnp.uint32(len(plan.ops))).astype(jnp.int32)
                ]
                d2 = prng.bits(meta_key, META_SITE_DRAW, c + 2)
                d3 = prng.bits(meta_key, META_SITE_DRAW, c + 3)
                # occ: flip window bit k of one schedule clause's row
                occ_row = sched_rows[
                    (d2 % jnp.uint32(n_sched)).astype(jnp.int32)
                ]
                k = (d3 % jnp.uint32(10)).astype(jnp.int32)
                m_occ = jnp.where(
                    occ_cols == occ_row, p_occ ^ (jnp.int32(1) << k),
                    p_occ,
                )
                # clause: toggle one togglable clause's disable bit
                m_off = p_off ^ tog_bits[
                    (d2 % jnp.uint32(n_tog)).astype(jnp.int32)
                ]
                # rate: set one message clause's scale from the menu
                rate_row = rate_rows[
                    (d2 % jnp.uint32(n_rateops)).astype(jnp.int32)
                ]
                sc = scale_menu[(d3 % jnp.uint32(3)).astype(jnp.int32)]
                m_rate = jnp.where(rate_cols == rate_row, sc, p_rate)
                # horizon: bisect toward the prefix, or restore full
                h_eff = jnp.where(p_h == 0, full_h, p_h)
                alt = jnp.maximum(h_eff // 2, full_h // 8)
                m_h = jnp.where(
                    (d2 % jnp.uint32(2)) == jnp.uint32(0),
                    jnp.int32(0), alt,
                )
                cand_occ = jnp.where(op == 0, m_occ, p_occ)
                cand_off = jnp.where(op == 1, m_off, p_off)
                cand_rate = jnp.where(op == 2, m_rate, p_rate)
                cand_h = jnp.where(op == 3, m_h, p_h)
                c2 = c + adv_of[op]
                h1m, h2m = tpun.genome_hash64(
                    p_seed, cand_off, cand_occ, cand_rate, cand_h
                )
                dup = jnp.any(
                    (sarange < sn) & (sh1 == h1m) & (sh2 == h2m)
                )
                # dup -> single fresh fallback (consumes the next fresh
                # seed, no extra meta draws — the restructured host path)
                f_seed = nf
                h1f, h2f = fresh_hash(f_seed)
                seed_i = jnp.where(dup, f_seed, p_seed)
                off_i = jnp.where(dup, jnp.int32(0), cand_off)
                occ_i = jnp.where(dup, jnp.zeros_like(cand_occ), cand_occ)
                rate_i = jnp.where(
                    dup, jnp.ones_like(cand_rate), cand_rate
                )
                h_i = jnp.where(dup, jnp.int32(0), cand_h)
                org_i = jnp.where(dup, jnp.int32(0), jnp.int32(1))
                nf2 = jnp.where(dup, nf + stride, nf)
                # claim immediately: a second mutant drawing this genome
                # within THIS generation must fall back too
                sh1b = sh1.at[sn].set(
                    jnp.where(dup, h1f, h1m), mode="drop"
                )
                sh2b = sh2.at[sn].set(
                    jnp.where(dup, h2f, h2m), mode="drop"
                )
                sn2 = jnp.minimum(sn + 1, S)
                at = nF + i
                return (
                    c2, nf2, sh1b, sh2b, sn2,
                    seeds.at[at].set(seed_i),
                    offs.at[at].set(off_i),
                    occs.at[at].set(occ_i),
                    rates.at[at].set(rate_i),
                    hs.at[at].set(h_i),
                    origins.at[at].set(org_i),
                )

            (c, nf, sh1, sh2, sn,
             seeds, offs, occs, rates, hs, origins) = jax.lax.fori_loop(
                0, nM, mut_body,
                (c0, nf, sh1, sh2, sn,
                 seeds, offs, occs, rates, hs, origins),
            )
            # swarm groups: one coin per togglable clause per group,
            # statically unrolled (group layout is plan arithmetic)
            base = nF + nM
            for start in range(0, nS_, plan.swarm_group):
                gsz = min(plan.swarm_group, nS_ - start)
                off_g = jnp.int32(0)
                for b in plan.tog_bits:
                    coin = (
                        prng.bits(meta_key, META_SITE_DRAW, c)
                        % jnp.uint32(COIN_DENOM)
                    ) < jnp.uint32(COIN_DENOM // 2)
                    off_g = jnp.where(coin, off_g | jnp.int32(b), off_g)
                    c = c + jnp.int32(1)
                p0 = base + start
                seeds = seeds.at[p0:p0 + gsz].set(
                    nf + stride * jnp.arange(gsz, dtype=jnp.uint32)
                )
                offs = offs.at[p0:p0 + gsz].set(off_g)
                origins = origins.at[p0:p0 + gsz].set(jnp.int32(2))
                nf = nf + stride * jnp.uint32(gsz)
            # claim fresh + swarm genomes (mutants claimed in-loop):
            # exactly one append per pop candidate, so seen_n tracks the
            # host len(_seen) — fresh/swarm seeds are brand-new, so each
            # append is genuinely a new genome
            claim = list(range(nF)) + list(range(base, A))
            if claim:
                ci = jnp.asarray(claim, jnp.int32)
                hh1, hh2 = tpun.genome_hash64(
                    seeds[ci], offs[ci], occs[ci], rates[ci], hs[ci]
                )
                slots = sn + jnp.arange(len(claim), dtype=jnp.int32)
                sh1 = sh1.at[slots].set(hh1, mode="drop")
                sh2 = sh2.at[slots].set(hh2, mode="drop")
                sn = jnp.minimum(sn + len(claim), S)
            return (seeds, offs, occs, rates, hs, origins,
                    c, nf, sh1, sh2, sn)

        def build_fresh(c0, nf0, sh1, sh2, sn):
            # empty ring (host: `not parents`): ALL fresh, no meta draws
            seeds = nf0 + stride * jnp.arange(A, dtype=jnp.uint32)
            offs = jnp.zeros((A,), jnp.int32)
            occs = jnp.zeros((A, n_occ), jnp.int32)
            rates = jnp.ones((A, n_rate), jnp.float32)
            hs = jnp.zeros((A,), jnp.int32)
            origins = jnp.zeros((A,), jnp.int32)
            h1a, h2a = tpun.genome_hash64(seeds, offs, occs, rates, hs)
            slots = sn + jnp.arange(A, dtype=jnp.int32)
            return (
                seeds, offs, occs, rates, hs, origins, c0,
                nf0 + stride * jnp.uint32(A),
                sh1.at[slots].set(h1a, mode="drop"),
                sh2.at[slots].set(h2a, mode="drop"),
                jnp.minimum(sn + A, S),
            )

        def next_gen(_):
            (seeds_new, off_new, occ_new, rate_new, h_new, origin_new,
             c_next, nf_next, sh1n, sh2n, sn_next) = jax.lax.cond(
                ring_n > 0, build_mixed, build_fresh,
                dl.counter, dl.next_fresh,
                dl.seen_h1, dl.seen_h2, dl.seen_n,
            )
            h_ep, h_of = tpun.genome_ctl_rows(h_new, plan.full_h)
            queue2 = RefillQueue(
                seeds=seeds_new, off=off_new, occ=occ_new,
                rate_scale=rate_new, h_epoch=h_ep, h_off=h_of,
            )
            head_ctl = TriageCtl(
                off=off_new[:L], occ=occ_new[:L],
                rate_scale=rate_new[:L],
                h_epoch=h_ep[:L], h_off=h_of[:L],
            )
            # whole-state respawn: at a boundary EVERY lane re-inits on
            # the new head admissions (no masked merge — the refill path
            # handles partial retirement; a boundary is total)
            fresh = self._init(seeds_new[:L], head_ctl)
            zi = functools.partial(jnp.zeros, dtype=jnp.int32)
            rf2 = rf._replace(
                # step_cap, iters and busy carry over (cumulative
                # occupancy accounting across the whole window)
                cursor=jnp.int32(L),
                admitted=jnp.arange(L, dtype=jnp.int32),
                retired=jnp.full((A,), -1, jnp.int32),
                violated=jnp.zeros((A,), jnp.bool_),
                deadlocked=jnp.zeros((A,), jnp.bool_),
                violation_at=jnp.full((A,), INF_US, jnp.int32),
                violation_epoch=zi((A,)),
                violation_step=jnp.full((A,), -1, jnp.int32),
                steps=zi((A,)),
                events=zi((A,)),
                overflow=zi((A,)),
                dead_drops=zi((A,)),
                nonmember_drops=zi((A,)),
                unsynced_loss=zi((A,)),
                clock=zi((A,)),
                epoch=zi((A,)),
                fires=zi((A, len(FIRE_KINDS))),
                occ_fired=(
                    None if rf.occ_fired is None
                    else jnp.zeros((A, n_occ), jnp.uint32)
                ),
                cov_bitmap=jnp.zeros((A, COV_WORDS), jnp.uint32),
                cov_hiwater=zi((A,)),
                cov_transitions=zi((A,)),
            )
            loop2 = folded_loop._replace(
                counter=c_next, next_fresh=nf_next,
                seen_h1=sh1n, seen_h2=sh2n, seen_n=sn_next,
                gen_h_raw=h_new, gen_origin=origin_new,
            )
            return fresh._replace(queue=queue2, refill=rf2, loop=loop2)

        def window_done(_):
            return ns._replace(loop=folded_loop)

        return jax.lax.cond(
            gens_done < dl.target_gens, next_gen, window_done, None
        )

    def init_refill(
        self, seeds, lanes: int, ctl=None,
        step_cap: int = 100_000,
    ) -> SimState:
        """Build a refill-mode state: `lanes` device lanes fed from a
        device-resident queue of ALL `seeds` (one admission per seed).

        `ctl` (triage mode) is an [A]-row TriageCtl giving EVERY
        admission its own clause/occurrence/rate/horizon genome — the
        shape `triage.build_ctl` / `explore.ctl_for` already produce.
        Admissions 0..L-1 start resident (lane order == admission
        order); the rest admit in retirement order. `step_cap` is the
        per-admission step budget — the chunked path's max_steps, and
        the truncation semantics are identical. See run_refill."""
        seeds = jnp.asarray(seeds, jnp.uint32)
        if seeds.ndim != 1 or seeds.shape[0] == 0:
            raise ValueError("init_refill needs a non-empty 1-D seed array")
        A = int(seeds.shape[0])
        L = max(1, min(int(lanes), A))
        if ctl is not None and not self.triage:
            raise ValueError(
                "a refill ctl queue requires BatchedSim(..., triage=True)"
            )
        if self.triage and ctl is None:
            ctl = default_ctl(A, self.config.horizon_us)
        head_ctl = None
        if self.triage:
            if int(ctl.off.shape[0]) != A:
                raise ValueError(
                    f"refill ctl has {int(ctl.off.shape[0])} rows for "
                    f"{A} admissions — one genome per admission"
                )
            head_ctl = jax.tree_util.tree_map(lambda x: x[:L], ctl)
        state = (
            self.init(seeds[:L]) if head_ctl is None
            else self.init(seeds[:L], head_ctl)
        )
        self.dispatch_count += 1
        # jnp.array (COPY), never asarray: the queue rides the donated
        # sweep carry, so an aliased caller array would be DELETED by the
        # first segment's donation — a caller must be able to reuse its
        # seed/ctl arrays (e.g. to run the same queue sharded and
        # unsharded for a bit-identity check)
        queue = RefillQueue(
            seeds=jnp.array(seeds, jnp.uint32),
            off=None if ctl is None else jnp.array(ctl.off, jnp.int32),
            occ=None if ctl is None else jnp.array(ctl.occ, jnp.int32),
            rate_scale=(
                None if ctl is None
                else jnp.array(ctl.rate_scale, jnp.float32)
            ),
            h_epoch=(
                None if ctl is None else jnp.array(ctl.h_epoch, jnp.int32)
            ),
            h_off=(
                None if ctl is None else jnp.array(ctl.h_off, jnp.int32)
            ),
        )
        zi = functools.partial(jnp.zeros, dtype=jnp.int32)
        if step_cap <= 0:
            raise ValueError(f"step_cap must be positive, got {step_cap}")
        log = RefillLog(
            cursor=jnp.int32(L),
            admitted=jnp.arange(L, dtype=jnp.int32),
            step_cap=jnp.int32(step_cap),
            iters=jnp.int32(0),
            busy=zi((L,)),
            retired=jnp.full((A,), -1, jnp.int32),
            violated=jnp.zeros((A,), jnp.bool_),
            deadlocked=jnp.zeros((A,), jnp.bool_),
            violation_at=jnp.full((A,), INF_US, jnp.int32),
            violation_epoch=zi((A,)),
            violation_step=jnp.full((A,), -1, jnp.int32),
            steps=zi((A,)),
            events=zi((A,)),
            overflow=zi((A,)),
            dead_drops=zi((A,)),
            nonmember_drops=zi((A,)),
            unsynced_loss=zi((A,)),
            clock=zi((A,)),
            epoch=zi((A,)),
            fires=zi((A, len(FIRE_KINDS))),
            occ_fired=(
                jnp.zeros((A, len(OCC_CLAUSES)), jnp.uint32)
                if self._occ_track else None
            ),
            cov_bitmap=(
                jnp.zeros((A, COV_WORDS), jnp.uint32)
                if self.coverage else None
            ),
            cov_hiwater=zi((A,)) if self.coverage else None,
            cov_transitions=zi((A,)) if self.coverage else None,
        )
        return state._replace(queue=queue, refill=log)

    def run_refill(
        self, seeds, lanes: int, max_steps: int = 100_000,
        dispatch_steps: int = DEFAULT_DISPATCH_STEPS, ctl=None,
        total_steps: Optional[int] = None,
    ) -> SimState:
        """Run ALL `seeds` as admissions of a continuously batched sweep
        over `lanes` device lanes: a lane that violates or reaches its
        per-admission horizon retires and admits the next queued seed
        inside the jitted loop, so the chip never idles on finished
        lanes (docs/continuous_batching.md). Decode with
        `refill_results` / `summarize_refill`.

        `max_steps` is the PER-ADMISSION step budget, with exactly the
        chunked path's semantics: an admission reaching it retires
        truncated (violated as-is) inside the step, so a violation past
        max_steps is invisible to both paths alike. `total_steps` bounds
        the WHOLE sweep's loop iterations; its default (max_steps * A)
        can never bind — even fully serialized admissions fit — and the
        speculative early-stop exits the segment loop as soon as the
        queue drains, so the generous bound costs at most one no-op
        segment."""
        state = self.init_refill(seeds, lanes, ctl, step_cap=max_steps)
        A = int(state.queue.seeds.shape[0])
        if total_steps is None:
            total_steps = int(max_steps) * A
        return self.run_state(state, total_steps, dispatch_steps)

    def init_devloop(
        self, seeds, lanes: int, ctl, window: int,
        step_cap: int = 100_000,
        meta_seed: int = 0, meta_counter: int = 0, next_fresh: int = 0,
        target_gens: Optional[int] = None,
        gen_h_raw=None, gen_origin=None,
        ring: Optional[dict] = None, union=None,
        seen: Optional[dict] = None,
    ) -> SimState:
        """Build a device-loop state: a refill sweep whose generation
        boundary — fold, rank, mutate, respawn — runs IN-JIT, so a
        window of up to `window` generations is one dispatch chain with
        zero host sync (r19, docs/explore.md).

        `seeds`/`ctl` are generation 0's population, exactly as the host
        `Explorer._population` built it (the host runs the first
        population itself so both faces share the same entry point);
        `meta_seed`/`meta_counter`/`next_fresh` resume the MetaRng
        cursor at the point the host left it. `gen_h_raw`/`gen_origin`
        carry generation 0's raw genome horizons and origin codes (the
        ctl encode is lossy: genome horizon 0 encodes as full horizon).
        `ring`/`union`/`seen` upload the explorer's current corpus
        top-K, coverage union, and genome-hash dedup set — all optional
        (a cold start begins empty). `window` (G) is a SHAPE: the
        archive capacity and jit cache key; `target_gens` <= G lets a
        final partial window reuse the compiled program."""
        import numpy as np

        plan = self.devloop
        if plan is None:
            raise ValueError(
                "init_devloop needs BatchedSim(..., devloop=plan)"
            )
        if ctl is None:
            raise ValueError("init_devloop requires a ctl queue (triage)")
        seeds = jnp.asarray(seeds, jnp.uint32)
        A = plan.pop
        if int(seeds.shape[0]) != A:
            raise ValueError(
                f"devloop population is {A} admissions per generation, "
                f"got {int(seeds.shape[0])} seeds"
            )
        G = int(window)
        if G < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        tg = G if target_gens is None else int(target_gens)
        if not 1 <= tg <= G:
            raise ValueError(
                f"target_gens must be in [1, {G}], got {target_gens}"
            )
        K, S = plan.top_k, plan.seen_cap
        n_occ = len(OCC_CLAUSES)
        n_rate = len(RATE_CLAUSES)
        state = self.init_refill(seeds, lanes, ctl, step_cap=step_cap)

        # -- ring upload (the host corpus's current top-K, sorted)
        ring = dict(ring or {})
        rn = int(ring.get("n", 0))
        if not 0 <= rn <= K:
            raise ValueError(f"ring has {rn} rows, capacity {K}")

        def buf(key, shape, dtype, fill=0):
            src = ring.get(key)
            out = np.full(shape, fill, dtype=dtype)
            if src is not None and rn:
                out[:rn] = np.asarray(src, dtype=dtype)[:rn]
            return jnp.array(out)

        ring_bits = buf("bits", (K,), np.int32)
        ring_seed = buf("seed", (K,), np.uint32)
        ring_off = buf("off", (K,), np.int32)
        ring_occ = buf("occ", (K, n_occ), np.int32)
        ring_rate = buf("rate", (K, n_rate), np.float32, fill=1.0)
        ring_h = buf("h", (K,), np.int32)

        # -- dedup-table upload + headroom: the window appends at most
        # one row per candidate, so a full window must fit
        seen = dict(seen or {})
        sn = int(seen.get("n", 0))
        if sn + G * A > S:
            raise ValueError(
                f"seen table has {sn} rows + window appends {G * A} "
                f"> capacity {S}; raise seen_cap or shrink the window"
            )
        s1 = np.zeros((S,), np.uint32)
        s2 = np.zeros((S,), np.uint32)
        if sn:
            s1[:sn] = np.asarray(seen["h1"], np.uint32)[:sn]
            s2[:sn] = np.asarray(seen["h2"], np.uint32)[:sn]

        un = (
            np.zeros((COV_WORDS,), np.uint32) if union is None
            else np.asarray(union, np.uint32)
        )
        if un.shape != (COV_WORDS,):
            raise ValueError(
                f"union bitmap must be [{COV_WORDS}] u32, got {un.shape}"
            )
        gh = (
            np.zeros((A,), np.int32) if gen_h_raw is None
            else np.asarray(gen_h_raw, np.int32)
        )
        go = (
            np.zeros((A,), np.int32) if gen_origin is None
            else np.asarray(gen_origin, np.int32)
        )
        zi = functools.partial(jnp.zeros, dtype=jnp.int32)
        # jnp.array COPIES throughout (donation safety — the loop carry
        # is donated every segment, same rule as the refill queue)
        loop = DevLoop(
            meta_key=jnp.uint32(key_from_seed(int(meta_seed))),
            counter=jnp.int32(int(meta_counter)),
            next_fresh=jnp.uint32(int(next_fresh) & 0xFFFFFFFF),
            gens_done=jnp.int32(0),
            target_gens=jnp.int32(tg),
            accepts=jnp.int32(0),
            ring_n=jnp.int32(rn),
            ring_bits=ring_bits,
            ring_seed=ring_seed,
            ring_off=ring_off,
            ring_occ=ring_occ,
            ring_rate=ring_rate,
            ring_h=ring_h,
            union=jnp.array(un),
            seen_h1=jnp.array(s1),
            seen_h2=jnp.array(s2),
            seen_n=jnp.int32(sn),
            gen_h_raw=jnp.array(gh),
            gen_origin=jnp.array(go),
            arch_seed=jnp.zeros((G, A), jnp.uint32),
            arch_off=zi((G, A)),
            arch_occ=zi((G, A, n_occ)),
            arch_rate=jnp.ones((G, A, n_rate), jnp.float32),
            arch_h=zi((G, A)),
            arch_origin=zi((G, A)),
            arch_violated=jnp.zeros((G, A), jnp.bool_),
            arch_bitmap=jnp.zeros((G, A, COV_WORDS), jnp.uint32),
            arch_hiwater=zi((G, A)),
            arch_transitions=zi((G, A)),
        )
        return state._replace(loop=loop)

    def run_devloop(
        self, state: SimState,
        dispatch_steps: int = DEFAULT_DISPATCH_STEPS,
        total_steps: Optional[int] = None,
    ) -> SimState:
        """Run a device-loop window to completion: segments of the SAME
        jitted step as every other mode, with the generation boundary
        firing inside the step whenever a generation fully retires. The
        default `total_steps` bound (step_cap * A * G) can never bind —
        even fully serialized admissions across every generation fit —
        and the speculative early-stop exits once the final generation
        drains, so the generous bound costs at most one no-op segment.
        Decode ONCE with `devloop_results` — that single transfer is the
        window's only host sync."""
        if state.loop is None:
            raise ValueError("run_devloop needs an init_devloop state")
        A = int(state.queue.seeds.shape[0])
        G = int(state.loop.arch_seed.shape[0])
        if total_steps is None:
            total_steps = int(state.refill.step_cap) * A * G
        return self.run_state(state, total_steps, dispatch_steps)

    # --------------------------------------------------- sharded refill

    def init_refill_sharded(
        self, seeds, lanes: int, mesh: jax.sharding.Mesh, ctl=None,
        step_cap: int = 100_000,
    ) -> SimState:
        """Build the MULTI-CHIP refill state: the admission list is
        partitioned into one contiguous, equal-length sub-queue per mesh
        device (tail-padded with repeats of the first seed; the pad rows
        run normally and are stripped by `refill_results_sharded`), each
        device gets its own `lanes`-lane engine plus its own RefillLog
        result buffers and cursor, and every state leaf gains a leading
        device axis [D, ...] sharded one row per device.

        Device d's block IS the single-device refill state of sub-queue
        d — same shapes, same init draws — which is what makes the
        sharded sweep's per-admission rows bit-identical to the 1-device
        refill path (and hence to the chunked path) by construction:
        concatenating per-device rows in device order restores global
        admission (= seed) order."""
        import numpy as np

        seeds = np.asarray(seeds, np.uint32)
        if seeds.ndim != 1 or seeds.shape[0] == 0:
            raise ValueError(
                "init_refill_sharded needs a non-empty 1-D seed array"
            )
        D = int(mesh.devices.size)
        A = int(seeds.shape[0])
        Ad = -(-A // D)  # per-device sub-queue length (ceil)
        pad = Ad * D - A
        if pad:
            seeds_in = np.concatenate([seeds, np.repeat(seeds[:1], pad)])
        else:
            seeds_in = seeds
        ctl_in = ctl
        if ctl is not None and pad:
            ctl_in = jax.tree_util.tree_map(
                lambda x: jnp.concatenate(
                    [jnp.asarray(x), jnp.repeat(
                        jnp.asarray(x)[:1], pad, axis=0
                    )]
                ),
                ctl,
            )
        states = []
        for d in range(D):
            sub = seeds_in[d * Ad : (d + 1) * Ad]
            sub_ctl = (
                None if ctl_in is None
                else jax.tree_util.tree_map(
                    lambda x: x[d * Ad : (d + 1) * Ad], ctl_in
                )
            )
            states.append(
                self.init_refill(sub, lanes, sub_ctl, step_cap=step_cap)
            )
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *states
        )
        sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(mesh.axis_names[0])
        )
        # ONE device_put over the whole pytree (see shard_state)
        stacked = jax.device_put(
            stacked, jax.tree_util.tree_map(lambda _: sh, stacked)
        )
        self.dispatch_count += 1
        return stacked

    def _sharded_segment(self, mesh: jax.sharding.Mesh, n_steps: int):
        """The compiled multi-chip sweep segment: shard_map over the
        leading device axis, each device running the REAL per-device
        refill segment — `split_state`, the donated while_loop over
        `_step_split` (its `lax.cond` retire-and-admit branch stays a
        real cond, not a vmap-degraded select), per-device early exit
        when the device's own queue drains. ZERO cross-device
        collectives inside the step or the segment: devices touch only
        their own sub-queue, lanes, and result buffers; the harvest /
        early-stop gathers happen at segment end only, on the host side
        (run_state_sharded / refill_results_sharded). The analysis
        lane-independence rule walks this exact program and allowlists
        collectives by exact primitive name (none in-tree)."""
        key = (mesh, int(n_steps))
        fn = self._sharded_cache.get(key)
        if fn is not None:
            return fn
        spec = jax.sharding.PartitionSpec(mesh.axis_names[0])

        def seg(stacked: SimState) -> SimState:
            # each device sees its [1, ...] block: strip the device axis,
            # run the ordinary refill segment, put the axis back
            st = jax.tree_util.tree_map(lambda x: x[0], stacked)
            hot, cold, const = split_state(st)

            def cond(carry):
                h, _c, i = carry
                return jnp.logical_and(i < n_steps, jnp.any(~h.done))

            def body(carry):
                h, c, i = carry
                h2, c2, _ = self._step_split(h, c, const)
                return h2, c2, i + 1

            h, c, _ = jax.lax.while_loop(
                cond, body, (hot, cold, jnp.int32(0))
            )
            out = merge_state(h, c, const)
            return jax.tree_util.tree_map(lambda x: x[None], out)

        fn = jax.jit(
            jax.shard_map(
                seg, mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
        self._sharded_cache[key] = fn
        return fn

    def run_state_sharded(
        self, state: SimState, mesh: jax.sharding.Mesh, max_steps: int,
        dispatch_steps: int = DEFAULT_DISPATCH_STEPS,
    ) -> SimState:
        """run_state's segment loop over the shard_map'd segment program:
        same speculative early-stop (the all-done reduction over the
        sharded `done` plane is the one cross-device gather, dispatched
        at segment boundaries only), same donation discipline — ONE
        loop, parameterized by the segment runner."""
        return self.run_state(
            state, max_steps, dispatch_steps,
            segment=lambda st, n: self._sharded_segment(mesh, n)(st),
        )

    def run_refill_sharded(
        self, seeds, lanes: int, mesh: jax.sharding.Mesh,
        max_steps: int = 100_000, dispatch_steps: int = DEFAULT_DISPATCH_STEPS, ctl=None,
        total_steps: Optional[int] = None,
    ) -> SimState:
        """The multi-chip continuously batched sweep: ALL `seeds` run as
        admissions of D independent per-device refill engines (`lanes`
        lanes EACH), one shard_map'd program per segment. Decode with
        `refill_results_sharded(state, admissions=len(seeds))`.

        `max_steps` keeps the per-admission chunked-truncation semantics
        of run_refill; `total_steps` bounds each DEVICE's segment-loop
        iterations (default max_steps * per-device queue length — never
        binding). Per-admission rows are bit-identical to run_refill's
        and to the chunked path's for any fixed admission order (the
        multichip matrix tests pin this)."""
        state = self.init_refill_sharded(
            seeds, lanes, mesh, ctl, step_cap=max_steps
        )
        Ad = int(state.queue.seeds.shape[1])
        if total_steps is None:
            total_steps = int(max_steps) * Ad
        return self.run_state_sharded(state, mesh, total_steps, dispatch_steps)

    # ------------------------------------------------------------------ run

    # donate_argnums=1: the carry state's buffers are DONATED to each sweep
    # segment — XLA writes the output state into the input's HBM instead of
    # allocating a fresh ~100 MB pytree per dispatch and leaving the old one
    # live until the host drops its reference. Inside the while_loop XLA
    # already aliases the loop carry; donation extends that aliasing across
    # the chunked-dispatch boundary, so a long sweep's peak HBM is ONE state
    # (not two) and the inter-segment allocate/copy round-trip disappears.
    # Safe because `run` immediately rebinds `state` to the result: the
    # donated input is never read again (jax invalidates it loudly if a
    # future caller tries).
    @functools.partial(
        jax.jit, static_argnums=(0, 2), donate_argnums=(1,)
    )
    def _run(self, state: SimState, max_steps: int) -> SimState:
        # hot/cold/const split (r8): the while_loop carries only the hot +
        # cold pytrees; ConstState (key0, ctl, skew_ppm) rides as a
        # loop-invariant operand, so the fused step stops rewriting those
        # bytes every iteration and the donated segment stops rotating
        # them through fresh buffers at every dispatch boundary.
        hot, cold, const = split_state(state)

        def cond(carry):
            h, _c, i = carry
            return jnp.logical_and(i < max_steps, jnp.any(~h.done))

        def body(carry):
            h, c, i = carry
            h2, c2, _ = self._step_split(h, c, const)
            return h2, c2, i + 1

        h, c, _ = jax.lax.while_loop(cond, body, (hot, cold, jnp.int32(0)))
        return merge_state(h, c, const)

    def run(
        self, seeds, max_steps: int = 100_000, dispatch_steps: int = DEFAULT_DISPATCH_STEPS,
        mesh: Optional[jax.sharding.Mesh] = None, ctl=None,
    ) -> SimState:
        """Run lanes until every lane is done (or max_steps).

        With `mesh`, the lane axis is sharded over the mesh's first axis —
        the production multi-device sweep path (the reference uses ALL
        available parallel hardware for a seed sweep, one thread per seed,
        runtime/builder.rs:118-136; here it is one lane shard per chip,
        zero cross-device traffic). Results are bit-identical to the
        unsharded run: no draw folds the lane index, so a seed's trajectory
        does not depend on which device its lane landed on.

        The while_loop is dispatched in chunks of `dispatch_steps`: a long
        horizon at high lane counts would otherwise be ONE device program
        running for minutes with the host unable to stop it early.
        Chunking bounds each program's runtime and lets the host stop
        soon after every lane is done. At most two programs compile
        (chunk size + final tail).

        The early-stop check is SPECULATIVE (r6): segment k+1 is enqueued
        before the host reads segment k's all-done reduction, so segments
        run back-to-back with no host round-trip between them (the r5
        loop blocked on `done.all()` before each dispatch — one host
        round-trip of device idle per segment). When segment k did finish
        every lane, the speculatively-enqueued k+1 is a device no-op (the
        while_loop's cond is false on entry) and the loop exits one
        dispatch later than strictly needed; results are bit-identical
        either way.
        """
        if dispatch_steps <= 0:
            raise ValueError(f"dispatch_steps must be positive, got {dispatch_steps}")
        state = self.init(seeds) if ctl is None else self.init(seeds, ctl)
        self.dispatch_count += 1
        if mesh is not None:
            L = state.clock.shape[0]
            n_dev = int(mesh.devices.size)
            if L % n_dev:
                raise ValueError(
                    f"lane count {L} not divisible by mesh size {n_dev}; "
                    "pad the seed batch (run_batch does this automatically)"
                )
            state = self.shard_state(state, mesh, lane_axis=mesh.axis_names[0])
            self.dispatch_count += 1  # the single whole-pytree device_put
        return self.run_state(state, max_steps, dispatch_steps)

    def run_state(
        self, state: SimState, max_steps: int, dispatch_steps: int = DEFAULT_DISPATCH_STEPS,
        segment=None,
    ) -> SimState:
        """run()'s chunked segment loop on a PRE-BUILT state (the shared
        tail of run / run_refill / run_refill_sharded): speculative
        early-stop, donated segments, dispatch accounting — see run()'s
        docstring. `segment(state, n)` overrides the donated `_run`
        program (run_state_sharded passes the shard_map'd segment), so
        the loop logic exists exactly once."""
        if dispatch_steps <= 0:
            raise ValueError(
                f"dispatch_steps must be positive, got {dispatch_steps}"
            )
        run_segment = segment or (lambda st, n: self._run(st, n))
        remaining = max_steps
        alive = None
        while remaining > 0:
            if alive is not None:
                # enqueue the previous segment's all-done reduction FIRST
                # (tiny scalar; reads state.done before the donation
                # below — PJRT keeps the buffer alive for the in-flight
                # reader, so donation stays safe)
                alive = self._any_alive(state)
                self.dispatch_count += 1
            n = min(dispatch_steps, remaining)
            # the segment DONATES state: the rebinding here is what makes
            # that legal — the pre-segment buffers are dead the moment
            # the segment is dispatched
            state = run_segment(state, n)
            self.dispatch_count += 1
            remaining -= n
            # block on the reduction only AFTER the next segment is in
            # flight: the early stop costs at most one no-op segment,
            # never a device-idle host round-trip
            if alive is not None:
                with telemetry.span("wait", site="segment"):
                    stop = not bool(alive)
                if stop:
                    break
            if alive is None and remaining > 0:
                alive = True  # arm the check from the second segment on
        return state

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def run_steps(self, state: SimState, n_steps: int) -> SimState:
        """Fixed-step scan (benchmark-friendly: no host syncs)."""
        hot, cold, const = split_state(state)

        def body(carry, _):
            h, c = carry
            h2, c2, _ = self._step_split(h, c, const)
            return (h2, c2), None

        (h, c), _ = jax.lax.scan(body, (hot, cold), None, length=n_steps)
        return merge_state(h, c, const)

    # donated like _run: run_traced hands the freshly-built init state in
    # and never touches it again (the [T, 1, ...] record stream is a new
    # allocation either way)
    @functools.partial(
        jax.jit, static_argnums=(0, 2), donate_argnums=(1,)
    )
    def _run_traced(self, state: SimState, n_steps: int):
        """`n_steps` traced steps, bit for bit a fixed-length `lax.scan` of
        the step: the final state and the [n_steps, L, ...] records. The
        loop stops one step after every lane is done. A done lane's state
        is a fixed point of the step but for its chain key, which no record
        leaf reads, so that step's record is the record of every later
        step: it fills the rows left, and the key takes its turns alone."""
        hot, cold, const = split_state(state)
        rec = jax.eval_shape(lambda: self._step_split(hot, cold, const)[2])
        recs = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n_steps,) + x.shape, x.dtype), rec
        )

        def cond(carry):
            _h, _c, i, _r, frozen = carry
            return (i < n_steps) & ~frozen

        def body(carry):
            h, c, i, r, _ = carry
            h2, c2, rec = self._step_split(h, c, const)
            r = jax.tree_util.tree_map(
                lambda buf, x: jax.lax.dynamic_update_index_in_dim(
                    buf, x, i, 0
                ),
                r, rec,
            )
            return h2, c2, i + 1, r, jnp.all(h.done)

        h, c, i, recs, _ = jax.lax.while_loop(
            cond, body, (hot, cold, jnp.int32(0), recs, jnp.bool_(False))
        )
        after = jnp.arange(n_steps) >= i  # rows the loop did not step

        def fill(buf):
            last = jax.lax.dynamic_index_in_dim(buf, i - 1, 0)
            return jnp.where(
                after.reshape((n_steps,) + (1,) * (buf.ndim - 1)), last, buf
            )

        recs = jax.tree_util.tree_map(fill, recs)
        h = h._replace(key=_turn_key(h.key, n_steps - i))
        return merge_state(h, c, const), recs

    def run_traced(self, seed: int, max_steps: int = 20_000, ctl=None):
        """Re-run ONE seed with full event capture (the violation microscope).

        Returns (final_state, TraceRecord with [T, 1, ...] leaves),
        T = `max_steps`. Use trace.extract_trace to turn the records into
        readable events. The trajectory is bit-identical to the same seed
        inside any batch: the step function is the same jitted program and
        all randomness is derived from the lane seed, never from lane
        position. The device stops stepping one step after the lane is
        done; the rows past it hold the done lane's record, as if it had
        been stepped to `max_steps` (`_run_traced`). `ctl` (triage mode)
        traces a SHRUNK candidate — e.g. a repro bundle's — with the
        suppressed faults absent from the record stream.
        """
        seeds = jnp.asarray([seed], jnp.uint32)
        state = self.init(seeds) if ctl is None else self.init(seeds, ctl)
        self.dispatch_count += 2  # init + the traced scan below
        return self._run_traced(state, max_steps)

    # ------------------------------------------------------------ sharding

    def shard_state(
        self, state: SimState, mesh: jax.sharding.Mesh, lane_axis: str = "seeds",
        node_axis: Optional[str] = None,
    ) -> SimState:
        """Shard lane (and optionally node) axes over a device mesh.

        Lanes are independent, so lane-sharding needs no collectives at all —
        the scaling-book data-parallel recipe. Node-sharding additionally
        splits per-node state (dim 1 of every [L, N, ...] leaf, which in the
        dest-major layout includes the message pool); XLA inserts gathers
        for the cross-node routing. The straggler side pool's dim 1 is the
        candidate axis, not the node axis — it stays lane-sharded only.

        WHEN TO USE WHICH (measured, benches/node_sharding.py + the table
        in docs/perf_notes.md): shard the LANE axis for throughput — on an
        8-device mesh the 2-D layouts LOSE at every N measured (12x
        slower at N = 8, still behind at N = 32): node sharding pays
        per-step cross-device gathers for message routing, lane sharding
        pays nothing. Pass `node_axis` only when a single device cannot
        HOLD the per-node state (very large N x state: a memory-capacity
        lever, not a speed lever).
        """
        P = jax.sharding.PartitionSpec
        N = self.spec.n_nodes

        def sharding_for(x, node_ok=True):
            if x.ndim == 0:
                return jax.sharding.NamedSharding(mesh, P())
            axes: list = [lane_axis] + [None] * (x.ndim - 1)
            if (
                node_axis is not None and node_ok and x.ndim >= 2
                and x.shape[1] == N
            ):
                axes[1] = node_axis
            return jax.sharding.NamedSharding(mesh, P(*axes))

        # ONE device_put over the whole pytree (a per-leaf loop dispatches
        # ~40 transfers, each paying a host dispatch)
        strag = state.strag
        shardings = jax.tree_util.tree_map(
            sharding_for, state._replace(strag=None)
        )
        rest = jax.device_put(state._replace(strag=None), shardings)
        if strag is not None:
            strag = jax.device_put(
                strag,
                jax.tree_util.tree_map(
                    functools.partial(sharding_for, node_ok=False), strag
                ),
            )
        return rest._replace(strag=strag)


def abs_time_us(state: SimState):
    """Absolute virtual time per lane as int64 numpy (epoch * REBASE + off)."""
    import numpy as np

    return np.asarray(state.epoch, np.int64) * REBASE_US + np.asarray(
        state.clock, np.int64
    )


# the widest lane axis `_sum64` sums exactly (run_batch caps its chunks here)
SUM64_MAX_LANES = 65_536


def _sum64(x: jnp.ndarray, axis=0):
    """Exact lane sum of a non-negative i32 tensor WITHOUT int64 (x64 mode
    is off): split into 16-bit halves, sum each in u32 — hi * 2^16 + lo is
    recombined host-side as a Python int. Both partials stay below 2^32
    only for lanes <= 65536 (values < 2^31), so that bound is ENFORCED:
    a bigger batch must be summarized in chunks (run_batch already
    chunks), not allowed to wrap the u32 partials silently."""
    if x.shape[axis] > SUM64_MAX_LANES:
        raise ValueError(
            f"_sum64: lane axis {x.shape[axis]} > {SUM64_MAX_LANES} "
            "would overflow the u32 partial sums — summarize in chunks"
        )
    xu = x.astype(jnp.uint32)
    return (
        jnp.sum(xu >> 16, axis=axis, dtype=jnp.uint32),
        jnp.sum(xu & jnp.uint32(0xFFFF), axis=axis, dtype=jnp.uint32),
    )


def _join64(hi, lo) -> int:
    import numpy as np

    return int(np.asarray(hi, np.int64) * 65536 + np.asarray(lo, np.int64))


def _summary_reduction(state: SimState) -> dict:
    """The decode-side fusion (r8): every per-summary reduction — lane
    counters, chaos fire totals, per-occurrence fire counts, coverage
    popcounts — folded into ONE jitted device program. summarize()
    previously pulled a dozen full [L, ...] tensors to the host and
    reduced them in numpy; a chunked sweep paid those transfers per chunk.
    Now the device reduces and the host reads back only scalars/rows."""
    violated = state.violated
    out = {
        "violations": jnp.sum(violated, dtype=jnp.int32),
        "deadlocked": jnp.sum(state.deadlocked, dtype=jnp.int32),
        "events64": _sum64(state.events),
        "overflow64": _sum64(state.overflow),
        "dead_drops64": _sum64(state.dead_drops),
        "nonmember_drops64": _sum64(state.nonmember_drops),
        "unsynced_loss64": _sum64(state.unsynced_loss),
        "steps64": _sum64(state.steps),
        "epoch64": _sum64(state.epoch),
        "clock64": _sum64(state.clock),
        # earliest first-violation step over violating lanes: the triage
        # shrinker's run-to-step truncation anchor (INT32_MAX = none)
        "first_violation_step": jnp.min(
            jnp.where(violated, state.violation_step, jnp.int32(2**31 - 1))
        ),
        "fires64": _sum64(state.fires, axis=0),  # ([K], [K])
    }
    if state.occ_fired is not None:
        # per-(clause row, occurrence bit) lane counts [R, 32]
        bits = (
            state.occ_fired[:, :, None]
            >> jnp.arange(32, dtype=jnp.uint32)[None, None, :]
        ) & jnp.uint32(1)
        out["occ_counts"] = bits.sum(axis=0, dtype=jnp.int32)
    if state.cov is not None:
        out["cov_union"] = jax.lax.reduce(
            state.cov.bitmap, jnp.uint32(0), jax.lax.bitwise_or, (0,)
        )  # [COV_WORDS]
        out["cov_union_bits"] = jax.lax.population_count(
            out["cov_union"]
        ).sum(dtype=jnp.int32)
        out["cov_hiwater"] = jnp.max(state.cov.hiwater)
        out["cov_transitions64"] = _sum64(state.cov.transitions)
    return out


_SUMMARY_RED = jax.jit(_summary_reduction)


def summarize(state: SimState, spec: Optional[ProtocolSpec] = None) -> dict:
    """Host-side summary of a finished batch (bug reports with repro info).

    Pass the spec to include its `lane_metrics` diagnostics — e.g. the Raft
    spec reports how many lanes saturated their fixed-capacity log (a lane
    whose log stopped appending is a lane that stopped finding bugs; that
    must be visible, not silent).

    All batch-wide reductions run on device in one fused decode program
    (`_summary_reduction`); the host pulls back only the reduced rows plus
    the [L] violation bitmap (for lane indices).
    """
    import numpy as np

    red = _SUMMARY_RED(state)
    violated = np.asarray(state.violated)
    L = int(violated.shape[0])
    steps_total = _join64(*red["steps64"])
    vt_total_us = (
        _join64(*red["epoch64"]) * REBASE_US + _join64(*red["clock64"])
    )
    out = {
        "lanes": L,
        "violations": int(red["violations"]),
        "violation_lanes": np.nonzero(violated)[0].tolist()[:32],
        "deadlocked": int(red["deadlocked"]),
        "total_events": _join64(*red["events64"]),
        "total_overflow": _join64(*red["overflow64"]),
        "total_dead_drops": _join64(*red["dead_drops64"]),
        "total_nonmember_drops": _join64(*red["nonmember_drops64"]),
        "total_unsynced_loss": _join64(*red["unsynced_loss64"]),
        "mean_steps": steps_total / L,
        "mean_virtual_secs": vt_total_us / L / 1e6,
    }
    if out["violations"]:
        out["first_violation_step"] = int(red["first_violation_step"])
    # per-fault-kind chaos fire counts (the coverage report's raw data)
    f_hi, f_lo = red["fires64"]
    f_hi, f_lo = np.asarray(f_hi, np.int64), np.asarray(f_lo, np.int64)
    for i, name in enumerate(FIRE_KINDS):
        out[f"fires_{name}"] = int(f_hi[i] * 65536 + f_lo[i])
    # per-occurrence fire counts (nemesis schedule clauses only): lanes in
    # which occurrence k of the clause applied — coverage_report renders
    # these next to the clause totals, and chunked run_batch sums them
    if state.occ_fired is not None:
        occ_counts = np.asarray(red["occ_counts"])
        for row, clause in enumerate(OCC_CLAUSES):
            for k in range(32):
                n = int(occ_counts[row, k])
                if n:
                    out[f"occfires_{clause}_k{k}"] = n
    if state.cov is not None:
        out["coverage_bits"] = int(red["cov_union_bits"])
        out["coverage_hiwater"] = int(red["cov_hiwater"])
        out["coverage_transitions"] = _join64(*red["cov_transitions64"])
    if spec is not None and spec.lane_metrics is not None:
        for name, arr in spec.lane_metrics(state.node).items():
            a = np.asarray(arr)
            if a.dtype == np.bool_:
                out[name] = int(a.sum())
            else:
                out[name] = float(a.mean())
    return out


def refill_results(state: SimState) -> dict:
    """Decode a finished refill sweep into per-ADMISSION numpy rows.

    Rows are in admission order (== the seed order handed to
    run_refill), so chunked-vs-refill comparisons are row-for-row. Each
    retired admission's row was harvested on device at its retirement
    step; admissions still mid-flight when the step budget ran out (the
    truncation case — see run_refill) are harvested here from their
    lane's final state, which is exactly what the chunked path reports
    for a lane truncated at max_steps. Also computes the sweep's lane
    OCCUPANCY: busy-lane-steps / total-lane-steps — the continuous-
    batching headline metric (benches/roofline.py reports it)."""
    import numpy as np

    rf = state.refill
    if rf is None:
        raise ValueError("refill_results needs a run_refill final state")
    if np.asarray(state.queue.seeds).ndim != 1:
        raise ValueError(
            "state has a leading device axis (run_refill_sharded) — "
            "decode it with refill_results_sharded"
        )
    # np.array (COPY), not np.asarray: the jax-array views are read-only
    # and the final-harvest loop below writes rows in place
    out = {
        f: np.array(getattr(rf, f))
        for f in (
            "retired", "violated", "deadlocked", "violation_at",
            "violation_epoch", "violation_step", "steps", "events",
            "overflow", "dead_drops", "nonmember_drops", "unsynced_loss",
            "clock", "epoch", "fires",
        )
    }
    for f in ("occ_fired", "cov_bitmap", "cov_hiwater", "cov_transitions"):
        v = getattr(rf, f)
        out[f] = None if v is None else np.array(v)
    A = out["violated"].shape[0]
    L = int(np.asarray(rf.busy).shape[0])
    # final harvest: lanes that ran out of step budget mid-admission
    done = np.asarray(state.done)
    live = ~done
    li = np.asarray(rf.admitted)[live]
    if li.size:
        pairs = {
            "violated": state.violated, "deadlocked": state.deadlocked,
            "violation_at": state.violation_at,
            "violation_epoch": state.violation_epoch,
            "violation_step": state.violation_step,
            "steps": state.steps, "events": state.events,
            "overflow": state.overflow, "dead_drops": state.dead_drops,
            "nonmember_drops": state.nonmember_drops,
            "unsynced_loss": state.unsynced_loss,
            "clock": state.clock, "epoch": state.epoch,
            "fires": state.fires,
        }
        if out["occ_fired"] is not None:
            pairs["occ_fired"] = state.occ_fired
        if out["cov_bitmap"] is not None:
            pairs["cov_bitmap"] = state.cov.bitmap
            pairs["cov_hiwater"] = state.cov.hiwater
            pairs["cov_transitions"] = state.cov.transitions
        for name, src in pairs.items():
            out[name][li] = np.asarray(src)[live]
    iters = int(np.asarray(rf.iters))
    busy = int(np.asarray(rf.busy, np.int64).sum())
    out["admissions"] = A
    out["lanes"] = L
    out["iters"] = iters
    out["busy_lane_steps"] = busy
    out["total_lane_steps"] = iters * L
    out["occupancy"] = busy / max(iters * L, 1)
    out["truncated"] = int(live.sum())
    return out


def devloop_results(state: SimState) -> dict:
    """Decode a finished device-loop window — the ONE host sync the
    window pays (r19, docs/explore.md). Returns the search cursors
    (meta counter, next_fresh, seen_n), the corpus ring + coverage
    union as upload-ready dicts (feed them straight back into
    `init_devloop` for the next window), and one dict per executed
    generation with the archived genomes and per-admission results in
    admission order — exactly what the host `Explorer._fold_part`
    replays to rebuild its corpus."""
    import numpy as np

    dl = state.loop
    if dl is None:
        raise ValueError("devloop_results needs a run_devloop final state")
    rn = int(np.asarray(dl.ring_n))
    gens_done = int(np.asarray(dl.gens_done))
    rf = state.refill
    out = {
        "gens_done": gens_done,
        "target_gens": int(np.asarray(dl.target_gens)),
        "counter": int(np.asarray(dl.counter)),
        "next_fresh": int(np.asarray(dl.next_fresh)),
        "accepts": int(np.asarray(dl.accepts)),
        "seen_n": int(np.asarray(dl.seen_n)),
        "union": np.array(dl.union),
        "ring": {
            "n": rn,
            "bits": np.array(dl.ring_bits)[:rn],
            "seed": np.array(dl.ring_seed)[:rn],
            "off": np.array(dl.ring_off)[:rn],
            "occ": np.array(dl.ring_occ)[:rn],
            "rate": np.array(dl.ring_rate)[:rn],
            "h": np.array(dl.ring_h)[:rn],
        },
        "iters": int(np.asarray(rf.iters)),
        "busy_lane_steps": int(np.asarray(rf.busy, np.int64).sum()),
    }
    arch = {
        f: np.array(getattr(dl, "arch_" + f))
        for f in (
            "seed", "off", "occ", "rate", "h", "origin", "violated",
            "bitmap", "hiwater", "transitions",
        )
    }
    out["gens"] = [
        {f: a[g] for f, a in arch.items()} for g in range(gens_done)
    ]
    return out


def refill_results_sharded(
    state: SimState, admissions: Optional[int] = None,
) -> dict:
    """Decode a finished SHARDED refill sweep (run_refill_sharded) into
    the same per-admission rows `refill_results` produces, in global
    admission (= seed) order: device d's rows are sub-queue d's rows,
    concatenated in device order and stripped of the tail pad
    (`admissions` = the original un-padded seed count).

    This is the segment-end gather the multi-chip determinism contract
    allows: the step itself never crosses devices, so each device's rows
    are bit-identical to a 1-device refill of its sub-queue, and the
    concatenation is bit-identical to the 1-device refill (and chunked)
    rows of the whole list. Occupancy comes back both aggregate and
    per-device (`per_device`): each device's busy-lane-steps over its
    OWN iteration count — the per-chip utilization the mesh_scaling
    bench and the multichip smoke assert on. `lane_steps_per_iter` is
    the aggregate busy-lane-step throughput per sweep iteration
    (busy total / max device iters): the hardware-independent scaling
    number (1 device caps at L; D devices at D * L)."""
    import numpy as np

    if state.refill is None or state.queue is None:
        raise ValueError(
            "refill_results_sharded needs a run_refill_sharded final state"
        )
    lead = np.asarray(state.queue.seeds).ndim
    if lead != 2:
        raise ValueError(
            "state has no leading device axis — use refill_results for "
            "single-device refill sweeps"
        )
    D = int(np.asarray(state.queue.seeds).shape[0])
    per = [
        refill_results(jax.tree_util.tree_map(lambda x, _d=d: x[_d], state))
        for d in range(D)
    ]
    row_fields = [
        "retired", "violated", "deadlocked", "violation_at",
        "violation_epoch", "violation_step", "steps", "events",
        "overflow", "dead_drops", "nonmember_drops", "unsynced_loss",
        "clock", "epoch",
        "fires", "occ_fired", "cov_bitmap", "cov_hiwater",
        "cov_transitions",
    ]
    out: dict = {}
    for f in row_fields:
        if per[0][f] is None:
            out[f] = None
            continue
        rows = np.concatenate([p[f] for p in per])
        out[f] = rows if admissions is None else rows[:admissions]
    A = int(out["violated"].shape[0])
    iters = [p["iters"] for p in per]
    busy = [p["busy_lane_steps"] for p in per]
    total = [p["total_lane_steps"] for p in per]
    out["admissions"] = A
    out["lanes"] = per[0]["lanes"]
    out["devices"] = D
    out["iters"] = max(iters)
    out["busy_lane_steps"] = sum(busy)
    out["total_lane_steps"] = sum(total)
    out["occupancy"] = sum(busy) / max(sum(total), 1)
    # count truncated admissions from the STRIPPED rows (a truncated
    # admission never got its retirement scatter, so its `retired` row
    # is still -1) — the per-device counts include tail-pad duplicates
    out["truncated"] = int((out["retired"] == -1).sum())
    out["per_device"] = [
        {
            "iters": iters[d],
            "busy_lane_steps": busy[d],
            "total_lane_steps": total[d],
            "occupancy": busy[d] / max(total[d], 1),
        }
        for d in range(D)
    ]
    out["lane_steps_per_iter"] = sum(busy) / max(max(iters), 1)
    return out


def summarize_refill(res: dict) -> dict:
    """summarize()'s vocabulary over refill_results rows: the same keys,
    aggregated over ADMISSIONS, so run_batch's chunk-total folding and
    the chaos-coverage report read both paths identically. (lane_metrics
    diagnostics need final node state, which a refilled lane no longer
    holds — the refill path reports the engine counters only.)"""
    import numpy as np

    A = int(res["admissions"])
    violated = res["violated"]
    steps_total = int(res["steps"].astype(np.int64).sum())
    vt_total_us = int(
        res["epoch"].astype(np.int64).sum() * REBASE_US
        + res["clock"].astype(np.int64).sum()
    )
    out = {
        "lanes": A,
        "violations": int(violated.sum()),
        "violation_lanes": np.nonzero(violated)[0].tolist()[:32],
        "deadlocked": int(res["deadlocked"].sum()),
        "total_events": int(res["events"].astype(np.int64).sum()),
        "total_overflow": int(res["overflow"].astype(np.int64).sum()),
        "total_dead_drops": int(res["dead_drops"].astype(np.int64).sum()),
        "total_nonmember_drops": int(
            res["nonmember_drops"].astype(np.int64).sum()
        ),
        "total_unsynced_loss": int(
            res["unsynced_loss"].astype(np.int64).sum()
        ),
        "mean_steps": steps_total / A,
        "mean_virtual_secs": vt_total_us / A / 1e6,
        "occupancy": round(float(res["occupancy"]), 4),
    }
    if out["violations"]:
        out["first_violation_step"] = int(
            res["violation_step"][violated].min()
        )
    fires = res["fires"].astype(np.int64).sum(axis=0)
    for i, name in enumerate(FIRE_KINDS):
        out[f"fires_{name}"] = int(fires[i])
    if res.get("occ_fired") is not None:
        bits = (
            res["occ_fired"][:, :, None]
            >> np.arange(32, dtype=np.uint32)[None, None, :]
        ) & np.uint32(1)
        occ_counts = bits.sum(axis=0)
        for row, clause in enumerate(OCC_CLAUSES):
            for k in range(32):
                n = int(occ_counts[row, k])
                if n:
                    out[f"occfires_{clause}_k{k}"] = n
    if res.get("cov_bitmap") is not None:
        union = np.bitwise_or.reduce(res["cov_bitmap"], axis=0)
        out["coverage_bits"] = int(
            np.unpackbits(union.view(np.uint8)).sum()
        )
        out["coverage_hiwater"] = int(res["cov_hiwater"].max())
        out["coverage_transitions"] = int(
            res["cov_transitions"].astype(np.int64).sum()
        )
    return out
