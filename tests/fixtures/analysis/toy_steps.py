"""Planted jaxpr-rule violations as toy step programs.

Each function is a deliberately broken miniature of the engine pattern a
Layer-1 rule guards; tests/test_analysis.py traces them with
jax.make_jaxpr and asserts the matching rule FIRES (and that its clean
twin passes). Kept tiny so tracing is milliseconds."""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from madsim_tpu.tpu import prng


# ------------------------------------------------------------- callbacks

def clean_step(x):
    return x * 2


def callback_step(x):
    jax.debug.print("x = {}", x)  # host sync inside the step
    return x * 2


# ------------------------------------------------------------- rng taint

def pure_schedule_draw(key0, k):
    # victim draw indexed by the occurrence counter: the legal pattern
    return prng.randint(key0, 203, 0, 5, index=k)


def impure_schedule_draw(key0, clock):
    # drawing the victim at an index derived from the lane CLOCK couples
    # the fault schedule to the trajectory — the exact bug class
    return prng.randint(key0, 203, 0, 5, index=clock)


def impure_draw_inside_jit(key0, clock):
    # the same bug hidden behind an inline-jitted helper: the mix eqns
    # live in a pjit sub-jaxpr, and the witness must still name the
    # clock leaf via the enclosing top-level equation
    return jax.jit(
        lambda k, c: prng.randint(k, 203, 0, 5, index=c)
    )(key0, clock)


def clean_funnel(key, payload):
    new_key = prng.fold(key, 1)
    coin = prng.uniform(prng.fold(key, 7), 33)
    return new_key, coin + payload[..., 0]


def contaminated_funnel(key, payload):
    # folding protocol state INTO the carried key poisons every
    # downstream step's draws
    new_key = prng.fold(key, payload[..., 0])
    return new_key, jnp.zeros_like(payload[..., 0])


# ------------------------------------------------------------ leaky refill

def clean_refill(key, key0, done, qseeds, cursor):
    # the legal continuous-batching refill: a retiring lane's NEW chain
    # roots derive from its admitted queue seed alone (key_from(seed)),
    # exactly what a fresh chunked lane's _init would draw — survivors
    # keep their chains untouched (the select's bool mask carries no
    # value taint)
    ji = done.astype(jnp.int32)
    adm = jnp.clip(cursor + jnp.cumsum(ji) - ji, 0, qseeds.shape[0] - 1)
    fresh = prng.key_from(jnp.take(qseeds, adm, axis=0))
    new_key = jnp.where(done, fresh, prng.fold(key, 1))
    new_key0 = jnp.where(done, fresh, key0)
    victim = prng.randint(new_key0, 203, 0, 5)  # schedule draw: key0 only
    return new_key, new_key0, victim


def leaky_refill(key, key0, done, qseeds, cursor):
    # the planted refill leak: the refilled lane's init FOLDS A
    # SURVIVOR'S RUNNING KEY CHAIN into its new schedule root — its
    # fault schedule is then a function of how far other work happened
    # to have run, not of (seed, clause, occurrence); rng-taint must
    # catch the key0-rooted draw mixing chain material
    ji = done.astype(jnp.int32)
    adm = jnp.clip(cursor + jnp.cumsum(ji) - ji, 0, qseeds.shape[0] - 1)
    fresh = prng.key_from(jnp.take(qseeds, adm, axis=0))
    contaminated = prng.fold(fresh, jnp.roll(key, 1))  # survivor's chain
    new_key = jnp.where(done, contaminated, prng.fold(key, 1))
    new_key0 = jnp.where(done, contaminated, key0)
    victim = prng.randint(new_key0, 203, 0, 5)  # a schedule draw off it
    return new_key, new_key0, victim


# ------------------------------------------------ leaky device-loop ring

def clean_devloop_ring(key, meta_key, counter, ring_seed, ring_n, done):
    # the legal device-loop generation boundary (r19): a mutant's new
    # schedule root derives from a corpus-ring PARENT seed alone, picked
    # by a MetaRng draw — (meta_key, counter) is the host MetaRng's
    # murmur cursor, deliberately disjoint from every lane's schedule
    # key, and survivors' running chains never enter the ring
    d0 = prng.bits(meta_key, 301, index=counter)
    pidx = jnp.clip(
        (d0 % jnp.maximum(ring_n, 1).astype(jnp.uint32)).astype(jnp.int32),
        0, ring_seed.shape[0] - 1,
    )
    root = prng.key_from(ring_seed[pidx])
    new_key = jnp.where(done, root, prng.fold(key, 1))
    victim = prng.randint(root, 203, 0, 5)  # schedule draw: ring seed only
    return new_key, victim


def leaky_ring(key, meta_key, counter, ring_seed, ring_n, done):
    # the planted device-loop leak: the corpus-ring scatter FOLDS A
    # SURVIVOR LANE'S RUNNING KEY CHAIN into the stored seed — every
    # mutant descended from that row then runs a fault schedule that is
    # a function of how far other lanes happened to have run, not of
    # (seed, clause, occurrence); rng-taint must catch the ring-rooted
    # draw mixing chain (KEY2) material
    leaked = ring_seed.at[0].set(prng.fold(ring_seed[0], key[0]))
    d0 = prng.bits(meta_key, 301, index=counter)
    pidx = jnp.clip(
        (d0 % jnp.maximum(ring_n, 1).astype(jnp.uint32)).astype(jnp.int32),
        0, ring_seed.shape[0] - 1,
    )
    root = prng.key_from(leaked[pidx])
    new_key = jnp.where(done, root, prng.fold(key, 1))
    victim = prng.randint(root, 203, 0, 5)  # a schedule draw off it
    return new_key, victim


# ------------------------------------------------- sharded collectives

def clean_sharded_segment(mesh):
    """The legal multi-chip refill shape: each device steps its own
    block, no cross-device primitive anywhere (engine._sharded_segment's
    contract, docs/multichip.md)."""
    P = jax.sharding.PartitionSpec

    def seg(x):
        return x * 2 + 1

    return jax.shard_map(
        seg, mesh=mesh, in_specs=(P(mesh.axis_names[0]),),
        out_specs=P(mesh.axis_names[0]), check_vma=False,
    )


def leaky_sharded_segment(mesh):
    """The planted multi-chip leak: a psum inside the sharded segment —
    every device's step now depends on every other device's state, so
    per-device rows stop being the pure per-seed function the mesh
    bit-identity contract requires. The lane-independence rule's
    collective walk must flag it by exact primitive name."""
    P = jax.sharding.PartitionSpec
    axis = mesh.axis_names[0]

    def seg(x):
        return x + jax.lax.psum(x.sum(), axis)

    return jax.shard_map(
        seg, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_vma=False,
    )


# ----------------------------------------------------------------- dtype

def time_f32_step(timer):
    # the r1 clock-skew bug: f32 multiply on a time value loses integer
    # microseconds past 2^24 us
    return (timer.astype(jnp.float32) * jnp.float32(1.00005)).astype(
        jnp.int32
    )


def time_int_step(timer):
    from madsim_tpu.tpu.engine import scale_delay_ppm

    return scale_delay_ppm(timer, 50)


# ------------------------------------------------------ lane independence

def lane_coupled_step(x):
    # subtracting a cross-lane mean entangles every lane with the batch
    return x - x.mean(axis=0, keepdims=True)


def lane_coupled_rhs_matmul(m, x):
    # x: [L, F]; contracting the LANE axis on the RHS operand
    return m @ x


def lane_coupled_transposed(x):
    # the lane axis moved to position 1 by the transpose, then contracted
    return x.T @ x


def lane_local_step(x):
    return x - x.mean(axis=1, keepdims=True)


# -------------------------------------------------------------- donation

class ToyHot(NamedTuple):
    key: Any
    x: Any


class ToyCold(NamedTuple):
    acc: Any


class ToyConst(NamedTuple):
    key0: Any
    scale: Any


HOT_NAMES = ("hot.key", "hot.x")
COLD_NAMES = ("cold.acc",)
CONST_NAMES = ("const.key0", "const.scale")


def toy_state(lanes: int = 13):
    hot = ToyHot(
        key=jax.ShapeDtypeStruct((lanes,), jnp.uint32),
        x=jax.ShapeDtypeStruct((lanes,), jnp.int32),
    )
    cold = ToyCold(acc=jax.ShapeDtypeStruct((lanes,), jnp.int32))
    const = ToyConst(
        key0=jax.ShapeDtypeStruct((lanes,), jnp.uint32),
        scale=jax.ShapeDtypeStruct((), jnp.int32),
    )
    return hot, cold, const


def good_toy_step(hot, cold, const):
    coin = (prng.bits(const.key0, 5) & 1).astype(jnp.int32)
    x2 = hot.x + const.scale + coin
    return ToyHot(prng.fold(hot.key, 1), x2), ToyCold(cold.acc + x2)


def widened_toy_step(hot, cold, const):
    # hot.x leaves the step as f32: no output matches its buffer, so the
    # leaf cannot be donated — the donation-coverage regression
    x2 = (hot.x + const.scale).astype(jnp.float32)
    return ToyHot(prng.fold(hot.key, 1), x2), ToyCold(cold.acc)


def good_toy_run(hot, cold, const, n=4):
    def body(carry):
        h, c, i = carry
        h2, c2 = good_toy_step(h, c, const)
        return h2, c2, i + 1

    def cond(carry):
        return carry[2] < n

    h, c, _ = jax.lax.while_loop(cond, body, (hot, cold, jnp.int32(0)))
    return h, c


def leaky_toy_run(hot, cold, const, n=4):
    # const.scale rides the while carry: donation rotates a loop
    # invariant through fresh buffers every segment — the regression the
    # hot/cold/const split can silently lose. The body rewrites s (as a
    # step that threads the whole state through would): a carry returned
    # untouched is hoisted out of the loop by jax's while_loop itself.
    def body(carry):
        h, c, s, i = carry
        h2, c2 = good_toy_step(h, c, ToyConst(const.key0, s))
        return h2, c2, s + jnp.zeros_like(s), i + 1

    def cond(carry):
        return carry[3] < n

    h, c, _, _ = jax.lax.while_loop(
        cond, body, (hot, cold, const.scale, jnp.int32(0))
    )
    return h, c
