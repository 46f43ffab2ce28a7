"""Device-resident round telemetry — the records that ride the scan carry.

Two pytrees, both built from the same template as
``repro.privacy.accountant.PrivacyAccountant`` (NamedTuples of device
scalars with a ``zero()`` constructor and traceable update), so a traced
federation observes itself without a single extra host sync:

* :class:`RoundTelemetry` — ONE round's record: pilot id, participation /
  fault / degradation counts, the cost numerator+denominator the master
  actually averaged, and the public wire tags (modulus, fanout, levels).
  ``WirePath.round_step`` emits it in ``info["telemetry"]``; ``lax.scan``
  stacks it like every other info leaf and the driver fetches ALL rounds in
  the one post-run transfer it already performs.
* :class:`TelemetryCarry` — cumulative totals riding
  ``RoundState.telemetry``: checkpointed with the history buffers, so a
  resumed run continues its counters exactly where the interrupted run
  stopped.

Counts, not bytes, on purpose: float32 holds integers exactly only up to
2**24 and the wire totals (``model_bytes * (N+1)``-shaped quantities)
blow through that for any real model. The device records exact int32
counts; ``repro.telemetry.trace`` derives byte totals on the host through
``repro.core.protocol`` — where they are cross-checked against the
simulator's independent ledger math and any divergence raises
:class:`~repro.telemetry.trace.TelemetryMismatch`.

Everything here is plain ``jnp`` reductions over (N,) operands the round
already computed — no new kernel launches, no host syncs, and the jaxpr
the leakage audit sees gains only scalar outputs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Fault-code constants mirrored from repro.fed.faults (importing it here
# would cycle through repro.fed.__init__ back into rounds); the identity is
# pinned by tests/test_telemetry.py.
FAULT_NONE = 0
DROP_BEFORE = 1


class RoundTelemetry(NamedTuple):
    """One round's device-resident record — int32/float32 scalars only.

    ``cost_sum``/``weight_sum`` are the numerator and denominator of the
    size-weighted cost average over the workers whose report the master
    USED (sampled, surviving, in a viable sibling group) — the host divides
    and applies the all-reports-lost carry rule, so the trace reports the
    exact average the protocol acted on.
    """
    round: jax.Array          # absolute 1-based round index
    pilot: jax.Array          # k* of this round
    n_sampled: jax.Array      # participation-mask popcount
    n_used: jax.Array         # reports the master used (post fault/viability)
    n_dead: jax.Array         # sampled workers that faulted this round
    n_pre_uplink: jax.Array   # dead BEFORE uplink (bytes never spent)
    n_recovered: jax.Array    # dead in viable groups (seeds reconstructable)
    n_degraded: jax.Array     # live survivors excluded by group viability
    cost_sum: jax.Array       # sum(size_k * cost_k) over used workers
    weight_sum: jax.Array     # sum(size_k) over used workers
    modulus_bits: jax.Array   # wire modulus tag (0 = plain wire)
    fanout: jax.Array         # tree fanout tag (0 = flat aggregation)
    levels: jax.Array         # resolved tree depth tag (0 = flat)


class TelemetryCarry(NamedTuple):
    """Cumulative totals riding ``RoundState.telemetry`` (scan carry +
    checkpoint): a resumed federation's counters continue bitwise."""
    rounds: jax.Array
    sampled: jax.Array
    used: jax.Array
    dead: jax.Array
    pre_uplink: jax.Array
    recovered: jax.Array
    degraded: jax.Array
    cost_sum: jax.Array

    @classmethod
    def zero(cls) -> "TelemetryCarry":
        # One buffer per field: the carry is donated into jitted round
        # programs, and a buffer donated twice is an error on the chip.
        z = lambda: jnp.zeros((), jnp.int32)
        return cls(rounds=z(), sampled=z(), used=z(), dead=z(),
                   pre_uplink=z(), recovered=z(), degraded=z(),
                   cost_sum=jnp.zeros((), jnp.float32))

    def add(self, rec: RoundTelemetry) -> "TelemetryCarry":
        """Fold one round's record into the running totals (traceable)."""
        return TelemetryCarry(
            rounds=self.rounds + 1,
            sampled=self.sampled + rec.n_sampled,
            used=self.used + rec.n_used,
            dead=self.dead + rec.n_dead,
            pre_uplink=self.pre_uplink + rec.n_pre_uplink,
            recovered=self.recovered + rec.n_recovered,
            degraded=self.degraded + rec.n_degraded,
            cost_sum=self.cost_sum + rec.cost_sum)


def _count(x) -> jax.Array:
    return jnp.sum(x.astype(jnp.int32)).astype(jnp.int32)


def build_round_record(*, t, k_star, n: int, costs, sizes, mask=None,
                       codes=None, sel_mask=None, dead_eff=None,
                       modulus_bits: int = 0, fanout: int = 0,
                       levels: int = 0) -> RoundTelemetry:
    """Assemble one round's :class:`RoundTelemetry` from operands the round
    computed anyway.

    ``mask`` — the (N,) participation row (None = all sampled); ``codes`` —
    the round's int32 fault codes (None = no fault plan); ``sel_mask`` —
    the post-fault/viability selection mask the pilot and cost carry used
    (None = everyone sampled is used); ``dead_eff`` — the masked wire's
    recoverable-dead mask from ``recovery.effective_masks`` (None off the
    recovery path). All may be traced; the result is scalars only.
    """
    costs = jnp.asarray(costs, jnp.float32)
    sizes = jnp.asarray(sizes, jnp.float32)
    pm = (jnp.ones((n,), jnp.float32) if mask is None
          else (jnp.asarray(mask, jnp.float32) > 0).astype(jnp.float32))
    if codes is None:
        live = pm
        n_dead = jnp.asarray(0, jnp.int32)
        n_pre = jnp.asarray(0, jnp.int32)
    else:
        codes = jnp.asarray(codes, jnp.int32)
        ok = (codes == FAULT_NONE).astype(jnp.float32)
        live = pm * ok
        n_dead = _count(pm * (1.0 - ok))
        n_pre = _count(pm * (codes == DROP_BEFORE).astype(jnp.float32))
    used = (live if sel_mask is None
            else (jnp.asarray(sel_mask, jnp.float32) > 0
                  ).astype(jnp.float32))
    n_used = _count(used)
    n_recovered = (jnp.asarray(0, jnp.int32) if dead_eff is None
                   else _count(jnp.asarray(dead_eff) > 0))
    return RoundTelemetry(
        round=jnp.asarray(t, jnp.int32),
        pilot=jnp.asarray(k_star, jnp.int32),
        n_sampled=_count(pm),
        n_used=n_used,
        n_dead=n_dead,
        n_pre_uplink=n_pre,
        n_recovered=n_recovered,
        n_degraded=_count(live) - n_used,
        cost_sum=jnp.sum(costs * sizes * used),
        weight_sum=jnp.sum(sizes * used),
        modulus_bits=jnp.asarray(modulus_bits, jnp.int32),
        fanout=jnp.asarray(fanout, jnp.int32),
        levels=jnp.asarray(levels, jnp.int32))
