"""Deterministic fault injection for the federated round.

A :class:`FaultPlan` is a seeded, stateless description of the failure
axis: each round, each worker independently draws one fault code from a
FAULT_DOMAIN counter stream (the same lowbias32 chain every other stream
in the system uses), so the schedule is a pure function of
``(plan.seed, round, worker)`` — both simulator drivers, ``scan_rounds``
and the distributed mesh realize bitwise the same faults, and a resumed
run replays its schedule exactly.

Three fault types, matching the cross-device failure model:

* ``DROP_BEFORE`` — the worker dies before its uplink: nothing arrives,
  no uplink bytes are spent.
* ``DROP_AFTER`` — the worker dies after committing its masked uplink:
  its words arrived but the protocol must discard them (the worker is
  gone; its contribution is excluded from the survivors-only aggregate).
  Uplink bytes were spent.
* ``STRAGGLER`` — the uplink exceeds the round timeout: discarded like a
  death, but the bytes were spent.

All three are identical to the AGGREGATION math — the worker's row leaves
the sum, and on the masked wire its uncancelled pairwise-mask residue is
repaired from reconstructed seeds (``repro.privacy.recovery``) — they
differ only in byte accounting. Fault codes are int32 on purpose: the
masked-wire audit forbids int8/uint8 tensors anywhere in the round
program, and fault codes are public control metadata, not wire payload.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.privacy import masking as pvm

FAULT_NONE = 0
DROP_BEFORE = 1     # died before uplink: no bytes spent, row excluded
DROP_AFTER = 2      # died after uplink: bytes spent, row excluded + repair
STRAGGLER = 3       # exceeded timeout: bytes spent, row excluded + repair


@dataclass(frozen=True)
class FaultPlan:
    """Per-round i.i.d. fault probabilities, realized deterministically.

    Probabilities are per worker per round; they must sum to at most 1
    (the remainder is the no-fault outcome). ``seed`` namespaces the
    fault stream — independent of mask/RR/recovery streams by domain
    separation even at equal seeds.
    """
    seed: int = 0
    drop_before_uplink: float = 0.0
    drop_after_uplink: float = 0.0
    straggler: float = 0.0

    def __post_init__(self):
        for name in ("drop_before_uplink", "drop_after_uplink", "straggler"):
            p = getattr(self, name)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"{name} must be in [0, 1], got {p}")
        if self.total > 1.0:
            raise ValueError(
                f"fault probabilities sum to {self.total} > 1")

    @property
    def total(self) -> float:
        return (self.drop_before_uplink + self.drop_after_uplink
                + self.straggler)

    @property
    def active(self) -> bool:
        return self.total > 0.0

    def codes(self, t, n: int) -> jnp.ndarray:
        """The (n,) int32 fault codes of round ``t`` (``t`` may be traced).

        One uniform draw per worker from the FAULT_DOMAIN stream, split by
        cumulative thresholds — so lowering one probability to zero never
        reshuffles the draws of the remaining fault types.
        """
        u = pvm.stream_key(self.seed, jnp.arange(n), t,
                           domain=pvm.FAULT_DOMAIN)
        r = u.astype(jnp.float32) * jnp.float32(2.0 ** -32)
        p1 = jnp.float32(self.drop_before_uplink)
        p2 = p1 + jnp.float32(self.drop_after_uplink)
        p3 = p2 + jnp.float32(self.straggler)
        return jnp.where(
            r < p1, DROP_BEFORE,
            jnp.where(r < p2, DROP_AFTER,
                      jnp.where(r < p3, STRAGGLER,
                                FAULT_NONE))).astype(jnp.int32)

    def alive(self, t, n: int) -> jnp.ndarray:
        """(n,) float32 survival mask of round ``t``: 1 where no fault."""
        return (self.codes(t, n) == FAULT_NONE).astype(jnp.float32)

    def code_matrix(self, t0: int, rounds: int, n: int) -> np.ndarray:
        """(rounds, n) int32 host copy of rounds ``t0 .. t0+rounds-1``:
        row ``i`` is ``codes(t0 + i, n)`` bitwise.

        The same :meth:`codes` hash broadcast over a column of rounds, in
        one compiled dispatch and one fetch. ``t0`` is traced, so every
        later schedule of the same ``(rounds, n)`` reuses the program."""
        return np.asarray(_code_matrix(self, np.uint32(t0), rounds, n))


@partial(jax.jit, static_argnums=(0, 2, 3))
def _code_matrix(plan: FaultPlan, t0, rounds: int, n: int) -> jax.Array:
    t = t0 + jnp.arange(rounds, dtype=jnp.uint32)
    return plan.codes(t[:, None], n)
