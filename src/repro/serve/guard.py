"""Numerical watchdog: per-slot non-finite logit detection, fused into
the batched sampling call.

A poisoned stream (int8 scale overflow, corrupted block, a model bug)
must degrade **one** request, not the engine: NaN/Inf logits in one
slot row would otherwise flow through the shared
``jax.random.categorical`` call and, worse, keep writing garbage into
the shared KV pool every step.  :func:`sample_and_flag` is the
one-device-call answer — the same batched greedy/temperature sampler
the runner always ran, plus a per-row ``all(isfinite)`` reduction fused
into the same jitted computation.  The flags ride back on the single
host transfer the engine already pays for the sampled tokens, so the
happy path gains **no extra host syncs** and no second kernel launch.

Guarantees the chaos suite pins down:

* a flagged row's token is sampled from zeroed logits (deterministic,
  finite — never lets a NaN pick an out-of-range token id); the engine
  quarantines the stream before the token is ever appended;
* *clean* rows are bit-identical to the unguarded sampler: their logits
  pass through untouched, per-row argmax is independent across rows,
  and ``jax.random.categorical``'s gumbel noise depends only on
  ``(key, shape)`` — so quarantining slot ``i`` never perturbs slot
  ``j``'s greedy (or seeded-sampling) stream.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import tracing

__all__ = ["nonfinite_rows", "sample_and_flag", "ReplicaGuard",
           "ReplicaGuardPolicy"]


def nonfinite_rows(logits: jax.Array) -> jax.Array:
    """``(rows, V) -> (rows,)`` bool: True where ANY logit in the row is
    NaN/Inf.  One fused reduction; jit-safe."""
    return ~jnp.all(jnp.isfinite(logits), axis=-1)


def sample_and_flag(key: jax.Array, logits: jax.Array,
                    temps: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched sampling with a fused watchdog.

    ``logits (rows, V)``, ``temps (rows,)`` -> ``(tokens (rows,) int,
    bad (rows,) bool)``.  Greedy rows (``temps == 0``) take the per-row
    argmax; temperature rows draw categorically — exactly the runner's
    historical ``_sample_all`` on clean rows.  Bad rows sample from
    zeroed logits (token 0 under greedy) and are flagged for the engine
    to quarantine.
    """
    with jax.named_scope(tracing.SAMPLE):
        bad = nonfinite_rows(logits)
        clean = jnp.where(bad[:, None], 0.0, logits)
        greedy = jnp.argmax(clean, axis=-1)
        safe = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.random.categorical(key, clean / safe[:, None],
                                         axis=-1)
        return jnp.where(temps > 0, sampled, greedy), bad


# ---------------------------------------------------------------------------
# Replica-level health (the per-stream watchdog's fleet twin)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReplicaGuardPolicy:
    """When the router pulls a whole replica out of rotation.

    The per-stream watchdog above quarantines *one* poisoned request; a
    replica that keeps producing casualties (a corrupted pool, a bad
    device) or whose ``step`` raises outright is a fleet problem — its
    queued work should move to healthy replicas instead of feeding a
    failing engine."""
    #: per-stream quarantines before the replica itself is suspect
    max_quarantined: int = 4
    #: uncaught ``step()`` exceptions tolerated (0 = first one trips)
    max_step_failures: int = 0


class ReplicaGuard:
    """Health verdict over one replica engine.  Trips once, stays
    tripped (re-admitting a flapping replica mid-evacuation would
    split-brain its queue); the router guarantees at least one replica
    always stays routable regardless of verdicts."""

    def __init__(self, policy: ReplicaGuardPolicy | None = None):
        self.policy = policy or ReplicaGuardPolicy()
        self.step_failures = 0
        self.last_error: BaseException | None = None
        self.tripped: str | None = None

    def record_failure(self, exc: BaseException) -> None:
        """Count one uncaught step exception."""
        self.step_failures += 1
        self.last_error = exc

    def healthy(self, engine) -> bool:
        if self.tripped is not None:
            return False
        if self.step_failures > self.policy.max_step_failures:
            self.tripped = "step_failures"
        elif engine.quarantined >= max(1, self.policy.max_quarantined):
            self.tripped = "quarantined_streams"
        return self.tripped is None
