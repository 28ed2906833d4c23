"""Fault tolerance & elasticity planning (pure functions → unit-testable).

At thousand-node scale the framework must survive pod/host loss without
operator intervention.  The moving parts:

* **Work units.**  MGBC's source *rounds* (core/scheduler.py) and LM
  *steps* are idempotent and additive, so recovery = re-issue, never
  partial-state repair.
* **Elastic re-mesh.**  ``plan_elastic_remesh`` maps a device loss to a
  new mesh shape (shrink the replica/data axis first — the model axes
  encode weight layouts and are expensive to change) and emits the
  checkpoint-reload plan.
* **Straggler mitigation.**  ``StragglerPolicy`` tracks per-worker round
  times and flags rounds for speculative re-execution (backup tasks)
  when a worker exceeds ``factor``× the running median.  Because BC
  accumulation is additive per-round, duplicate completions are resolved
  by a "first result wins" commit in the round ledger.  The *integrated*
  version of this idea — per-replica ledgers, EWMA-threshold detection,
  steal/re-deal of pending rounds — is the shared round loop's
  ``straggler=`` policy (:data:`repro.core.driver.STRAGGLER_POLICIES`).
* **Round ledger.**  ``RoundLedger`` records committed rounds so a
  restart (or a duplicated speculative execution) never double-counts —
  this is what makes BC exact across failures.
"""
from __future__ import annotations

import collections
import dataclasses
import statistics

__all__ = [
    "MeshPlan",
    "plan_elastic_remesh",
    "StragglerPolicy",
    "RoundLedger",
    "BCCheckpoint",
    "schedule_fingerprint",
    "TransientRoundError",
    "ReplicaLostError",
    "IntegrityError",
    "is_transient_error",
    "TRANSIENT_STATUSES",
]


class IntegrityError(RuntimeError):
    """A round output failed its integrity audit beyond recovery.

    Raised by the driver when a block keeps failing the ABFT checksum /
    claim / output-domain audits (``integrity="audit"|"checksum"``) after
    the re-dispatch budget and the clean-fallback recompute are both
    exhausted — finite-but-wrong data that would otherwise silently enter
    the BC accumulator.  Never retryable: by construction every retry
    path was already tried.
    """


class TransientRoundError(RuntimeError):
    """A round failure worth retrying on the same device set.

    Raised by the chaos harness (:mod:`repro.distributed.chaos`) to model
    the transient XLA/runtime failures a long-lived service sees; the
    driver's per-block retry loop (:class:`repro.core.driver.BCDriver`)
    treats it — and a runtime error whose status is in
    :data:`TRANSIENT_STATUSES` — as retryable within the retry budget.
    Any other exception propagates immediately.
    """


class ReplicaLostError(RuntimeError):
    """A sub-cluster replica's devices are gone (preemption, host loss).

    Carries the lost ``replica`` index.  Not retryable in place: the
    driver's multi-ledger loop consults :func:`plan_elastic_remesh`,
    merges the dead replica's ledger into a survivor's, re-deals its
    pending rounds and continues on the surviving lanes (the dead lane
    is dealt only padding from then on).
    """

    def __init__(self, replica: int, message: str | None = None):
        super().__init__(message or f"replica {replica} lost")
        self.replica = int(replica)


#: Runtime status codes retried in place alongside
#: :class:`TransientRoundError`.  The runtime raises every device, compile
#: and allocation failure as one :class:`jax.errors.JaxRuntimeError`
#: whose message starts with its status; only UNAVAILABLE (a peer or
#: link that may come back) is transient.  RESOURCE_EXHAUSTED, INTERNAL,
#: INVALID_ARGUMENT and the rest fail the same way on every retry, so
#: retrying them would only hide a device fault behind a slower run.
TRANSIENT_STATUSES = ("UNAVAILABLE",)


def is_transient_error(exc: BaseException) -> bool:
    """True when a round failure should be retried in place."""
    import jax

    if isinstance(exc, TransientRoundError):
        return True
    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    status = str(exc).split(":", 1)[0].strip()
    return status in TRANSIENT_STATUSES


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]
    reload_from_checkpoint: bool
    reshard_params: bool
    note: str


def plan_elastic_remesh(
    current_shape: tuple[int, ...],
    axes: tuple[str, ...],
    devices_lost: int,
) -> MeshPlan:
    """Shrink policy: drop whole replica ('pod') groups first, then halve
    the 'data' axis; never touch 'model' (weight layout)."""
    shape = list(current_shape)
    n = 1
    for s in shape:
        n *= s
    remaining = n - devices_lost
    if remaining <= 0:
        raise ValueError("no devices left")

    # drop pods while a whole pod is gone
    if "pod" in axes:
        pod_ax = axes.index("pod")
        per_pod = n // shape[pod_ax]
        pods_left = remaining // per_pod
        if pods_left >= 1:
            if pods_left != shape[pod_ax]:
                shape[pod_ax] = pods_left
                return MeshPlan(
                    shape=tuple(shape),
                    axes=axes,
                    reload_from_checkpoint=False,  # replicas hold full state
                    reshard_params=False,
                    note=f"dropped to {pods_left} pods; surviving replicas "
                    f"re-deal the remaining source rounds",
                )
            return MeshPlan(tuple(shape), axes, False, False, "no change")
    # halve data axis until it fits
    data_ax = axes.index("data")
    while True:
        prod = 1
        for s in shape:
            prod *= s
        if prod <= remaining:
            break
        if shape[data_ax] % 2 != 0 or shape[data_ax] == 1:
            raise ValueError(f"cannot shrink mesh {current_shape} to {remaining}")
        shape[data_ax] //= 2
    return MeshPlan(
        shape=tuple(shape),
        axes=axes,
        reload_from_checkpoint=True,
        reshard_params=True,
        note="data axis halved; params resharded from checkpoint, "
        "global batch rescaled",
    )


class StragglerPolicy:
    """Median-based speculative re-execution (MapReduce backup tasks).

    Standalone detector for external orchestration; the BC round loop
    itself uses the integrated multi-ledger scheduler
    (``BCDriver(straggler="steal"|"redeal")``, core/driver.py)."""

    def __init__(self, factor: float = 2.0, min_samples: int = 5, window: int = 512):
        self.factor = factor
        self.min_samples = min_samples
        # bounded history: a long-lived service observes millions of
        # rounds and the median only needs the recent regime anyway
        self.times: collections.deque[float] = collections.deque(maxlen=window)

    def observe(self, seconds: float) -> None:
        self.times.append(seconds)

    def should_speculate(self, elapsed: float) -> bool:
        if len(self.times) < self.min_samples:
            return False
        return elapsed > self.factor * statistics.median(self.times)


class RoundLedger:
    """Exactly-once commit of additive work units (BC rounds / steps).

    The shared round loop (:class:`repro.core.driver.BCDriver`) consumes
    a ledger directly: committed rounds are re-dealt as inert padding
    columns, so a speculatively duplicated round is accumulated exactly
    once.  The ledger is deliberately *in-memory only* — a round is
    marked committed at dispatch, before its contribution is anywhere
    durable, so persisting the ledger alone would drop work on a crash.
    Durable kill-and-resume is :class:`BCCheckpoint`, which snapshots
    the committed set together with the matching partial BC sums.
    """

    def __init__(self):
        self._committed: set[int] = set()

    def try_commit(self, round_id: int) -> bool:
        """True if this result should be accumulated (first completion)."""
        if round_id in self._committed:
            return False
        self._committed.add(round_id)
        return True

    def is_committed(self, round_id: int) -> bool:
        """Read-only commit check (the multi-ledger driver consults every
        replica's ledger before committing into one — first commit wins)."""
        return round_id in self._committed

    def merge(self, other: "RoundLedger") -> int:
        """Absorb (move) another ledger's committed set into this one.

        The replica-loss re-mesh path: the dead replica's commits must
        stay committed (exactly-once), so a survivor's ledger takes them
        over and the dead ledger is emptied — the committed *union*
        across ledgers is unchanged, only the attribution moves.
        Returns the number of rounds newly committed here.
        """
        added = len(other._committed - self._committed)
        self._committed |= other._committed
        other._committed = set()
        return added

    def pending(self, total_rounds: int) -> list[int]:
        return [r for r in range(total_rounds) if r not in self._committed]

    def state(self) -> list[int]:
        return sorted(self._committed)

    @classmethod
    def from_state(cls, committed: list[int]) -> "RoundLedger":
        led = cls()
        led._committed = set(committed)
        return led


# BCCheckpoint — the durable (partial BC, n_s, committed rounds) triple —
# lives with the rest of the durable-state code in
# repro/checkpoint/checkpointer.py since it grew per-replica ledger
# namespacing; re-exported here because this is where the ledger protocol
# it completes is defined (and where existing callers import it from).
from repro.checkpoint.checkpointer import BCCheckpoint  # noqa: E402,F401


def schedule_fingerprint(n: int, schedule) -> str:
    """Content hash tying a checkpoint to one (graph, schedule) pair."""
    import zlib

    crc = 0
    for rnd in schedule.rounds:
        crc = zlib.crc32(rnd.sources.tobytes(), crc)
        crc = zlib.crc32(rnd.derived.tobytes(), crc)
    return (
        f"n{n}_b{schedule.batch_size}_k{schedule.derived_per_round}_"
        f"r{len(schedule.rounds)}_{crc:08x}"
    )
