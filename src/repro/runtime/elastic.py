"""Elastic runtime: membership-aware re-planning, checkpoint re-sharding,
and mid-trajectory recovery.

ZeRO-Infinity's pitch (paper Sec. 1) is extreme-scale training on clusters
the user does not fully control; at that scale membership changes mid-run —
a node dies, a preempted host rejoins. This module makes recovery a
first-class subsystem instead of a restart script, built as a state machine
over the pieces that already exist in the repo:

  detect   — ``ClusterMembership`` tracks which launch-time ranks are alive
             (simulated here: ``ChaosSchedule`` events and the env-driven
             ``FailureInjector`` stand in for real health checks) and
             projects the surviving cluster back onto a ``HardwareSpec``
             (``with_membership``: fewer devices, proportionally less
             aggregate DRAM/NVMe).
  re-plan  — every incarnation re-runs ``plan_run`` against the surviving
             hardware: tiers / window / read-ahead may legitimately change
             when capacity shrinks (e.g. host params demote to NVMe). The
             *engine* is pinned at its first-incarnation choice — portable
             checkpoints are engine-family-specific, so a re-plan may move
             tiers but never flips pjit <-> zero3 mid-run.
  re-shard — state crosses the membership change through the checkpoint
             layer's logical (dp-independent) layout: a crash restores the
             latest durable checkpoint onto the new mesh (full state when
             the tier layout matches — optimizer moments survive — else the
             tier-independent ``portable_state``/``adopt_state`` path); a
             graceful rejoin snapshots the live state to host and re-adopts
             it at the *current* step, losing no work. The explicit
             engine's flat rows are padded to a dp multiple, so
             ``adapt_state_layout`` re-pads them for the new degree (the
             pad region is zeros by construction).
  resume   — the executor continues the deterministic synthetic stream from
             the resume step; ``elastic_*`` step metrics (restart count,
             re-plan count, cumulative recovery wall time) and
             ``sys=elastic`` trace spans make recovery cost attributable.

Exercised by tests/test_fault_tolerance.py (unit matrix) and
tests/dist_scripts/chaos.py (8 simulated ranks, dp 4 -> 2 -> 4, loss-parity
against an uninterrupted run).
"""
from __future__ import annotations

import dataclasses
import os
import random
import re
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.plan import HardwareSpec, plan_run
from repro.runtime import trace
from repro.runtime.fault import (RecoveryBudgetExceeded, SimulatedFailure,
                                 StragglerMonitor)
from repro.runtime.metrics import MetricsLogger, elastic_step_metrics


class RankLostError(SimulatedFailure):
    """A member of the cluster vanished mid-step (simulated). Subclasses
    ``SimulatedFailure`` so generic supervision (``retry_loop``) also treats
    it as retryable."""


class PlanInfeasibleError(RuntimeError):
    """Re-planning against the surviving hardware produced an infeasible
    placement — the run cannot continue on the remaining capacity."""


# ---------------------------------------------------------------------------
# chaos schedule: deterministic membership-event injection
# ---------------------------------------------------------------------------

_EVENT_RE = re.compile(r"^(fail|revive)(?::([0-9][0-9,]*))?@([0-9]+)$")


@dataclasses.dataclass(frozen=True)
class ChaosEvent:
    step: int
    kind: str  # "fail" | "revive"
    ranks: Optional[Tuple[int, ...]] = None  # None = policy default


def parse_chaos(spec: str) -> List[ChaosEvent]:
    """``"fail@3"`` / ``"fail:2,3@5;revive@9"`` -> ordered events.

    Grammar: ``kind[:rank[,rank...]]@step`` joined by ``;`` (or whitespace).
    Omitted ranks mean the policy default: ``fail`` takes the highest alive
    rank, ``revive`` readmits every dead rank.
    """
    events = []
    for tok in re.split(r"[;\s]+", spec.strip()):
        if not tok:
            continue
        m = _EVENT_RE.match(tok)
        if m is None:
            raise ValueError(
                f"bad chaos event {tok!r}: expected kind[:ranks]@step, e.g. "
                "'fail@3', 'fail:2,3@5', 'revive@9'")
        kind, ranks, step = m.group(1), m.group(2), int(m.group(3))
        events.append(ChaosEvent(
            step=step, kind=kind,
            ranks=tuple(int(r) for r in ranks.split(",")) if ranks else None))
    return sorted(events, key=lambda e: e.step)


class ChaosSchedule:
    """Fire-once event queue over training steps. Events pop when they
    fire, so a step re-executed after recovery never re-triggers the fault
    that caused the recovery (the single-process analogue of
    ``FailureInjector``'s marker file)."""

    def __init__(self, events: Sequence[ChaosEvent]):
        self._pending = sorted(events, key=lambda e: e.step)

    @classmethod
    def from_spec(cls, spec: Optional[str]) -> Optional["ChaosSchedule"]:
        return cls(parse_chaos(spec)) if spec else None

    def due(self, step: int) -> List[ChaosEvent]:
        """Pop every event scheduled at or before ``step``."""
        fired = [e for e in self._pending if e.step <= step]
        if fired:
            self._pending = [e for e in self._pending if e.step > step]
        return fired

    def __len__(self) -> int:
        return len(self._pending)


# ---------------------------------------------------------------------------
# membership
# ---------------------------------------------------------------------------

class ClusterMembership:
    """Which of the launch-time ranks are alive, and what cluster that
    leaves the planner. Rank r is pinned to ``devices[r]``; the hardware
    view scales the full-membership ``HardwareSpec`` down to the survivors
    (per-device rates unchanged, aggregate DRAM/NVMe shrink with the lost
    nodes). ``version`` bumps on every change so consumers can detect a
    stale view cheaply."""

    def __init__(self, devices: Optional[Sequence] = None,
                 hardware: Optional[HardwareSpec] = None):
        if devices is None:
            import jax

            devices = jax.devices()
        self.devices = list(devices)
        if not self.devices:
            raise ValueError("ClusterMembership needs at least one device")
        self.n_total = len(self.devices)
        base = hardware if hardware is not None else HardwareSpec.detect()
        self.base = (base if base.n_devices == self.n_total
                     else base.with_membership(self.n_total))
        self._alive = set(range(self.n_total))
        self.version = 0
        self.events: List[Tuple[str, Tuple[int, ...], int]] = []

    @property
    def n_alive(self) -> int:
        return len(self._alive)

    def alive_ranks(self) -> List[int]:
        return sorted(self._alive)

    def alive_devices(self) -> list:
        return [self.devices[r] for r in sorted(self._alive)]

    def is_alive(self, rank: int) -> bool:
        return rank in self._alive

    def fail(self, ranks: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
        """Mark ranks dead; returns the ranks actually removed. The last
        survivor is never removed — killing it models a plain process crash
        (restart, no shrink), not an empty cluster."""
        if ranks is None:
            alive = sorted(self._alive)
            ranks = alive[-1:] if len(alive) > 1 else []
        lost = [r for r in ranks if r in self._alive]
        keep_one = len(self._alive) - len(lost) < 1
        if keep_one:
            lost = lost[:-1]
        lost_t = tuple(lost)
        for r in lost_t:
            self._alive.discard(r)
        if lost_t:
            self.version += 1
            self.events.append(("fail", lost_t, self.n_alive))
        return lost_t

    def revive(self, ranks: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
        """Readmit dead ranks (default: all of them); returns the joiners."""
        dead = [r for r in range(self.n_total) if r not in self._alive]
        if ranks is None:
            ranks = dead
        joined = tuple(r for r in ranks if r in dead)
        for r in joined:
            self._alive.add(r)
        if joined:
            self.version += 1
            self.events.append(("revive", joined, self.n_alive))
        return joined

    def hardware(self, n: Optional[int] = None) -> HardwareSpec:
        """The surviving cluster as the planner sees it (optionally capped
        at ``n`` devices — the mesh may use fewer ranks than are alive when
        the batch does not divide evenly; spares stay idle)."""
        return self.base.with_membership(n if n is not None else self.n_alive)

    def dp_for(self, global_batch: int) -> int:
        """Largest data-parallel degree <= n_alive dividing the batch."""
        for d in range(min(self.n_alive, global_batch), 0, -1):
            if global_batch % d == 0:
                return d
        return 1


# ---------------------------------------------------------------------------
# stats & straggler policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElasticStats:
    """Cumulative recovery counters, surfaced as ``elastic_*`` step metrics
    and in the run summary line."""

    restarts: int = 0        # crash recoveries (checkpoint restore path)
    replans: int = 0         # plan_run invocations (incl. the boot plan)
    resizes: int = 0         # graceful membership changes (live re-shard)
    rank_losses: int = 0     # ranks removed by fail events
    recovery_s: float = 0.0  # cumulative failure -> resumed-step wall time
    last_recovery_s: float = 0.0
    membership_version: int = 0
    n_alive: int = 0

    def step_metrics(self) -> Dict[str, float]:
        return elastic_step_metrics(
            restarts=self.restarts, replans=self.replans,
            resizes=self.resizes, recovery_s=self.recovery_s,
            n_alive=self.n_alive, membership_version=self.membership_version)


def wire_straggler(monitor: StragglerMonitor, log=print) -> StragglerMonitor:
    """Install the single-process straggler action: log the outlier and
    record a ``sys=elastic`` span (step + slowdown in the span args) so
    flagged steps are visible next to recovery spans in the trace. The
    multi-host action (re-shard data away from the slow host) would replace
    this callback at real scale."""

    def action(step: int, dt: float, baseline: float) -> None:
        slowdown = dt / baseline if baseline else 0.0
        with trace.span("straggler", sys="elastic", cls="straggler",
                        step=step, slowdown=round(slowdown, 2)):
            log(f"straggler: step {step} took {dt * 1e3:.1f} ms "
                f"({slowdown:.1f}x the median {baseline * 1e3:.1f} ms)")

    monitor.on_straggler = action
    return monitor


# ---------------------------------------------------------------------------
# dp-dependent layout adaptation
# ---------------------------------------------------------------------------

def _repad_last(arr, width: int):
    """Grow/shrink the last axis to ``width``. Only the zero pad region is
    ever truncated (flat rows are padded to a dp multiple past the logical
    parameter count), so this is lossless across dp degrees."""
    a = np.asarray(arr)
    cur = a.shape[-1]
    if cur == width:
        return a
    if cur > width:
        return a[..., :width]
    pad = [(0, 0)] * (a.ndim - 1) + [(0, width - cur)]
    return np.pad(a, pad)


def adapt_state_layout(tree, executor):
    """Re-pad dp-dependent leaves of a (host) state/portable tree to
    ``executor``'s layout. The explicit engine pads each per-layer flat row
    to a multiple of dp, so a checkpoint written at another dp degree (or a
    live snapshot carried across a resize) re-pads here; the GSPMD engine's
    leaves are logical shapes and pass through untouched."""
    if not getattr(executor, "is_explicit", False) or not isinstance(tree, dict):
        return tree
    out = dict(tree)
    padded = executor.engine.layout.padded
    for k in ("flat", "master", "m", "v"):
        v = out.get(k)
        if v is not None and getattr(v, "ndim", 0) >= 1:
            out[k] = _repad_last(v, padded)
    if executor.is_moe and "eflat" in out:
        out["eflat"] = _repad_last(out["eflat"], executor.engine.elayout.padded)
    return out


def _host_tree(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ElasticConfig:
    max_restarts: int = 3
    recovery_budget_s: float = 60.0  # cumulative failure->resume wall clock
    backoff_s: float = 0.05
    jitter: float = 0.25
    seed: int = 0  # jitter RNG (deterministic restart timing in tests)


@dataclasses.dataclass
class _Directive:
    """What the next incarnation should do to obtain its state."""

    kind: str  # "boot" | "crash" | "resize"
    step: Optional[int] = None  # resume step for a live resize
    carry: Optional[dict] = None  # host snapshot carried across a resize


class ElasticSupervisor:
    """Owns the train loop's recovery policy: runs the executor in
    *incarnations*, each planned for and meshed over the currently-alive
    membership, and shepherds state across the boundary (see module
    docstring for the detect -> re-plan -> re-shard -> resume machine).

    Two recovery paths, both exercised by the chaos matrix:

    * **crash** (``fail`` event / injected failure): the incarnation dies
      mid-step; state restores from the latest durable checkpoint onto the
      new mesh and the steps since it re-execute (the deterministic data
      stream makes the re-executed trajectory exact).
    * **resize** (``revive`` event): detected between steps; the live state
      snapshots to host and re-adopts at the current step — nothing lost,
      no checkpoint involved.
    """

    def __init__(self, *, model, shape, train, membership: ClusterMembership,
                 ckpt, chaos: Optional[ChaosSchedule] = None, injector=None,
                 straggler: Optional[StragglerMonitor] = None,
                 objective: str = "throughput",
                 overrides: Optional[dict] = None,
                 parallel_kw: Optional[dict] = None,
                 nvme_dir: str = "/tmp/repro_nvme", overlap: bool = True,
                 config: Optional[ElasticConfig] = None, resume: bool = False,
                 log_every: int = 5, log=print):
        self.model = model
        self.shape = shape
        self.train = train
        self.membership = membership
        self.ckpt = ckpt
        self.chaos = chaos
        self.injector = injector
        self.straggler = wire_straggler(straggler, log) if straggler else None
        self.objective = objective
        self.overrides = dict(overrides or {})
        self.parallel_kw = dict(parallel_kw or {})
        self.nvme_dir = nvme_dir
        self.overlap = overlap
        self.config = config or ElasticConfig()
        self.resume = resume
        self.log_every = max(1, log_every)
        self.log = log
        self.stats = ElasticStats(n_alive=membership.n_alive)
        self.history: dict = {"losses": [], "loss_by_step": {},
                              "metrics": [], "dp_history": [], "plans": []}
        self._rng = random.Random(self.config.seed)
        self._gen = 0
        self._t_fail: Optional[float] = None
        self._executor = None
        self._gen_dir: Optional[str] = None

    # -- public ---------------------------------------------------------

    def run(self) -> dict:
        directive = _Directive("boot")
        while True:
            try:
                out = self._incarnation(directive)
            except SimulatedFailure as e:
                self.stats.restarts += 1
                if self.stats.restarts > self.config.max_restarts:
                    raise
                if self.stats.recovery_s > self.config.recovery_budget_s:
                    raise RecoveryBudgetExceeded(
                        f"elastic: {self.stats.recovery_s:.2f}s cumulative "
                        f"recovery exceeds the "
                        f"{self.config.recovery_budget_s:.0f}s budget") from e
                self.log(f"elastic: restart #{self.stats.restarts} after: {e}")
                delay = (self.config.backoff_s
                         * (2 ** (self.stats.restarts - 1))
                         * (1.0 + self.config.jitter * self._rng.random()))
                time.sleep(delay)
                directive = _Directive("crash")
                continue
            if out is None:
                break
            self.stats.resizes += 1
            directive = out
        self.history["restarts"] = self.stats.restarts
        self.history["elastic"] = self.stats.step_metrics()
        return self.history

    # -- one incarnation --------------------------------------------------

    def _incarnation(self, d: _Directive) -> Optional[_Directive]:
        gen, self._gen = self._gen, self._gen + 1
        self._teardown()
        # ---- detect: project the surviving membership onto hardware ----
        dp = self.membership.dp_for(self.shape.global_batch)
        hw = self.membership.hardware(dp)
        self.log(f"elastic: incarnation {gen}: "
                 f"{self.membership.n_alive}/{self.membership.n_total} ranks "
                 f"alive -> dp={dp} (membership v{self.membership.version})")
        self.history["dp_history"].append(dp)
        # ---- re-plan against the survivors ----
        with trace.span("elastic_replan", sys="elastic", attr="compute",
                        dp=dp, gen=gen):
            plan = plan_run(self.model, self.shape, hw,
                            objective=self.objective, overrides=self.overrides)
            self.stats.replans += 1
        if not plan.feasible:
            raise PlanInfeasibleError(
                "elastic: re-derived plan is infeasible for the surviving "
                f"hardware ({dp} devices): " + "; ".join(plan.warnings))
        # portable checkpoints are engine-family-specific: pin the engine at
        # the boot incarnation's choice so later re-plans move tiers only
        self.overrides.setdefault("engine", plan.engine)
        self.history["plans"].append(plan.summary())
        self.log(f"elastic: {plan.summary()}")
        executor, mesh, run = self._build(plan, dp, gen)
        # ---- re-shard state across the membership change ----
        with trace.span("elastic_reshard", sys="elastic", attr="compute",
                        dp=dp, kind=d.kind):
            state, start = self._reshard(executor, d)
        # ---- resume the trajectory ----
        return self._resume(executor, mesh, run, plan, state, start, dp)

    def _build(self, plan, dp: int, gen: int):
        import dataclasses as dc

        import jax

        from repro.core.executor import InfinityExecutor

        # each incarnation streams through its own NVMe namespace: rank-key
        # layouts are dp-dependent and stale rows from the previous degree
        # must never be readable
        self._gen_dir = os.path.join(self.nvme_dir, f"gen{gen}")
        run = plan.to_run_config(train=self.train, nvme_dir=self._gen_dir,
                                 overlap=self.overlap)
        if self.parallel_kw:
            run = run.replace(
                parallel=dc.replace(run.parallel, **self.parallel_kw))
        mesh = jax.make_mesh(
            (dp, 1), ("data", "model"),
            devices=self.membership.alive_devices()[:dp],
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self._executor = InfinityExecutor(run, mesh, plan=plan)
        return self._executor, mesh, run

    def _teardown(self) -> None:
        if self._executor is not None:
            self._executor.close()
            self._executor = None
        if self._gen_dir is not None:
            shutil.rmtree(self._gen_dir, ignore_errors=True)
            self._gen_dir = None

    # -- re-shard paths ---------------------------------------------------

    def _portable_keys(self, executor, available) -> List[str]:
        if executor.is_explicit:
            keys = ["flat", "other", "other_opt", "step"]
            if executor.is_moe:
                keys.append("eflat")
        else:
            keys = ["params"]
        missing = [k for k in keys if k not in available]
        if missing:
            raise KeyError(f"portable leaves missing: {missing}")
        return keys

    def _reshard(self, executor, d: _Directive):
        import jax

        if d.kind == "resize":
            return self._adopt_carry(executor, d.carry, d.step), d.step
        if d.kind == "crash" or (d.kind == "boot" and self.resume):
            self.ckpt.wait()  # quiesce any in-flight async save first
            if self.ckpt.latest_step() is not None:
                return self._restore(executor)
            if d.kind == "crash":
                self.log("elastic: no durable checkpoint yet — "
                         "re-initializing from the seed")
        state = executor.init_state(
            jax.random.PRNGKey(self.train.seed))
        return state, 0

    def _restore(self, executor):
        """Checkpoint -> state on this executor's mesh. Full restore keeps
        the optimizer moments (loss parity with an uninterrupted run); a
        tier layout change falls back to the portable subset."""
        import jax

        sh = executor.state_shardings()
        try:
            restored, extra = self.ckpt.restore(sh)
        except KeyError:
            like = {k: sh[k] for k in self._portable_keys(executor, sh)}
            portable, extra = self.ckpt.restore(like)
            start = extra["next_step"]
            portable = adapt_state_layout(portable, executor)
            state = executor.adopt_state(portable, step=start)
            self.log(f"elastic: portable restore (tier layout changed) at "
                     f"step {start}")
            return state, start
        start = extra["next_step"]
        restored = adapt_state_layout(restored, executor)
        state = jax.device_put(restored, sh)
        state = executor.reseed(state, step=start)
        self.log(f"elastic: full restore from checkpoint at step {start}")
        return state, start

    def _adopt_carry(self, executor, carry: dict, step: int):
        """Live host snapshot (from the previous incarnation) -> state."""
        import jax

        sh = executor.state_shardings()
        carry = adapt_state_layout(carry, executor)
        if jax.tree.structure(carry) == jax.tree.structure(sh):
            # same tier layout on both sides of the resize: the full state
            # (optimizer moments included) crosses intact
            state = jax.device_put(carry, sh)
            return executor.reseed(state, step=step)
        portable = {k: carry[k]
                    for k in self._portable_keys(executor, carry)}
        return executor.adopt_state(portable, step=step)

    # -- the step loop ----------------------------------------------------

    def _resume(self, executor, mesh, run, plan, state, start: int,
                dp: int) -> Optional[_Directive]:
        import jax

        from repro.data.pipeline import PrefetchLoader, SyntheticStream

        step_fn = executor.make_train_step()
        stream = SyntheticStream(executor.input_specs(self.shape),
                                 run.model.vocab_size, seed=self.train.seed)
        loader = PrefetchLoader(stream, start, self.train.steps,
                                executor.batch_shardings(self.shape))
        logger = MetricsLogger(log_fn=self.log)
        tokens = self.shape.global_batch * self.shape.seq_len
        self.stats.n_alive = self.membership.n_alive
        self.stats.membership_version = self.membership.version
        if self._t_fail is not None:
            # the recovery interval ends here: failure (or resize detection)
            # -> re-planned, re-sharded, ready to step
            dt_rec = time.perf_counter() - self._t_fail
            self._t_fail = None
            self.stats.recovery_s += dt_rec
            self.stats.last_recovery_s = dt_rec
            trace.instant("elastic_resume", sys="elastic", step=start,
                          recovery_s=round(dt_rec, 3), dp=dp)
            self.log(f"elastic: resumed at step {start} after {dt_rec:.2f}s "
                     f"recovery (dp={dp})")
            if self.stats.recovery_s > self.config.recovery_budget_s:
                raise RecoveryBudgetExceeded(
                    f"elastic: cumulative recovery {self.stats.recovery_s:.2f}s"
                    f" exceeds the {self.config.recovery_budget_s:.0f}s budget")
        try:
            with jax.set_mesh(mesh):
                for step, batch in loader:
                    directive = self._membership_events(executor, state, step)
                    if directive is not None:
                        return directive
                    if self.injector is not None:
                        self.injector.maybe_fail(step)
                    if self.straggler is not None:
                        self.straggler.start()
                    state, metrics = step_fn(state, batch)
                    loss = float(metrics["loss"])
                    dt = (self.straggler.stop(step)
                          if self.straggler is not None else 0.0)
                    self.history["losses"].append(loss)
                    self.history["loss_by_step"][step] = loss
                    if step % self.log_every == 0:
                        extras = self.stats.step_metrics()
                        if self.straggler is not None:
                            extras.update(self.straggler.step_metrics())
                        rec = logger.log(step, loss, tokens, dt, **extras)
                        self.history["metrics"].append(rec)
                    if (self.train.checkpoint_every
                            and (step + 1) % self.train.checkpoint_every == 0):
                        self.ckpt.save(step + 1,
                                       executor.checkpoint_state(state),
                                       {"next_step": step + 1})
        except SimulatedFailure:
            self._t_fail = time.perf_counter()
            trace.instant("elastic_failure", sys="elastic", dp=dp)
            raise
        self.ckpt.wait()
        self.history["final_state"] = state
        bw = executor.bandwidth_stats()
        if bw:
            self.history["nvme_stats"] = bw
        return None

    def _membership_events(self, executor, state,
                           step: int) -> Optional[_Directive]:
        """Apply chaos events due at ``step``. A ``fail`` mutates membership
        and raises (the crash the lost rank causes); a ``revive`` returns a
        resize directive carrying the live state."""
        if self.chaos is None:
            return None
        for ev in self.chaos.due(step):
            if ev.kind == "fail":
                lost = self.membership.fail(ev.ranks)
                self.stats.rank_losses += len(lost)
                who = f"rank(s) {list(lost)}" if lost else \
                    "sole survivor (process crash, no shrink)"
                raise RankLostError(f"chaos: lost {who} at step {step}")
            joined = self.membership.revive(ev.ranks)
            if not joined:
                continue
            self._t_fail = time.perf_counter()
            with trace.span("elastic_snapshot", sys="elastic", attr="compute",
                            step=step):
                carry = _host_tree(executor.checkpoint_state(state))
            self.log(f"elastic: rank(s) {list(joined)} rejoined at step "
                     f"{step} — graceful re-plan")
            return _Directive("resize", step=step, carry=carry)
        return None
