"""GeoTrainer: the end-to-end geo-distributed training loop.

Composes every substrate: model + configs, distributed step builders
(WAN sync strategies), data pipeline, AdamW/DiLoCo, checkpointing (async,
checksummed), heartbeat failure detection, straggler monitoring, elastic
re-meshing, and the ScaleAcross fabric — which supplies the *WAN cost
model* per step, so a CPU run reports the same communication economics
the paper measures on its emulated testbed (Fig. 14).

This is the driver behind ``examples/train_geo.py`` and
``launch/train.py``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import AsyncCheckpointer, CheckpointStore
from repro.core.geo import GeoFabric, SyncOptions
from repro.core.schedule import CollectiveSchedule, strategy_names
from repro.data import loader_for_model
from repro.distributed import init_train_state, make_train_step
from repro.launch.shapes import params_specs
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, DilocoConfig

from .failure import HeartbeatMonitor, optimal_checkpoint_interval, plan_recovery
from .straggler import StragglerMonitor

# Host spans of the loop, written into a profiler trace when one is recording
# (``jax.profiler``) and costing about a microsecond each otherwise.  Each step
# is one ``STEP`` span; inside it the phases below follow one another, in this
# order, without overlap.
SPAN_STEP = "repro.train.step"
SPAN_EVENTS = "repro.train.events"  # scenario events, on steps that have any
SPAN_FEED = "repro.train.feed"  # next batch from the loader, and its device_put
SPAN_DISPATCH = "repro.train.dispatch"  # the step call, until it returns
SPAN_FETCH = "repro.train.fetch"  # loss and grad norm to the host: the wait for the device
SPAN_BOOKKEEP = "repro.train.bookkeep"  # heartbeats, stragglers, recovery, row, log, cadence
SPAN_CALLBACK = "repro.train.callback"  # the caller's on_step
SPAN_CHECKPOINT = "repro.train.checkpoint"  # a save when one is due, and the final wait


@dataclasses.dataclass
class TrainerConfig:
    seq_len: int = 128
    global_batch: int = 8
    steps: int = 100
    strategy: str = "hier"
    num_channels: int = 4
    checkpoint_every: Optional[int] = None  # None -> Young/Daly auto
    checkpoint_keep: int = 3
    log_every: int = 10
    seed: int = 0
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    diloco: DilocoConfig = dataclasses.field(default_factory=DilocoConfig)
    mtbf_s: float = 6 * 3600.0  # assumed per-pod MTBF for ckpt cadence


class GeoTrainer:
    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        *,
        trainer_cfg: TrainerConfig,
        checkpoint_dir: str,
        geo: Optional[GeoFabric] = None,
        scenario=None,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.tc = trainer_cfg
        self.sync_options = SyncOptions(jitter=False)
        self.scenario = scenario
        if scenario is not None:
            # declarative path (repro.scenario.Scenario): the spec supplies
            # the emulated deployment, the WAN sync strategy/cadence, the
            # step budget, the costing options, and the event script
            # (replayed at step boundaries in run()).  The spec's modeling
            # fields the trainer measures for real — compute_seconds /
            # overlap_fraction / grad_bytes / model — are not consumed
            # here; straggler events only scale modeled compute, so they
            # are skipped too.  Explicit trainer_cfg fields the spec does
            # not cover (batch shape, optimizer, checkpoint cadence) are
            # kept as passed.
            if geo is not None:
                raise ValueError("pass scenario or geo, not both")
            geo = scenario.topology.build()
            wl = scenario.workload
            if wl.strategy is not None:
                # the spec is authoritative, including an explicit steps=1
                self.tc = dataclasses.replace(
                    self.tc,
                    strategy=wl.strategy,
                    num_channels=scenario.topology.num_channels,
                    steps=wl.steps,
                )
            self.sync_options = dataclasses.replace(
                scenario.options, jitter=False
            )
        self.geo = geo or GeoFabric(num_pods=max(mesh.shape.get("pod", 1), 1) + (0 if "pod" in mesh.axis_names else 1))
        self.store = CheckpointStore(checkpoint_dir, keep=trainer_cfg.checkpoint_keep)
        self.ckpt = AsyncCheckpointer(self.store)
        pods = [f"pod{i}" for i in range(mesh.shape.get("pod", 1))] or ["pod0"]
        self.heartbeats = HeartbeatMonitor(pods, interval_ms=100.0)
        self.stragglers = StragglerMonitor(pods)
        self.metrics_log: List[Dict[str, float]] = []
        self._build()

    # -- setup -----------------------------------------------------------------

    def _build(self) -> None:
        cfg, tc = self.cfg, self.tc
        self.loader = loader_for_model(
            cfg, seq_len=tc.seq_len, global_batch=tc.global_batch, seed=tc.seed
        )
        p_shapes = params_specs(cfg)
        batch_np = self.loader.next_batch()
        self.loader.step -= 1  # peek, don't consume
        batch_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch_np
        )
        self.step_fn, self.shardings = make_train_step(
            cfg, self.mesh,
            opt_cfg=tc.opt,
            strategy=tc.strategy,
            num_channels=tc.num_channels,
            diloco_cfg=tc.diloco,
            params_shapes=p_shapes,
            batch_shapes=batch_shapes,
            donate=False,
        )
        self.grad_bytes = sum(
            int(np.prod(s.shape)) * 4 for s in jax.tree.leaves(p_shapes)
        )

    def init_or_restore(self):
        cfg, tc = self.cfg, self.tc
        params = init_params(jax.random.PRNGKey(tc.seed), cfg)
        state = init_train_state(params, tc.opt, strategy=tc.strategy)
        start_step = 0
        latest = self.store.latest_step()
        if latest is not None:
            (params, state), meta = self.store.restore(latest, (params, state))
            start_step = int(meta.get("data_step", latest))
            self.loader.step = start_step
        params = jax.device_put(params, self.shardings["params"])
        state = jax.device_put(state, self.shardings["state"])
        return params, state, start_step

    def _ckpt_interval(self, step_time_s: float) -> int:
        if self.tc.checkpoint_every is not None:
            return self.tc.checkpoint_every
        save_overhead = max(self.grad_bytes / 1e9, 0.05)  # ~1 GB/s disk
        return optimal_checkpoint_interval(
            step_time_s=max(step_time_s, 1e-3),
            save_overhead_s=save_overhead,
            mtbf_s=self.tc.mtbf_s,
        )

    # -- the loop -----------------------------------------------------------------

    def run(
        self,
        *,
        on_step: Optional[Callable[[int, Dict[str, float]], None]] = None,
        inject_failure_at: Optional[int] = None,
    ) -> Dict[str, Any]:
        params, state, start = self.init_or_restore()
        tc = self.tc
        last_ckpt = start
        # WAN cost estimate via the schedule-strategy registry.  Note
        # make_train_step currently restricts tc.strategy to the paper five
        # (all registered), so today this always costs; the registry check
        # keeps the estimate in sync if the step builders grow strategies
        # that have no schedule (or vice versa).
        wan_cost = (
            self.geo.sync_cost(
                tc.strategy,
                self.grad_bytes,
                options=dataclasses.replace(self.sync_options, jitter=False),
            )
            if isinstance(tc.strategy, CollectiveSchedule)
            or tc.strategy in strategy_names()
            else None
        )
        recovery_drills = []
        # scenario event script, replayed at step boundaries (straggler
        # events scale *modeled* compute only, so the trainer skips them —
        # its compute is measured for real)
        events_by_step: Dict[int, list] = {}
        scenario_rollup = None
        apply_event = None
        straggler_noop: Dict[int, float] = {}
        if self.scenario is not None and self.scenario.events:
            from repro.scenario.runner import ScenarioResult, apply_event

            scenario_rollup = ScenarioResult(
                scenario=self.scenario, steps=[], sync=None, geo=self.geo
            )
            for ev in self.scenario.events:
                if ev.kind != "straggler":
                    events_by_step.setdefault(ev.at_step, []).append(ev)
        t_step_ewma = None
        # simulated heartbeat clock: one beat interval per training step, so
        # detection semantics are step-count-based (detect_mult missed
        # steps) regardless of wall-clock step duration.
        interval_ms = next(iter(self.heartbeats.workers.values())).session.interval_ms
        sim_ms = 0.0
        batch = {}
        with self.mesh:
            for step in range(start, tc.steps):
                with StepTraceAnnotation(SPAN_STEP, step_num=step):
                    if step in events_by_step:
                        with TraceAnnotation(SPAN_EVENTS):
                            for ev in events_by_step[step]:
                                apply_event(ev, self.geo, scenario_rollup, straggler_noop)
                    with TraceAnnotation(SPAN_FEED):
                        batch = jax.device_put(self.loader.next_batch(), self.shardings["batch"])
                    t0 = time.time()
                    with TraceAnnotation(SPAN_DISPATCH):
                        params, state, metrics = self.step_fn(params, state, batch)
                    with TraceAnnotation(SPAN_FETCH):
                        loss = float(metrics["loss"])
                        grad_norm = float(metrics.get("grad_norm", 0.0))
                    dt = time.time() - t0

                    with TraceAnnotation(SPAN_BOOKKEEP):
                        t_step_ewma = dt if t_step_ewma is None else 0.8 * t_step_ewma + 0.2 * dt
                        sim_ms += interval_ms
                        for pod in self.heartbeats.workers:
                            if inject_failure_at is not None and step >= inject_failure_at and pod == "pod1":
                                continue  # pod1 goes silent
                            self.heartbeats.heartbeat(pod, sim_ms)
                            self.stragglers.record(pod, dt)
                        # +1 ms epsilon: a pod missing detect_mult consecutive beats
                        # is declared dead on exactly that step
                        dead = self.heartbeats.poll(sim_ms + 1.0)
                        if dead:
                            plan = plan_recovery(
                                step=step,
                                last_checkpoint_step=last_ckpt,
                                step_time_s=t_step_ewma or dt,
                                detect_time_ms=self.heartbeats.detect_time_ms(),
                                checkpoint_bytes=self.grad_bytes * 3,
                            )
                            recovery_drills.append({"step": step, "dead": dead, "plan": dataclasses.asdict(plan)})
                            inject_failure_at = None  # handled

                        row = {
                            "step": step,
                            "loss": loss,
                            "step_s": dt,
                            "grad_norm": grad_norm,
                            "wan_s_est": wan_cost.amortized_seconds if wan_cost else 0.0,
                        }
                        self.metrics_log.append(row)
                        if step % tc.log_every == 0:
                            print(
                                f"step {step:5d} loss {loss:7.4f} "
                                f"({dt:5.2f}s dispatch+fetch, +{row['wan_s_est']:.2f}s WAN est "
                                f"[{tc.strategy}])",
                                flush=True,
                            )
                        interval = self._ckpt_interval(t_step_ewma or dt)
                    if on_step:
                        with TraceAnnotation(SPAN_CALLBACK):
                            on_step(step, row)
                    if (step + 1) % max(interval, 1) == 0 or step == tc.steps - 1:
                        with TraceAnnotation(SPAN_CHECKPOINT):
                            self.ckpt.save(
                                step + 1, (params, state), metadata={"data_step": step + 1}
                            )
                        last_ckpt = step + 1
        with TraceAnnotation(SPAN_CHECKPOINT):
            self.ckpt.wait()
        return {
            "final_loss": self.metrics_log[-1]["loss"] if self.metrics_log else None,
            "params": params,
            "state": state,
            "batch_devices": len(
                {d for leaf in jax.tree.leaves(batch) for d in leaf.sharding.device_set}
            ),
            "metrics": self.metrics_log,
            "recovery_drills": recovery_drills,
            "sync_efficiency": self.stragglers.sync_efficiency(),
            "last_checkpoint": last_ckpt,
            "scenario_recoveries": (
                [
                    {"mechanism": t.mechanism, "recovery_ms": t.recovery_ms}
                    for t in scenario_rollup.recoveries
                ]
                if scenario_rollup is not None
                else []
            ),
            "scenario_evpn_resyncs": (
                len(scenario_rollup.evpn_resyncs)
                if scenario_rollup is not None
                else 0
            ),
        }
