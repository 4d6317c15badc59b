"""``AMBSession`` — the one programmatic surface over train / serve / bench.

A session owns everything the drivers used to hand-wire: mesh setup, param
init + sharding, clock construction, consensus-strategy and epoch-driver
selection (via :func:`repro.api.protocol.build_protocol`), and the uniform
``TrainState``.  The same four calls work identically across the exact,
gossip, quantized-gossip, and pipelined modes:

    session = AMBSession(TrainSpec(arch="qwen2-1.5b", smoke=True, data=4,
                                   model=2),
                         ClockSpec(kind="simulated"),
                         ConsensusSpec(consensus="gossip", graph="torus"))
    metrics = session.run(steps)          # prefetched data plane
    session.flush()                       # settle in-flight consensus
    session.save("ckpt/")                 # primal checkpoint, any mode
    w = session.params                    # current primal iterate

    ``run`` feeds the session from an :class:`repro.data.InputSource`
    (default: :meth:`batch_source`, per-worker shards of the arch's LM
    token stream) through a background :class:`repro.data.Prefetcher`,
    overlapping epoch t's device step with epoch t+1's host build +
    transfer.  ``step(batch)`` remains the single-epoch primitive for
    callers that hand-build batches.  The jitted step/flush donate the
    TrainState (``donate_argnums=0``): every protocol's output state
    leaf aliases its input leaf, so the old iterate's buffers are
    reused in place instead of briefly doubling resident memory.

Elastic worker membership is first-class: ``session.set_active(mask)``
exploits AMB's existing b_i(t) = 0 tolerance — a masked worker's
minibatch is forced to zero (so its sequence weights vanish from the
eq.-6 average) and the gossip operator is rebuilt over the survivors —
ring/torus fleets relayout onto a smaller ring/torus whose taps stay on
the collective-permute fast path
(:func:`repro.dist.consensus.survivor_taps`; non-circulant graphs fall
back to the dense :func:`repro.dist.consensus.masked_metropolis`).  The
TrainState carries over untouched across membership changes: a
rejoining worker resumes from its (stale) dual replica and consensus
re-mixes it in.  :meth:`run`'s ``faults=`` hook drives a
:class:`repro.faults.FaultModel` through this machinery epoch by epoch,
and ``TrainSpec.redundancy`` adds coded data placement so the gradient
estimate stays unbiased while workers are down
(:mod:`repro.dist.redundancy`).
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..ckpt import load_checkpoint, save_checkpoint
from ..configs import get_config, smoke_config
from ..control import Controller, EpochRecord
from ..core.stragglers import amb_batch_sizes, fmb_finish_times
from ..data import Prefetcher, StreamSource, put_batch
from ..data.pipeline import LMTokenStream
from ..dist import use_sharding
from ..dist.amb import num_workers
from ..dist.params import tree_shardings
from ..kernels import router
from ..launch.mesh import make_host_mesh
from ..metrics import MetricsLogger
from ..models import init_params
from ..optim import make_optimizer
from .clock import make_clock
from .protocol import build_protocol
from .specs import ClockSpec, ConsensusSpec, ControllerSpec, TrainSpec

Array = jax.Array

# profiler span names (stable: the benchmark's per-layer metrics read them)
SPAN_EPOCH = "amb.epoch"
SPAN_CLOCK = "amb.epoch.clock"
SPAN_DISPATCH = "amb.epoch.dispatch"
SPAN_WAIT = "amb.epoch.wait"
SPAN_RECORD = "amb.epoch.record"
SPAN_ON_STEP = "amb.on_step"


def _unalias(state):
    """Break object aliasing between TrainState leaves.

    ``donate_argnums`` requires every donated buffer to appear exactly
    once in the arguments, but freshly-*initialized* states can hold one
    array under two leaves (e.g. fp32 params, where the dual-averaging
    ``opt["w0"] = params.astype(f32)`` no-op returns ``params`` itself)
    — stepping such a state donates the buffer twice and XLA rejects the
    execute.  Copying the repeat occurrences once at assembly restores
    the protocols' aliasing contract; stepped states are always
    alias-free (each output leaf owns its buffer).
    """
    seen: set = set()

    def u(x):
        if isinstance(x, jax.Array):
            if id(x) in seen:
                return jnp.copy(x)
            seen.add(id(x))
        return x

    return jax.tree.map(u, state)


class AMBSession:
    """One AMB training/serving session over a device mesh.

    Args:
      train: architecture / mesh / optimizer spec.
      clock: fixed-time contract spec (measured or simulated b_i(t)).
      consensus: consensus strategy + epoch driver spec.
      mesh: an existing mesh to run on; default builds a host mesh from
        ``train``'s (pod, data, model) extents.
      params: pre-initialized (e.g. restored) parameters; default
        initializes from ``train.seed`` and shards per the layout rules.
      cfg: an explicit :class:`repro.models.common.ArchConfig`, for
        custom architectures outside the registry (tests, research).
      controller: a :class:`repro.api.specs.ControllerSpec`; when
        ``enabled``, every ``step`` feeds a telemetry record to a
        :class:`repro.control.Controller` and applies its actions
        in-place — budget into the :class:`~repro.api.clock.Clock`,
        staleness by drain-and-rebuild (:meth:`_apply_staleness`) — no
        restart, no recompile beyond the new driver graph.
      metrics_path: optional JSONL path; when set, every epoch (and
        every controller decision) is appended via
        :class:`repro.metrics.MetricsLogger`.

    A zero-step session is a well-defined no-op: construction alone
    yields valid ``params`` (the initialization), ``flush`` and ``save``
    work, and no loss is ever fabricated.
    """

    def __init__(self, train: TrainSpec,
                 clock: Optional[ClockSpec] = None,
                 consensus: Optional[ConsensusSpec] = None,
                 controller: Optional[ControllerSpec] = None, *,
                 mesh=None, params=None, cfg=None, metrics_path=None):
        self.train = train
        if train.kernels != "auto":
            # pin the kernel routing for the process (logged once by the
            # router); "auto" leaves any ambient REPRO_KERNELS in force
            router.set_mode(train.kernels)
        self.clock_spec = clock if clock is not None else ClockSpec()
        self.consensus_spec = consensus if consensus is not None \
            else ConsensusSpec()
        self.cfg = cfg if cfg is not None else (
            smoke_config(train.arch) if train.smoke
            else get_config(train.arch))
        self.mesh = mesh if mesh is not None else make_host_mesh(
            train.data, train.model, pod=train.pod)
        self.n_workers = num_workers(self.mesh)
        self.global_batch = self.n_workers * train.batch_per_worker
        self._batch_axes = tuple(a for a in ("pod", "data")
                                 if a in self.mesh.axis_names)
        # coded redundancy: validated here (fail at construction, not in
        # the first step) — the same CodedAssignment drives both the data
        # placement (batch_source) and the decode weights (dist steps)
        self._assignment = None
        if train.redundancy > 1:
            from ..dist.redundancy import CodedAssignment
            self._assignment = CodedAssignment(self.n_workers,
                                               train.redundancy)
        self._slow: Optional[np.ndarray] = None   # fault-injected slowdowns

        self.clock = make_clock(self.clock_spec, self.n_workers,
                                train.batch_per_worker)
        self._decentralized = (self.consensus_spec.pipeline
                               or self.consensus_spec.async_epochs
                               or self.consensus_spec.consensus != "exact")
        self._optimizer = None
        if not self._decentralized:
            if train.optimizer == "dual_averaging":
                self._optimizer = make_optimizer(
                    "dual_averaging",
                    beta=self.consensus_spec.beta(self.global_batch))
            else:
                self._optimizer = make_optimizer(train.optimizer)
        elif train.optimizer != "dual_averaging":
            raise ValueError("gossip / pipelined / async modes run the "
                             "paper's dual-averaging protocol; use "
                             "optimizer='dual_averaging'")

        self.controller_spec = controller if controller is not None \
            else ControllerSpec()
        self.controller: Optional[Controller] = None
        if self.controller_spec.enabled:
            self.controller = Controller(
                self.controller_spec, n_workers=self.n_workers,
                comm_time=self.clock_spec.comm_time,
                b_target=self.global_batch, b_cap=self.global_batch,
                staleness=self.consensus_spec.staleness,
                async_mode=self.consensus_spec.async_epochs)
        self.metrics = MetricsLogger(metrics_path) if metrics_path \
            else None

        self._key = jax.random.PRNGKey(train.seed)
        self._active: Optional[tuple] = None
        self._protocols: dict = {}       # (mask, staleness) -> protocol
        self._build_protocol()

        with use_sharding(self.mesh):
            if params is None:
                params = init_params(self._key, self.cfg)
                params = jax.tree.map(
                    lambda p, sh: jax.device_put(p, sh), params,
                    tree_shardings(params, self.mesh))
            self.state = _unalias(self.protocol.init(params))
        self.steps_done = 0
        self.sim_wall = 0.0

    # -- construction ------------------------------------------------------

    def _build_protocol(self, active: Optional[tuple] = None) -> None:
        """(Re)build the epoch driver; at init, on set_active, and on a
        controller staleness retune.

        Exact consensus ignores ``active`` at the step level (a masked
        worker's b_i = 0 already zeroes it out of the eq.-6 average), so
        only the gossip-family protocols rebuild — and rebuilds are
        cached by ``(mask, staleness)``, so a worker rejoining a
        previously-seen configuration — or the controller swinging D
        back to an earlier value — reuses the warm jitted executable
        instead of recompiling.
        """
        mask = active if self._decentralized else None
        key = (mask, self.consensus_spec.staleness) \
            if self._decentralized else None
        if key not in self._protocols:
            amb = self.consensus_spec.to_amb_config(
                self.global_batch, self.train.seed, active=mask,
                noise_stats=self.controller is not None,
                redundancy=self.train.redundancy)
            proto = build_protocol(
                self.cfg, self.mesh, amb, optimizer=self._optimizer,
                pipeline=self.consensus_spec.pipeline,
                async_epochs=self.consensus_spec.async_epochs,
                staleness=self.consensus_spec.staleness)
            # donate the TrainState: every protocol's output state leaf
            # aliases its input leaf (shape/dtype/sharding — the
            # contract repro.api.protocol documents), so XLA rewrites
            # the iterate in place instead of holding old + new
            # parameter/dual/queue buffers live across the update
            self._protocols[key] = (
                proto, jax.jit(proto.step, donate_argnums=0),
                jax.jit(proto.flush, donate_argnums=0))
        self.protocol, self._step_fn, self._flush_fn = self._protocols[key]

    # -- elastic membership ------------------------------------------------

    @property
    def active(self) -> np.ndarray:
        """Bool (n_workers,) membership mask (all True when fully manned)."""
        if self._active is None:
            return np.ones(self.n_workers, dtype=bool)
        return np.asarray(self._active, dtype=bool)

    def set_active(self, mask) -> None:
        """Elastic worker join/leave: re-mask b_i(t), rebuild gossip taps.

        ``mask`` is a length-``n_workers`` boolean sequence.  A False
        worker contributes b_i(t) = 0 every epoch (its sequence weights
        vanish — the paper's straggler-wipeout case, which AMB already
        tolerates) and is cut out of the gossip graph; ring/torus
        fleets re-lay the survivors onto a smaller ring/torus (taps
        stay collective-permutes), other graphs re-derive dense
        Metropolis weights on the induced subgraph.  A single survivor
        degenerates to identity consensus; an all-inactive mask is
        rejected before any state is touched.  The TrainState (params /
        dual replicas) is preserved, so a later ``set_active`` that
        re-admits the worker resumes it from its stale dual and lets
        consensus pull it back in.

        In-flight consensus is **drained first** (pipelined / async
        modes): a queued payload was packed for the *old* membership's
        gossip operator, so it settles under the operator it was
        enqueued against before the taps rebuild.  The drain is a plain
        ``flush`` — always a valid state transition — so a subsequently
        rejected mask (e.g. one that disconnects the gossip graph) still
        leaves the session in a consistent, merely-settled state.
        """
        mask = np.asarray(mask, dtype=bool).reshape(-1)
        if mask.shape[0] != self.n_workers:
            raise ValueError(f"mask has {mask.shape[0]} entries for "
                             f"{self.n_workers} workers")
        if not mask.any():
            raise ValueError("at least one worker must stay active")
        active = None if mask.all() else tuple(bool(m) for m in mask)
        if active != self._active:
            self.flush()     # drain in-flight rounds under the old operator
        # build first, commit second: a rejected mask must leave the
        # session unchanged (modulo the always-valid drain above)
        self._build_protocol(active)
        self._active = active

    def set_slowdown(self, slow) -> None:
        """Pin per-worker slowdown multipliers on the clock draws.

        ``slow`` is a length-``n_workers`` sequence of per-gradient-time
        multipliers (or None to clear): each epoch's straggler-model
        draws are scaled per worker *before* the deadline cut, so a
        fail-slow worker's b_i(t) shrinks through the paper's own
        variable-minibatch mechanism — no special-casing downstream.
        Composes multiplicatively with the configured
        :class:`repro.core.stragglers.StragglerModel`.
        """
        if slow is None:
            self._slow = None
            return
        slow = np.asarray(slow, dtype=np.float64).reshape(-1)
        if slow.shape[0] != self.n_workers:
            raise ValueError(f"slowdown has {slow.shape[0]} entries for "
                             f"{self.n_workers} workers")
        if (slow <= 0).any():
            raise ValueError("slowdown multipliers must be positive")
        self._slow = None if np.all(slow == 1.0) else slow

    # -- the epoch ---------------------------------------------------------

    def epoch_sizes(self, times: Array, budget: float) -> Array:
        """b_i(t) for one epoch: deadline cut + membership mask."""
        if self.train.mode == "amb":
            b = amb_batch_sizes(times, budget)
        else:
            b = jnp.full((self.n_workers,), self.train.batch_per_worker,
                         jnp.int32)
        if self._active is not None:
            b = jnp.where(jnp.asarray(self.active), b, 0)
        return b

    def step(self, batch, b: Optional[Array] = None) -> dict:
        """Run one AMB epoch on a (host) global batch; returns metrics.

        ``batch`` is the unsharded global batch (leading dim
        ``global_batch``); the session shards it over the worker axes.
        ``b`` overrides the clock-derived per-worker minibatch sizes
        (sized ``(n_workers,)``); by default the clock draws this epoch's
        per-gradient times and the deadline T decides b_i(t).

        The epoch and its phases are profiler spans (``amb.epoch`` and
        its ``.clock``, ``.dispatch``, ``.wait`` and ``.record``
        children, each with the stat ``epoch``); ``.wait`` is the one
        blocking read of the step's results.
        """
        epoch = self.steps_done
        with use_sharding(self.mesh), TraceAnnotation(SPAN_EPOCH,
                                                      epoch=epoch):
            with TraceAnnotation(SPAN_CLOCK, epoch=epoch):
                times, budget = self._draw()
                if b is None:
                    b = self.epoch_sizes(times, budget)
            with TraceAnnotation(SPAN_DISPATCH, epoch=epoch):
                batch = put_batch(batch, self.mesh, self._batch_axes)
                t0 = time.time()
                self.state, m = self._step_fn(self.state, batch, b)
            with TraceAnnotation(SPAN_WAIT, epoch=epoch):
                loss, gbatch, b = jax.device_get(
                    (m["loss"], m["global_batch"], b))
                step_s = time.time() - t0
                b = np.asarray(b)
            with TraceAnnotation(SPAN_RECORD, epoch=epoch,
                                 credited=int(b.sum()),
                                 computed=self.global_batch):
                self.clock.update(step_s, float(gbatch))
                self.steps_done += 1
                out = {"loss": float(loss),
                       "global_batch": float(gbatch),
                       "budget_s": float(budget),
                       "step_s": step_s,
                       "sim_wall_s": self.sim_wall,
                       "staleness": self.consensus_spec.staleness,
                       "b": b}
                if self.controller is not None:
                    action = self._control(m, out, b, times)
                    if action is not None:
                        out["action"] = action.to_dict()
                if self.metrics is not None:
                    self.metrics.log(self.steps_done,
                                     **{k: v for k, v in out.items()
                                        if k != "b"})
            return out

    def _draw(self) -> tuple:
        """This epoch's per-gradient times and budget T from the clock;
        advances the simulated wall clock."""
        skey = jax.random.fold_in(self._key, 10_000 + self.steps_done)
        times, budget = self.clock.epoch(skey)
        if self._slow is not None:
            # fault-injected degradation: scale each worker's
            # per-gradient times; the deadline cut turns the slowdown
            # into a smaller b_i(t) automatically
            times = times * jnp.asarray(self._slow, times.dtype)[:, None]
        # simulated wall clock: pipelined epochs hide T_c under the
        # next epoch's compute; async epochs give each consensus D
        # compute windows, so only T_c/D must fit per epoch; FMB
        # waits for the slowest worker
        if self.train.mode == "amb":
            spec = self.consensus_spec
            if spec.async_epochs:
                self.sim_wall += max(
                    float(budget), self.clock_spec.comm_time / spec.staleness)
            elif spec.pipeline:
                self.sim_wall += max(float(budget), self.clock_spec.comm_time)
            else:
                self.sim_wall += float(budget) + self.clock_spec.comm_time
        else:
            self.sim_wall += float(jnp.max(fmb_finish_times(
                times, self.train.batch_per_worker))) \
                + self.clock_spec.comm_time
        return times, budget

    def batch_source(self) -> StreamSource:
        """The session's default input: per-worker shards of the arch's
        LM token stream (worker i draws stream node i — distinct i.i.d.
        shards, deterministic in (seed, node, epoch) so restores resume
        the exact remaining stream).  Under coded redundancy the
        session's :class:`repro.dist.redundancy.CodedAssignment` places
        rotated copies of each group's block instead (group members
        share a stream node)."""
        return StreamSource(
            LMTokenStream(vocab_size=self.cfg.vocab_size,
                          seq_len=self.train.seq_len,
                          seed=self.train.seed),
            self.n_workers, self.train.batch_per_worker,
            assignment=self._assignment)

    def run(self, steps: int, source=None, *, prefetch: int = 2,
            on_step=None, faults=None) -> Optional[dict]:
        """Run ``steps`` epochs fed by ``source`` through the prefetched
        data plane; returns the last epoch's metrics (None at 0 steps).

        ``source`` is any :class:`repro.data.InputSource` (default:
        :meth:`batch_source`).  With ``prefetch >= 1`` a background
        :class:`repro.data.Prefetcher` keeps that many batches
        device-resident ahead of the consumer — epochs are drawn from
        the source at absolute indices ``steps_done .. steps_done +
        steps``, so a restored session continues the data order where
        the saved one stopped.  ``prefetch=0`` is the synchronous
        baseline (build, put, then step — the pre-dataplane behavior,
        kept for A/B timing).  ``on_step(epoch, metrics)`` is called
        after every epoch with the 0-based absolute index of the epoch
        that just ran (``steps_done`` has already advanced past it).

        ``faults`` is a :class:`repro.faults.FaultModel` (or a prebuilt
        :class:`repro.faults.FaultInjector`) applied *before* each
        epoch: membership changes go through :meth:`set_active` (which
        drains any in-flight async consensus first), slowdowns through
        :meth:`set_slowdown`.  The fault trajectory is a pure function
        of the epoch index, so a restored session under the same model
        replays it exactly.  Note the data plane keeps over-provisioning
        every worker's slots — a downed worker's samples are simply
        zero-weighted (or, under coded redundancy, re-covered by its
        group peers).
        """
        if steps <= 0:
            return None
        if source is None:
            source = self.batch_source()
        injector = None
        if faults is not None:
            from ..faults import FaultInjector
            injector = faults if isinstance(faults, FaultInjector) \
                else FaultInjector(faults)
        out = None
        if prefetch < 1:
            for epoch in range(self.steps_done, self.steps_done + steps):
                if injector is not None:
                    injector.apply(self, epoch)
                out = self.step(source.batch(epoch))
                if on_step is not None:
                    self._on_step(on_step, out)
            return out
        pf = Prefetcher(source, self.mesh, self._batch_axes,
                        depth=prefetch, start_epoch=self.steps_done,
                        steps=steps)
        try:
            # exactly ``steps`` takes, so no amb.data.wait span waits on
            # the end-of-stream marker
            for _ in range(steps):
                batch = next(pf)
                # the prefetcher yields epochs in order from steps_done,
                # so the incoming batch's epoch IS the current counter
                if injector is not None:
                    injector.apply(self, self.steps_done)
                out = self.step(batch)
                if on_step is not None:
                    self._on_step(on_step, out)
        finally:
            pf.close()
        return out

    def _on_step(self, on_step, out: dict) -> None:
        """The caller's per-epoch callback, under its own span."""
        epoch = self.steps_done - 1
        with TraceAnnotation(SPAN_ON_STEP, epoch=epoch):
            on_step(epoch, out)

    def _control(self, m: dict, out: dict, b: Array, times: Array):
        """Feed the epoch to the controller; apply any action in-place."""
        # measured mean per-gradient seconds, from the time each node
        # *actually spent* on the gradients it finished — exact even when
        # b_i saturates the data cap and the node idles out the window
        # (the naive T / b_i would over-bill those nodes and turn the
        # Lemma-6 re-solve into a positive feedback loop)
        tnp, bnp = np.asarray(times), np.asarray(b)
        eff = np.minimum(bnp, tnp.shape[1])
        done = eff >= 1
        tau_s = None
        if done.any():
            elapsed = np.cumsum(tnp, axis=1)[np.arange(tnp.shape[0]),
                                             np.maximum(eff, 1) - 1]
            tau_s = float(np.mean(elapsed[done] / eff[done]))
        rec = EpochRecord(
            t=self.steps_done, budget_s=out["budget_s"],
            comm_time_s=self.clock_spec.comm_time, step_s=out["step_s"],
            loss=out["loss"], b=bnp, tau_s=tau_s,
            global_batch=out["global_batch"],
            staleness=self.consensus_spec.staleness
            if self.consensus_spec.async_epochs else 1,
            grad_sq_norm=(float(m["grad_sq_norm"])
                          if "grad_sq_norm" in m else None),
            grad_var=float(m["grad_var"]) if "grad_var" in m else None)
        action = self.controller.observe(rec)
        if action is None:
            return None
        if action.budget is not None:
            self.clock.set_budget(action.budget)
        if action.staleness is not None:
            self._apply_staleness(action.staleness)
        # a b_target move needs no actuation here: it feeds the next
        # Lemma-6 re-solve, so the batch is driven through the deadline T
        return action

    def flush(self) -> None:
        """Settle in-flight consensus (pipelined mode); no-op otherwise."""
        with use_sharding(self.mesh):
            self.state = self._flush_fn(self.state)

    def _apply_staleness(self, staleness: int) -> None:
        """Retune the async driver's D mid-run: drain, rebuild, migrate.

        The in-flight queue is **drained first** (a plain ``flush``, the
        same move :meth:`set_active` makes): every queued payload was
        packed with the *old* D's damping gamma and must settle under
        the operator it was enqueued against.  The new driver then
        starts from an empty queue — the settled dual ``z`` and the
        epoch counter ``t`` carry over, the ``staleness``-shaped queue
        (and snapshot) leaves are re-initialized to the flushed-empty
        zeros.  Rebuilds go through the same ``(mask, staleness)``
        protocol cache as :meth:`set_active`, so revisiting a D reuses
        the warm executable.
        """
        if staleness == self.consensus_spec.staleness:
            return
        if not self.consensus_spec.async_epochs:
            raise ValueError("staleness is the async driver's knob; this "
                             "session runs "
                             f"{self.protocol.mode!r}")
        self.flush()    # settle the queue under the D it was packed for
        self.consensus_spec = self.consensus_spec.replace(
            staleness=int(staleness))
        self._build_protocol(self._active)
        with use_sharding(self.mesh):
            fresh = self.protocol.init(self.state["w0"])
            fresh["z"] = self.state["z"]
            fresh["w0"] = self.state["w0"]
            fresh["t"] = self.state["t"]
            self.state = _unalias(fresh)

    def close(self) -> None:
        """Release the metrics logger (idempotent)."""
        if self.metrics is not None:
            self.metrics.close()
            self.metrics = None

    # -- the iterate -------------------------------------------------------

    @property
    def params(self):
        """The current primal iterate, identical across modes.

        Exact mode: the optimizer's parameters.  Gossip modes: the
        node-averaged prox of the dual replicas
        (:func:`repro.dist.amb.gossip_primal`).  Pipelined sessions
        should ``flush()`` first so the last enqueued message is folded
        in.
        """
        with use_sharding(self.mesh):
            return self.protocol.primal(self.state)

    def save(self, directory) -> None:
        """Checkpoint the primal + full TrainState at the current step.

        Layout: ``<dir>/step_<n>/`` keeps the primal-only public layout
        (what ``launch/serve`` style consumers read), and two restore
        companions are written alongside: ``<dir>/session_state/
        step_<n>/`` — the protocol TrainState (optimizer or dual-replica
        state, any in-flight consensus queue, the epoch counter) — and
        ``<dir>/session.json`` — the spec triple plus session counters.
        Together they let :meth:`restore` resume exactly.
        """
        directory = Path(directory)
        save_checkpoint(directory, self.steps_done, self.params)
        state_dir = save_checkpoint(directory / "session_state",
                                    self.steps_done, self.state)
        meta = {
            "step": self.steps_done,
            "sim_wall_s": self.sim_wall,
            "train": self.train.to_dict(),
            "clock": self.clock_spec.to_dict(),
            # NB: consensus_spec reflects the *current* staleness (the
            # controller may have retuned D), so a restore rebuilds the
            # driver whose queue shapes match the checkpointed state
            "consensus": self.consensus_spec.to_dict(),
            "active": None if self._active is None else list(self._active),
            "sec_per_grad": getattr(self.clock, "sec_per_grad", None),
            # the budget actually in force (controller actions pin it)
            "clock_budget": getattr(
                self.clock, "budget_t",
                getattr(self.clock, "compute_time", None)),
            "controller": None if self.controller is None else {
                "spec": self.controller_spec.to_dict(),
                "state": self.controller.to_state()},
        }
        blob = json.dumps(meta, sort_keys=True, indent=1)
        # per-step copy first: counters/mask must match the state they
        # describe when restore() selects an older step; the root copy
        # names the latest step (the restore default)
        (state_dir / "session.json").write_text(blob)
        (directory / "session.json").write_text(blob)

    @classmethod
    def restore(cls, directory, *, step: Optional[int] = None, mesh=None,
                cfg=None, metrics_path=None) -> "AMBSession":
        """Rebuild a session from a :meth:`save` directory, resuming exactly.

        Recovers the spec triple from ``session.json``, then the full
        TrainState — parameters, optimizer / dual-replica state
        (including any in-flight consensus queue), and the step counter
        — plus the simulated wall clock, the measured-clock EMA, and the
        elastic membership mask.  A restored session continues the
        training trajectory of the saved one step-for-step.

        ``step`` selects a checkpoint (default: the latest, named in the
        root ``session.json``); counters, clock EMA, and the membership
        mask come from that step's own metadata copy, so an older
        checkpoint resumes *its* trajectory, not the latest save's.
        ``mesh`` / ``cfg`` override the rebuilt mesh or architecture
        config (shapes must match the checkpoint — ``cfg`` is required
        when the saved session used a custom one).
        """
        directory = Path(directory)
        meta = json.loads((directory / "session.json").read_text())
        step_sel = meta["step"] if step is None else step
        per_step = (directory / "session_state" / f"step_{step_sel:08d}"
                    / "session.json")
        if per_step.exists():
            meta = json.loads(per_step.read_text())
        ctl = meta.get("controller")
        session = cls(TrainSpec.from_dict(meta["train"]),
                      ClockSpec.from_dict(meta["clock"]),
                      ConsensusSpec.from_dict(meta["consensus"]),
                      None if ctl is None
                      else ControllerSpec.from_dict(ctl["spec"]),
                      mesh=mesh, cfg=cfg, metrics_path=metrics_path)
        if meta.get("active") is not None:
            session.set_active(meta["active"])   # before the state lands:
            # the drain-on-change flush must not touch the restored queue
        state = load_checkpoint(directory / "session_state", step_sel,
                                like=session.state)

        def land(got, cur):
            # re-establish the mesh layout of the freshly-built state;
            # leaves the protocol init left uncommitted (scalars like the
            # epoch counter) must stay uncommitted, or jit refuses to mix
            # them with the mesh-sharded leaves
            if isinstance(cur.sharding, jax.sharding.NamedSharding):
                return jax.device_put(got, cur.sharding)
            return jnp.asarray(got)

        with use_sharding(session.mesh):
            session.state = _unalias(jax.tree.map(land, state,
                                                  session.state))
        session.steps_done = step_sel
        session.sim_wall = float(meta.get("sim_wall_s", 0.0))
        if meta.get("sec_per_grad") is not None \
                and hasattr(session.clock, "sec_per_grad"):
            session.clock.sec_per_grad = float(meta["sec_per_grad"])
        if meta.get("clock_budget") is not None:
            # re-pin the budget that was in force (a controller may have
            # moved it off the spec-derived value); for an unpinned
            # measured clock this key is None and re-derivation survives
            session.clock.set_budget(float(meta["clock_budget"]))
        if ctl is not None and session.controller is not None:
            session.controller.load_state(ctl["state"])
        return session
