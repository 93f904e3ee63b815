"""NeuLite FL server: progressive rounds with memory-aware participation.

Implements the full workflow of paper Fig. 1 + Alg. 1:
  1. Model Construction  — stage t from the schedule; split params into
                           (frozen, trainable=[L_{t-1}, θ_t, θ_Op]).
  2. Local Training      — selected clients run E epochs of Eq. 5.
  3. Model Aggregation   — weighted FedAvg over the trainable subtree.
  4. Progress Evaluation — validation metric feeds the plateau schedule.
  5. Model Growing       — next stage (round-robin growth by default).

Steps 2-3 are delegated to a pluggable ``ClientRuntime`` (federated.runtime):
``"sequential"`` loops clients in Python (reference), ``"vectorized"`` runs
the whole cohort as one jitted program, ``"sharded"`` shards the cohort axis
over a device mesh.  The server never touches step functions directly.

Note: ``RoundResult.mean_loss`` is the |D_c|-weighted mean of client local
losses (consistent with the Eq. 1 aggregation weights) on every backend —
earlier revisions reported an unweighted client mean, so plateau-schedule
trajectories driven by train loss can differ from pre-runtime history.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Union

import jax
import numpy as np

from repro import optim
from repro.checkpoint import (latest_checkpoint, load_checkpoint,
                              read_checkpoint_meta, save_checkpoint)
from repro.common import spans
from repro.core import (CurriculumHP, PlateauSchedule, RoundRobinSchedule,
                        SequentialSchedule)
from repro.core.memory import estimate_full_memory, estimate_stage_memory
from repro.data.loader import Batcher
from repro.federated import aggregation as agg
from repro.federated.client import dropout_prob, sample_fault_steps
from repro.federated.devices import Fleet, MaterializedFleet
from repro.federated.runtime import (AsyncBufferedRuntime, AsyncServerState,
                                     ClientRuntime, make_runtime)
from repro.federated.selection import SelectionPolicy, make_policy


@dataclasses.dataclass
class FLConfig:
    n_devices: int = 100
    clients_per_round: int = 10
    local_epochs: int = 5
    batch_size: int = 32
    lr: float = 0.05
    momentum: float = 0.9
    weight_decay: float = 5e-4
    num_stages: int = 4
    boundary_units: int = 1
    schedule: str = "round_robin"       # round_robin | plateau | sequential
    rounds_per_stage: int = 10          # for sequential
    curriculum: bool = True             # ablation: w/o CA
    co_adaptation: bool = True          # ablation: w/o PC (plateau + no
                                        # boundary units + no surrogates)
    mu: float = 0.01
    lambda1: float = 2.0
    lambda2: float = 1.0
    use_hsic_kernel: bool = False       # route the curriculum's nHSIC terms
                                        # through the fused Pallas kernel
                                        # (interpret mode off-TPU)
    alpha: float = 1.0                  # Dirichlet concentration
    selection: str = "random"           # round-open cohort policy over the
                                        # streaming fleet: random | tifl |
                                        # oort (federated.selection)
    seed: int = 0
    runtime: str = "sequential"         # sequential | vectorized | sharded
                                        # | async
    # --- 2-D rounds; used when runtime is "sharded" or "async" ---
    model_parallel: int = 1             # "model"-axis size of the host mesh
                                        # (1 = replicate params, shard only
                                        # the cohort axis)
    # --- buffered-async (FedBuff) rounds; used when runtime == "async" ---
    buffer_size: int = 0                # server flushes every K deliveries
                                        # (0 = everything delivered this
                                        # round: synchronous); deliveries
                                        # short of K stay buffered and
                                        # flush in a later round
    staleness_schedule: str = "polynomial"   # constant | polynomial
    staleness_alpha: float = 0.5        # d(s) = (1+s)^-alpha
    server_lr: float = 1.0              # scale on each flushed buffer delta
    max_staleness: Optional[int] = None  # evict buffered deltas more than
                                         # this many server versions behind,
                                         # checked at each round open (None
                                         # = never drop a delivery)
    # --- mid-round client dropout / fault injection (any runtime) ---
    dropout_schedule: str = "none"      # none | constant | ramp
    dropout_rate: float = 0.0           # per-client fault probability
    # --- crash safety: periodic exact server checkpoints (run()) ---
    checkpoint_dir: Optional[str] = None  # save_state target; None = never
    checkpoint_every: int = 0           # save every N completed rounds
                                        # (0 = never; run() saves after
                                        # round r when (r+1) % N == 0)
    keep_checkpoints: int = 3           # rotation depth in checkpoint_dir


@dataclasses.dataclass
class RoundResult:
    round_idx: int
    stage: int
    n_selected: int
    n_feasible: int
    mean_loss: float
    upload_bytes: int
    sim_time: float
    test_acc: Optional[float] = None
    server_version: Optional[int] = None   # async: monotone server param
                                           # version after this round (one
                                           # bump per buffer flush)


class NeuLiteServer:
    """``client_datasets`` is either a materialized list of per-client
    datasets (wrapped into ``Batcher``s — the paper-scale path) or a lazy
    batcher bank (``data.partition.ProceduralClients`` or anything with
    ``bank[cid] -> Batcher`` and ``len``) for populations too large to
    materialize.  ``fleet`` overrides the streaming device fleet (e.g. a
    ``MaterializedFleet`` over externally profiled devices); by default a
    ``Fleet(flc.seed, flc.n_devices, full_model_bytes)`` is derived — the
    server never holds per-device state, so its memory is O(cohort) in the
    population.  ``selection_policy`` overrides ``flc.selection``."""

    def __init__(self, adapter, client_datasets, flc: FLConfig,
                 test_batcher: Optional[Batcher] = None,
                 data_kind: str = "image",
                 runtime: Union[str, ClientRuntime, None] = None,
                 fleet: Optional[Fleet] = None,
                 selection_policy: Optional[SelectionPolicy] = None):
        self.adapter = adapter
        self.flc = flc
        self.rng = np.random.default_rng(flc.seed)
        self.params = adapter.init_params(jax.random.PRNGKey(flc.seed))
        self.optimizer = optim.sgd(flc.lr, flc.momentum, flc.weight_decay)
        self.hp = CurriculumHP(lambda1_max=flc.lambda1,
                               lambda2_max=flc.lambda2, mu=flc.mu,
                               enabled=flc.curriculum,
                               use_hsic_kernel=flc.use_hsic_kernel)
        spec = runtime if runtime is not None else flc.runtime
        rt_kwargs = {}
        if spec == "async":
            rt_kwargs = dict(buffer_size=flc.buffer_size,
                             staleness_schedule=flc.staleness_schedule,
                             staleness_alpha=flc.staleness_alpha,
                             server_lr=flc.server_lr,
                             max_staleness=flc.max_staleness,
                             model_parallel=flc.model_parallel)
        elif spec == "sharded":
            rt_kwargs = dict(model_parallel=flc.model_parallel)
        self.runtime = make_runtime(spec, adapter, self.optimizer, self.hp,
                                    **rt_kwargs)
        self.test_batcher = test_batcher
        if isinstance(client_datasets, (list, tuple)):
            self.batchers = [Batcher(ds, flc.batch_size, seed=flc.seed + i,
                                     kind=data_kind)
                             for i, ds in enumerate(client_datasets)]
        else:
            # lazy bank: bank[cid] -> Batcher, derived on demand — a 10^6
            # population never materializes datasets on the server
            self.batchers = client_datasets
        T = adapter.plan.num_stages
        if not flc.co_adaptation:
            self.schedule = SequentialSchedule(T, flc.rounds_per_stage)
        elif flc.schedule == "round_robin":
            self.schedule = RoundRobinSchedule(T)
        elif flc.schedule == "plateau":
            self.schedule = PlateauSchedule(T)
        else:
            self.schedule = SequentialSchedule(T, flc.rounds_per_stage)
        full_mem = estimate_full_memory(adapter, flc.batch_size,
                                        seq=self._seq_len())
        self.fleet = (fleet if fleet is not None
                      else Fleet(flc.seed, flc.n_devices, full_mem.total))
        self.selector = (selection_policy if selection_policy is not None
                         else make_policy(flc.selection))
        self._devices = None
        if (isinstance(self.runtime, AsyncBufferedRuntime)
                and self.runtime.client_speeds is None):
            # the fleet's heterogeneous speeds drive the virtual clock;
            # arrivals are sampled from the FULL population each round, so
            # the runtime gets the fleet itself (O(1) state), not a dict
            self.runtime.client_speeds = self.fleet
        self.history: List[RoundResult] = []
        self.next_round: int = 0        # first round index run() will run
                                        # (> 0 after restore)

    @property
    def devices(self):
        """Materialized ``DeviceProfile`` list — compatibility view for
        list-shaped consumers (O(population): lazy, never built by the
        round loop)."""
        if self._devices is None:
            self._devices = self.fleet.profiles(range(self.fleet.n_devices))
        return self._devices

    @devices.setter
    def devices(self, profiles):
        # injecting an explicit profile list (e.g. table2 reuses a smaller
        # model's budgets to deepen the memory wall) must reach selection,
        # so it replaces the fleet wholesale, not just the compat view
        new_fleet = MaterializedFleet(profiles)
        if (isinstance(self.runtime, AsyncBufferedRuntime)
                and self.runtime.client_speeds is self.fleet):
            self.runtime.client_speeds = new_fleet
        self.fleet = new_fleet
        self._devices = list(profiles)

    # ------------------------------------------------------------------ #
    def _seq_len(self) -> int:
        """Sequence length for the memory model (0 for image tasks)."""
        ds = self.batchers[0].ds if self.batchers else None
        toks = getattr(ds, "tokens", None)
        return 0 if toks is None else toks.shape[1] - 1

    def stage_mem_requirement(self, t: int) -> int:
        return estimate_stage_memory(self.adapter, t, self.flc.batch_size,
                                     seq=self._seq_len()).total

    # ------------------------------------------------------------------ #
    def run_round(self, r: int) -> RoundResult:
        with spans.span("fl.round", round=r) as rs:
            rr = self._run_round(r)
            if rs is not None:
                rs.attrs["stage"] = rr.stage
        return rr

    def _run_round(self, r: int) -> RoundResult:
        flc = self.flc
        with spans.span("fl.open") as opening:
            t = self.schedule.stage(r)
            state = getattr(self.runtime, "state", None)
            if state is not None and not getattr(self.schedule,
                                                 "revisits_stages", True):
                # monotone schedule: stages before t never train again, so
                # their pending async deltas are permanently unusable —
                # retire them instead of stranding them in the buffer
                state.drop_retired_stages(t)
            req = self.stage_mem_requirement(t)
            selected, n_feasible = self.selector.select(
                self.rng, self.fleet, flc.clients_per_round, req, r)
            faults = None
            prob = dropout_prob(flc.dropout_schedule, flc.dropout_rate, r)
            if selected and prob > 0:
                targets = [flc.local_epochs
                           * self.batchers[cid].steps_per_epoch
                           for cid in selected]
                faults = sample_fault_steps(self.rng, targets, prob)
            if opening is not None:
                opening.attrs["n_selected"] = len(selected)

        if selected:
            out = self.runtime.run_round(self.params, t, self.batchers,
                                         selected, flc.local_epochs,
                                         faults=faults)
            self.params = out.params
            # count only updates the server actually aggregated this round:
            # step-0 crashes never upload, and an async delivery is charged
            # in the round its flush lands — a straggler pending at round r
            # that flushes at round r+k counts once, at r+k, never twice
            # and never zero times
            n_up = (out.n_uploads if out.n_uploads is not None
                    else len(selected))
            upload = agg.tree_bytes(out.trainable) * n_up
            # the round's ONE host sync: mean loss and the per-cohort
            # losses the selection policy needs come over together
            # (hostsync audit gates this — see repro.analysis)
            with spans.span("fl.sync"):
                mean_loss_h, cohort_losses_h = jax.device_get(
                    (out.mean_loss, out.cohort_losses))
            mean_loss = float(mean_loss_h)
            if out.round_sim_time is not None:
                # async: the round spans from open to its last buffer flush
                # on the server's ABSOLUTE virtual clock (0 when deliveries
                # only buffered), never the slowest straggler
                sim_times = [out.round_sim_time]
            else:
                speeds = self.fleet.speeds(selected)
                sim_times = [nb / s
                             for s, nb in zip(speeds, out.num_batches)]
            # feed the round's per-cohort losses back to the policy (Oort's
            # statistical utility); losses arrive in selected-cohort order
            self.selector.observe(
                selected, np.asarray(cohort_losses_h)[:len(selected)], r)
        else:
            upload, mean_loss, sim_times = 0, float("nan"), []

        acc = None
        if self.test_batcher is not None:
            with spans.span("fl.evaluate"):
                acc = self.evaluate()
            self.schedule.observe(r, 1.0 - acc)
        else:
            self.schedule.observe(r, mean_loss)

        rr = RoundResult(round_idx=r, stage=t, n_selected=len(selected),
                         n_feasible=n_feasible, mean_loss=mean_loss,
                         upload_bytes=upload,
                         sim_time=float(max(sim_times)) if sim_times else 0.0,
                         test_acc=acc,
                         server_version=getattr(
                             getattr(self.runtime, "state", None),
                             "version", None))
        self.history.append(rr)
        self.next_round = r + 1
        return rr

    def run(self, rounds: int, log_every: int = 0) -> List[RoundResult]:
        """Run ``rounds`` further rounds starting at ``self.next_round``
        (0 on a fresh server, the resume point after ``restore``).  With
        ``flc.checkpoint_dir`` set and ``flc.checkpoint_every > 0`` the
        complete round-loop state is checkpointed after every
        ``checkpoint_every``-th completed round, so a killed process
        resumes exactly from the last visible checkpoint."""
        flc = self.flc
        start = self.next_round
        for r in range(start, start + rounds):
            rr = self.run_round(r)
            if log_every and (r % log_every == 0):
                print(f"round {r:4d} stage {rr.stage} "
                      f"loss {rr.mean_loss:.4f} acc {rr.test_acc} "
                      f"feasible {rr.n_feasible}/{self.flc.n_devices}")
            if (flc.checkpoint_dir is not None and flc.checkpoint_every > 0
                    and (r + 1) % flc.checkpoint_every == 0):
                with spans.span("fl.checkpoint"):
                    self.save_state(flc.checkpoint_dir)
        return self.history

    # ------------------------------------------------------------------ #
    # crash safety: exact checkpoint / resume of the full round loop
    # ------------------------------------------------------------------ #
    _STATE_FORMAT = "neulite-server"
    _STATE_VERSION = 1

    def save_state(self, directory: str, *, step: Optional[int] = None,
                   keep: Optional[int] = None) -> str:
        """Checkpoint the COMPLETE round-loop state so ``restore`` resumes
        bit-exactly: server params, the async pending buffer (stacked delta
        pytrees + per-entry metadata, including stragglers carried across
        rounds), schedule counters, selector state (TiFL credits / Oort
        utilities), the server RNG's bit-generator state, per-client and
        test batcher RNG states (materialized banks; procedural banks are
        stateless), round history, and the resume point.  Atomic and
        dtype-exact via ``repro.checkpoint.save_checkpoint``."""
        tree = {"params": self.params}
        meta = {
            "format": self._STATE_FORMAT,
            "state_version": self._STATE_VERSION,
            "next_round": int(self.next_round),
            "runtime": self.runtime.name,
            "num_stages": int(self.adapter.plan.num_stages),
            "schedule_kind": type(self.schedule).__name__,
            "selector_kind": type(self.selector).__name__,
            "rng": self.rng.bit_generator.state,
            "schedule": self.schedule.state_dict(),
            "selector": self.selector.state_dict(),
            "history": [dataclasses.asdict(rr) for rr in self.history],
            "async": None,
            "batcher_rngs": None,
            "test_batcher_rng": None,
        }
        state = getattr(self.runtime, "state", None)
        if state is not None:
            arrays, ameta = state.state_dict()
            tree["async"] = arrays
            meta["async"] = ameta
        if isinstance(self.batchers, (list, tuple)):
            # materialized batchers hold mutable np RNGs that stack_round /
            # evaluate consume — without them resumed batch order diverges
            meta["batcher_rngs"] = [b.rng.bit_generator.state
                                    for b in self.batchers]
        if self.test_batcher is not None and hasattr(self.test_batcher,
                                                     "rng"):
            meta["test_batcher_rng"] = (
                self.test_batcher.rng.bit_generator.state)
        if step is None:
            step = self.next_round
        if keep is None:
            keep = self.flc.keep_checkpoints
        return save_checkpoint(directory, step, tree, meta=meta, keep=keep)

    def load_state(self, path: str) -> None:
        """Install the state saved by ``save_state`` into this server.
        The server must have been constructed with the same configuration
        the checkpointed run was started with (runtime kind, stage count,
        schedule/selector kinds are validated; everything mutable is then
        overwritten)."""
        meta = read_checkpoint_meta(path)
        if not isinstance(meta, dict) or meta.get("format") \
                != self._STATE_FORMAT:
            raise ValueError(
                f"{path!r} is not a NeuLiteServer state checkpoint "
                f"(save_state writes format={self._STATE_FORMAT!r}; plain "
                f"param checkpoints cannot resume a round loop)")
        mine = {"runtime": self.runtime.name,
                "num_stages": int(self.adapter.plan.num_stages),
                "schedule_kind": type(self.schedule).__name__,
                "selector_kind": type(self.selector).__name__}
        for key, have in mine.items():
            if meta.get(key) != have:
                raise ValueError(
                    f"checkpoint/server mismatch on {key}: saved "
                    f"{meta.get(key)!r}, this server has {have!r} — "
                    f"rebuild the server with the run's original config")
        like = {"params": self.params}
        if meta["async"] is not None:
            like["async"] = AsyncServerState.arrays_like(
                self.adapter, self.params, meta["async"])
        tree, _ = load_checkpoint(path, like)
        self.params = tree["params"]
        if meta["async"] is not None:
            self.runtime.load_server_state(
                AsyncServerState.from_state_dict(meta["async"],
                                                 tree["async"]))
        self.rng.bit_generator.state = meta["rng"]
        self.schedule.load_state_dict(meta["schedule"])
        self.selector.load_state_dict(meta["selector"])
        self.history = [RoundResult(**h) for h in meta["history"]]
        self.next_round = int(meta["next_round"])
        if meta["batcher_rngs"] is not None:
            if (not isinstance(self.batchers, (list, tuple))
                    or len(self.batchers) != len(meta["batcher_rngs"])):
                n = len(meta["batcher_rngs"])
                raise ValueError(
                    f"checkpoint carries {n} client batcher RNG states but "
                    f"this server holds "
                    f"{len(self.batchers)} materialized batchers")
            for b, s in zip(self.batchers, meta["batcher_rngs"]):
                b.rng.bit_generator.state = s
        if (meta["test_batcher_rng"] is not None
                and self.test_batcher is not None
                and hasattr(self.test_batcher, "rng")):
            self.test_batcher.rng.bit_generator.state = (
                meta["test_batcher_rng"])

    @classmethod
    def restore(cls, adapter, client_datasets, flc: FLConfig,
                directory: str, **kwargs) -> "NeuLiteServer":
        """Rebuild a server from its constructor arguments plus the newest
        complete checkpoint in ``directory`` (or an explicit ``ckpt_*.npz``
        path).  ``kwargs`` are forwarded to ``__init__`` and must mirror
        the original construction; the returned server's ``run(n)``
        continues from the checkpointed round."""
        if directory.endswith(".npz"):
            path = directory
        else:
            path = latest_checkpoint(directory)
            if path is None:
                raise FileNotFoundError(
                    f"no complete checkpoint found in {directory!r}")
        server = cls(adapter, client_datasets, flc, **kwargs)
        server.load_state(path)
        return server

    # ------------------------------------------------------------------ #
    def evaluate(self, max_batches: int = 8, *, batched: bool = True
                 ) -> float:
        """Accuracy over valid positions only.

        Works for both sequence-level (B,) and token-level (B, S) labels:
        a ``batch["mask"]`` (or negative labels) marks padding positions
        that are excluded from both numerator and denominator.

        ``batched=True`` (default) stacks the test batches on a leading
        axis and runs ONE jitted program that maps the forward pass over
        the stack (``lax.map`` — one batch's activation footprint, not
        ``max_batches`` at once) and reduces the correct/valid counts on
        device — a single host sync per evaluation instead of one logits
        transfer per batch.  A ragged final partial batch (external
        batchers may yield one; ``Batcher`` never does) is padded to the
        max batch shape with ``mask=False`` rows so the stack stays
        rectangular and the padding counts in neither numerator nor
        denominator.  ``batched=False`` keeps the per-batch reference
        loop; both paths count identically (regression-tested).
        """
        batches = []
        for i, batch in enumerate(self.test_batcher.epoch()):
            if i >= max_batches:
                break
            batches.append(batch)
        if not batches:
            return 0.0

        def valid_mask(batch):
            labels = np.asarray(batch["labels"])
            mask = batch.get("mask")
            return ((labels >= 0) if mask is None
                    else np.asarray(mask, bool))

        if not batched:
            correct = total = 0
            fwd = jax.jit(self.adapter.forward_eval)
            for batch in batches:
                logits = fwd(self.params, batch["inputs"])
                pred = np.asarray(logits.argmax(-1))
                mask = valid_mask(batch)
                correct += int(((pred == np.asarray(batch["labels"]))
                                & mask).sum())
                total += int(mask.sum())
            return correct / max(total, 1)

        B = max(np.asarray(b["labels"]).shape[0] for b in batches)

        def pad0(x):
            x = np.asarray(x)
            short = B - x.shape[0]
            if short == 0:
                return x
            return np.concatenate(
                [x, np.zeros((short, *x.shape[1:]), x.dtype)])

        inputs = jax.tree.map(lambda *xs: np.stack([pad0(x) for x in xs]),
                              *[b["inputs"] for b in batches])
        labels = np.stack([pad0(b["labels"]) for b in batches])
        # padded rows get mask=False: excluded from numerator & denominator
        mask = np.stack([pad0(valid_mask(b)) for b in batches])
        # one host sync for the whole evaluation (hostsync audit gates this)
        correct, total = jax.device_get(
            self._eval_program()(self.params, inputs, labels, mask))
        return int(correct) / max(int(total), 1)

    def _eval_program(self):
        if getattr(self, "_eval_fn", None) is None:
            fwd = self.adapter.forward_eval

            def counts(params, inputs, labels, mask):
                def one(args):
                    inp, lab, msk = args
                    hit = (fwd(params, inp).argmax(-1) == lab) & msk
                    return hit.sum(), msk.sum()

                correct, valid = jax.lax.map(one, (inputs, labels, mask))
                return correct.sum(), valid.sum()

            self._eval_fn = jax.jit(counts)
        return self._eval_fn

    @property
    def participation_rate(self) -> float:
        if not self.history:
            return 0.0
        return float(np.mean([h.n_feasible / self.flc.n_devices
                              for h in self.history]))
