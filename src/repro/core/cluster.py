"""VirtualCluster — the executable embodiment of ElasWave.

An in-process cluster of virtual workers arranged as a DP x PP grid.  Every
paper mechanism operates on REAL state with REAL numerics:

* per-layer parameters owned by pipeline stages (migratable pytrees);
* ZeRO-1 optimizer shards per (stage, dp-rank) under contiguous or
  interleaved layouts (core/zero.py), stored on the flat-state backbone
  (core/statespace.py): one contiguous fp32 buffer per component per stage,
  with memoized interval tables replacing per-call ``owner_intervals``
  rebuilds;
* per-step ring snapshots to host memory (core/fabric/snapshot.py);
* live remap on shrink (core/fabric/remap.py) — actual array movement,
  integrity-checked;
* dynamic communicator group edits (core/communicator.py);
* dataflow resizing with exact gradient weighting (planners/dataflow.py);
* content-addressed RNG (= RNG resharding) vs a deliberately rank-addressed
  "naive" mode for the §7.5 ablation;
* DVFS / fail-slow factors feed the 1F1B timing simulator.

Gradients are computed with jax.grad over the *full* model per micro-batch
slice (the logically-centralized equivalent of the pipeline's math), so the
elastic run's loss trajectory can be compared against a fault-free run.  The
distribution layer (who owns what, what moves on which event, what it costs)
is exactly the paper's; see DESIGN.md §3.

The step is one jitted, ``vmap``-batched call per micro-batch size bucket
that weights and sums the items' gradients on the device, with a single
``device_get`` of the sum, one fused host-side Adam update per stage, and an
indexed-scatter parameter write-back.  Recovery rebuilds only the stages an
event touches, and asserts that every move preserves the stage's state.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.data.pipeline import GlobalBatchSampler, materialize_samples
from repro.models import registry as R
from repro.models.config import ModelConfig
from repro.models.layers import RngCtx
from repro.optim.adam import AdamConfig, adam_plan, adam_update_flat_np
from repro.spans import span
from .agent import Agent, Probe
from .clusterview import GroupDelta
from .controller import ElasticController
from .communicator import DynamicCommunicator, build_hybrid_groups
from .cost_model import HardwareSpec, SegmentCosts
from .engine import RecoveryPlan, ScheduleEngine
from .events import ElasticEvent, EventKind
from .fabric.remap import LiveRemap, RemapPlan
from .fabric.snapshot import ADAM_STATE_BYTES, SnapshotPool
from .migration import MigrationSpec, migration_timing
from .pipeline import StageTiming, simulate_1f1b
from .statespace import (COMPONENTS, HEAD, STEM, EntryFlattener, StageState,
                         get_table)


def _recovery_record(*, detect: float = 0.0, plan: float = 0.0,
                     communicator: float = 0.0, remap: float = 0.0,
                     migration: float = 0.0, verify: float = 0.0,
                     rng_moves: int = 0, degraded: int = 0,
                     overlap_saved: float = 0.0) -> Dict[str, float]:
    """One schema for every recovery record, regardless of event kind, so
    ``_merge_recovery_records`` output shape never depends on the event.

    ``verify`` (snapshot integrity scan) is a timed phase included in the
    total; ``degraded`` counts tolerance-tier shard rebuilds (zeroed Adam
    moments) and ``overlap_saved`` is stall hidden inside a preemption-notice
    window — info counters, not stall time, so they stay out of the total."""
    return {"detect": detect, "plan": plan, "communicator": communicator,
            "remap": remap, "migration": migration, "verify": verify,
            "total": detect + plan + communicator + remap + migration + verify,
            "rng_moves": rng_moves, "degraded": degraded,
            "overlap_saved": overlap_saved}


class VirtualCluster:
    def __init__(self, cfg: ModelConfig, dp: int, pp: int, *,
                 global_batch: int, num_micro: int, seq_len: int,
                 seed: int = 0, zero_layout: str = "interleaved",
                 adam: Optional[AdamConfig] = None,
                 rng_mode: str = "reshard",        # "reshard" | "naive"
                 hw: Optional[HardwareSpec] = None,
                 mem_cap: Optional[float] = None,
                 snapshot_enabled: bool = True,
                 non_blocking_migration: bool = True,
                 use_pallas: Optional[bool] = None):
        assert global_batch % num_micro == 0
        assert (global_batch // num_micro) % dp == 0, "initial even split"
        if use_pallas is None:
            # the platform picks the kernels: Pallas (Mosaic) on a TPU, the
            # plain-jnp forward elsewhere; an explicit argument wins
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.cfg = cfg
        self.dp0, self.pp = dp, pp
        self.global_batch, self.num_micro, self.seq = global_batch, num_micro, seq_len
        self.adam = adam or AdamConfig(master_weights=True)
        self.rng_mode = rng_mode
        self.hw = hw or HardwareSpec()
        self.zero_layout = zero_layout
        self.snapshot_enabled = snapshot_enabled
        self.non_blocking_migration = non_blocking_migration
        self.sampler = GlobalBatchSampler(global_batch, seed)
        self.base_key = jax.random.key(seed)

        # ---- model state (fp32 for deterministic CPU math) ----
        L = cfg.num_layers
        key = jax.random.key(seed + 1)
        ks = jax.random.split(key, L + 2)
        self.stem = R.init_stem(ks[0], cfg)
        self.layer_params: List[Any] = [R.init_layer(ks[1 + i], cfg, i)
                                        for i in range(L)]
        self.head = R.init_head(ks[L + 1], cfg)
        self.flattener = EntryFlattener()
        self.flattener.build_model_unraveler(self.stem, self.layer_params,
                                             self.head)
        # balanced initial layer assignment
        per = L // pp
        rem = L % pp
        ranges, a = [], 0
        for p in range(pp):
            b = a + per + (1 if p < rem else 0) - 1
            ranges.append((a, b))
            a = b + 1
        self.layer_assignment: List[Tuple[int, int]] = ranges

        # ---- workers / health ----
        self.alive = np.ones((dp, pp), dtype=bool)
        self.freq = np.ones((dp, pp))
        self.slow = np.ones((dp, pp))
        self.mem_used = np.zeros((dp, pp))   # fraction of capacity (probes)

        # ---- ZeRO stage states + snapshots ----
        self.stages: List[StageState] = []
        self.snapshots: List[SnapshotPool] = []
        for p in range(pp):
            st = self._build_stage_state(p, list(range(dp)))
            self.stages.append(st)
            pool = SnapshotPool(dp, self.adam)
            if snapshot_enabled:
                pool.bootstrap(0, [st.shard(r) for r in st.dp_ranks])
            self.snapshots.append(pool)

        # ---- control plane ----
        self.comm = DynamicCommunicator(build_hybrid_groups(dp, pp))
        # rank = d * pp + p, so the agent's stage topology is rank % pp —
        # fail-slow verdicts compare against stage peers, not the fleet
        self.agent = Agent(dp * pp,
                           stage_of={r: r % pp for r in range(dp * pp)})
        self.controller = ElasticController(self.agent)
        self.engine = ScheduleEngine(cfg, seq_len, self.hw, mem_cap)
        self.remapper = LiveRemap()

        # ---- bookkeeping ----
        self.step_count = 0
        self.opt_step = 0
        self.per_rank_mbs: List[int] = [global_batch // num_micro // dp] * dp
        self.grad_weights: List[float] = [1.0 / dp] * dp
        self.losses: List[float] = []
        self.recoveries: List[Dict[str, float]] = []
        self.warnings: List[ElasticEvent] = []   # advisory (OOM_RISK) events
        self.seg = SegmentCosts.build(cfg, seq_len, self.hw)
        self._scan_grad_cache: Dict[Tuple[int, int], Any] = {}

    # ------------------------------------------------------------------
    # state-space helpers
    # ------------------------------------------------------------------
    def _entry_tree(self, entry: int):
        if entry == STEM:
            return self.stem
        if entry == HEAD:
            return self.head
        return self.layer_params[entry]

    def _stage_entries(self, p: int) -> List[int]:
        a, b = self.layer_assignment[p]
        entries = list(range(a, b + 1))
        if p == 0:
            entries = [STEM] + entries
        if p == self.pp - 1:
            entries = entries + [HEAD]
        return entries

    def _build_stage_state(self, p: int, dp_ranks: List[int]) -> StageState:
        entries = self._stage_entries(p)
        vecs = [self.flattener.flatten_entry(e, self._entry_tree(e))
                for e in entries]
        sizes = [v.size for v in vecs]
        full = np.concatenate(vecs) if vecs else np.zeros(0, np.float32)
        return StageState.from_full(
            entries, sizes, self.zero_layout, dp_ranks,
            {"master": full, "mu": np.zeros_like(full),
             "nu": np.zeros_like(full)})

    def _write_params_from_masters(self):
        # indexed scatter (one fancy-index per stage, straight into the
        # model-flat buffer) + ONE jitted model unravel (a single
        # host->device transfer for the whole model)
        vec = np.empty(sum(st.total for st in self.stages), dtype=np.float32)
        with span("step.writeback", nbytes=vec.nbytes):
            off = 0
            for st in self.stages:
                st.table.scatter(st.flat["master"],
                                 out=vec[off:off + st.total])
                off += st.total
            self.stem, self.layer_params, self.head = \
                self.flattener.unflatten_model(vec)

    # ------------------------------------------------------------------
    # training math
    # ------------------------------------------------------------------
    def _loss_fn(self, stem, layers, head, tokens, labels, step_key, sample_ids):
        # self.use_pallas routes the forward through the Pallas kernels
        cfg = self.cfg
        x = R.apply_stem(stem, cfg, tokens, use_pallas=self.use_pallas)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        ctx = RngCtx(step_key=step_key, sample_ids=sample_ids,
                     deterministic=cfg.dropout_rate <= 0.0)
        aux_total = jnp.zeros((), jnp.float32)
        for lid in range(cfg.num_layers):
            x, aux = R.apply_layer(layers[lid], cfg, lid, x, positions, ctx,
                                   use_pallas=self.use_pallas)
            aux_total = aux_total + aux
        logits = R.apply_head(head, cfg, x, use_pallas=self.use_pallas)
        from repro.models.transformer import softmax_xent
        return softmax_xent(logits[:, :-1], labels[:, 1:]) + aux_total

    def _batched_grad_fn(self, batch_size: int, n_items: int):
        """One jitted call over ``n_items`` stacked micro-batches of
        ``batch_size``: the per-item losses, and the items' gradients
        weighted by ``weights`` (f32[n_items]) and summed into one flat
        float32 gradient, so the host fetches one vector however many items
        the bucket holds.  ``vmap`` batches the independent per-item grads
        (a ``lax.scan`` over items is ~1.5x slower on CPU).  The weights are
        an argument, not a traced constant, so a recovery that reweights the
        ranks runs the compiled program it has."""
        key = (batch_size, n_items)
        fn = self._scan_grad_cache.get(key)
        if fn is None:
            grad_one = jax.value_and_grad(self._loss_fn, argnums=(0, 1, 2))

            def batched(stem, layers, head, toks, labs, base_key, step, sids,
                        weights):
                # fold_in inside the jit: integer PRNG ops, bit-identical to
                # the eager fold, and one less host dispatch per step
                step_key = jax.random.fold_in(base_key, step)

                def one(tok, lab, sid):
                    return grad_one(stem, layers, head, tok, lab, step_key,
                                    sid)

                def fold(g):
                    # [n_items, ...] -> [...], a left fold in item order in
                    # float32: g[0]*w[0] + g[1]*w[1] + ...; per leaf, before
                    # the ravel, so no stacked flat copy is made
                    g = g.astype(jnp.float32)
                    acc = g[0] * weights[0]
                    for k in range(1, n_items):
                        acc = acc + g[k] * weights[k]
                    return acc
                losses, grads = jax.vmap(one)(toks, labs, sids)
                return losses, ravel_pytree(jax.tree.map(fold, grads))[0]

            fn = jax.jit(batched)
            self._scan_grad_cache[key] = fn
        return fn

    def _grad_calls(self, step: int):
        """The step's micro-items ``(rank, sample ids)`` in (micro, rank)
        order, one ``(item indices, jitted fn, args)`` call per micro-batch
        size bucket (uneven after a failure), and the bytes of tokens,
        sample ids and item weights uploaded for them.  Each
        item's weight, ``grad_weights[rank] / num_micro``, is read here on
        every call."""
        ids_by_rank = self.sampler.partition(step, self.per_rank_mbs,
                                             self.num_micro)
        items: List[Tuple[int, np.ndarray]] = []    # (rank, ids)
        for m in range(self.num_micro):
            for r, rank_ids in enumerate(ids_by_rank):
                ids = rank_ids[m]
                if len(ids):
                    items.append((r, ids))
        buckets: Dict[int, List[int]] = {}
        for k, (r, ids) in enumerate(items):
            buckets.setdefault(len(ids), []).append(k)
        calls, nbytes = [], 0
        for B, idxs in buckets.items():
            # one hash-materialization for the whole bucket (elementwise in
            # (sample_id, position), so reshape == per-item materialize)
            ids_cat = np.concatenate([items[k][1] for k in idxs])
            toks = materialize_samples(ids_cat, self.seq,
                                       self.cfg.vocab_size
                                       ).reshape(len(idxs), B, self.seq)
            if self.rng_mode == "reshard":
                sids = ids_cat.astype(np.int32).reshape(len(idxs), B)
            else:   # naive: rank-addressed streams (the paper's "w/o")
                sids = np.stack([np.arange(B, dtype=np.int32)
                                 + np.int32(items[k][0] * 100003)
                                 for k in idxs])
            weights = np.asarray([self.grad_weights[items[k][0]]
                                  / self.num_micro for k in idxs], np.float32)
            jt = jnp.asarray(toks)
            args = (self.stem, self.layer_params, self.head, jt, jt,
                    self.base_key, np.uint32(step), jnp.asarray(sids),
                    jnp.asarray(weights))
            calls.append((idxs, self._batched_grad_fn(B, len(idxs)), args))
            nbytes += toks.nbytes + sids.nbytes + weights.nbytes
        return items, calls, nbytes

    def _micro_grads(self, step: int) -> Tuple[float, np.ndarray]:
        """Weighted accumulation over micro-batches and DP slices — the
        numerics of dataflow-resized hybrid-parallel training.

        Micro-batches are bucketed by size (uneven after a
        failure), each bucket runs as ONE jitted vmap-batched call that
        weights and sums its items' gradients on the device, and one
        ``device_get`` per bucket (one per step in the common even-split
        case) fetches its losses and that one flat partial sum.  With one
        bucket the partial is the step's gradient; with several the host
        adds the partials in bucket order.  Returns ``(total_loss,
        model-flat gradient)``.
        """
        with span("step.inputs") as sp:
            items, calls, nbytes = self._grad_calls(step)
            sp.set_metadata(nbytes=nbytes)
        loss_rows: List[Any] = [None] * len(items)
        partials: List[np.ndarray] = []
        for idxs, fn, args in calls:
            # the wait before the fetch parts device time from the copy to
            # the host
            with span("step.device", items=len(idxs)):
                out = jax.block_until_ready(fn(*args))
            with span("step.fetch", nbytes=sum(x.nbytes for x in out)):
                losses, partial = jax.device_get(out)
            for i, k in enumerate(idxs):
                loss_rows[k] = losses[i]
            partials.append(partial)
        with span("step.accumulate", device_items=len(items),
                  elements=sum(p.size for p in partials[1:])):
            acc = partials[0]
            for partial in partials[1:]:
                acc = acc + partial
            total_loss = 0.0
            for k, (r, _ids) in enumerate(items):
                total_loss += (float(loss_rows[k])
                               * (self.grad_weights[r] / self.num_micro))
        return total_loss, acc

    def compile_step(self) -> List[Any]:
        """Compile the grad programs for the next ``train_step``
        (one per micro-batch size bucket) ahead of it and return them; the
        step then finds them in the jit cache.  Lets a caller time the
        compile apart from the step and read the compiled program."""
        _, calls, _ = self._grad_calls(self.step_count)
        programs = []
        for _, fn, args in calls:
            with span("compile.lower"):
                lowered = fn.lower(*args)
            with span("compile.compile"):
                programs.append(lowered.compile())
        return programs

    def train_step(self) -> float:
        step = self.step_count
        with span("step", step=step):
            loss, gflat = self._micro_grads(step)
            self.opt_step += 1
            grad_shard_by_stage: List[List[np.ndarray]] = []
            off = 0
            for p, st in enumerate(self.stages):
                with span("step.adam", elements=st.total, stage=p,
                          **adam_plan(st.total)):
                    # this stage's slice of the model-flat gradient, permuted
                    # to shard order with one fancy-index
                    gstage = gflat[off:off + st.total]
                    off += st.total
                    tbl = st.table
                    gshard = tbl.gather(gstage)
                    grad_shard_by_stage.append(tbl.split(gshard))
                    if st.total:
                        # ONE fused host-side Adam update over the stage's
                        # flat buffers (elementwise, so the shard order does
                        # not change a bit); the per-rank shards are views
                        # into the result
                        st.flat = adam_update_flat_np(gshard, st.flat,
                                                      self.opt_step, self.adam)
            self._write_params_from_masters()
            if self.snapshot_enabled:
                for p in range(self.pp):
                    with span("step.snapshot") as sp:
                        stats = self.snapshots[p].snapshot_step(
                            step, grad_shard_by_stage[p], self.opt_step)
                        sp.set_metadata(nbytes=stats.grad_bytes_sent)
            self.step_count += 1
            self.losses.append(loss)
        return loss

    # ------------------------------------------------------------------
    # timing model (feeds throughput benchmarks)
    # ------------------------------------------------------------------
    def simulate_step_time(self) -> float:
        stages = []
        per_micro = self.global_batch // self.num_micro
        for p, (a, b) in enumerate(self.layer_assignment):
            live = [d for d in range(self.dp0) if self.alive[d, p]]
            width = max(len(live), 1)
            mbs = -(-per_micro // width)
            worst = max((self.slow[d, p] / self.freq[d, p] for d in live),
                        default=1.0)
            eff = self.hw.peak_flops * self.hw.mfu / worst
            fl = self.seg.seg_fwd_flops(a, b, mbs)
            stages.append(StageTiming(fl / eff, 2 * fl / eff, self.num_micro))
        return simulate_1f1b(stages).step_time

    # ------------------------------------------------------------------
    # elasticity
    # ------------------------------------------------------------------
    def inject_fail_stop(self, d: int, p: int):
        self.alive[d, p] = False

    def inject_fail_slow(self, d: int, p: int, factor: float):
        self.slow[d, p] = factor

    def inject_mem_pressure(self, d: int, p: int, used_fraction: float):
        """Set the fraction of device memory worker (d, p) reports via its
        probes — feeds the Agent's OOM early-warning trend."""
        self.mem_used[d, p] = used_fraction

    def detect_and_recover(self) -> Optional[Dict[str, float]]:
        """Controller probes -> events -> ScheduleEngine plan -> executor.

        The loop bound is the controller's worst-case confirmation threshold
        (``max_confirm_misses``), not the bare miss limit: a rank that
        flapped earlier has an exponentially backed-off bar to clear."""
        with span("recover"):
            with span("recover.detect"):
                probes = []
                base_t = self.simulate_step_time()
                for d in range(self.dp0):
                    for p in range(self.pp):
                        probes.append(Probe(
                            self.step_count, d * self.pp + p,
                            heartbeat=bool(self.alive[d, p]),
                            step_seconds=base_t * self.slow[d, p],
                            mem_used=float(self.mem_used[d, p])))
                events: List[ElasticEvent] = []
                for _ in range(self.controller.max_confirm_misses()):
                    events = self.controller.observe(probes)
                    if events:
                        break
            if not events:
                return None
            return self.apply_event(events[0])

    def apply_event(self, ev: ElasticEvent) -> Dict[str, float]:
        """Recovery Executor entry point: one elastic event -> itemized MTTR.

        Multi-rank events (failure bursts) are applied as a deterministic
        rank-ordered sequence of single-rank recoveries; detection is paid
        once (the heartbeats are missed concurrently) and the control-plane
        phases accumulate."""
        t_detect = 0.5  # heartbeat interval bound (modeled)
        cells = [(r // self.pp, r % self.pp) for r in sorted(ev.ranks)]
        if ev.kind in (EventKind.FAIL_STOP, EventKind.SCALE_IN):
            recs = [self.recover_fail_stop(d, p,
                                           t_detect=t_detect if i == 0 else 0.0)
                    for i, (d, p) in enumerate(cells)]
            return _merge_recovery_records(recs)
        if ev.kind == EventKind.FAIL_SLOW:
            recs = [self.recover_fail_slow(d, p, ev.slow_factor,
                                           t_detect=t_detect if i == 0 else 0.0)
                    for i, (d, p) in enumerate(cells)]
            return _merge_recovery_records(recs)
        if ev.kind == EventKind.PREEMPT_NOTICE:
            # proactive drain: no detection phase (the scheduler TOLD us),
            # and recovery work overlaps the notice window
            recs = [self.drain_rank(d, p, deadline=ev.deadline)
                    for d, p in cells]
            return _merge_recovery_records(recs)
        if ev.kind == EventKind.SCALE_OUT:
            recs = [self.recover_scale_out(d, p) for d, p in cells]
            return _merge_recovery_records(recs)
        if ev.kind == EventKind.DVFS_SET:
            for d, p in cells:
                self.freq[d, p] = ev.freq
            return _recovery_record()
        if ev.kind == EventKind.OOM_RISK:
            # advisory: record the warning, no state or liveness change
            self.warnings.append(ev)
            return _recovery_record()
        raise ValueError(f"unsupported elastic event kind here: {ev.kind}")

    def build_view(self):
        """The cluster's health/topology state as the shared rank-vectorized
        ``core.clusterview.ClusterView`` (the analytic-plane currency).  The
        view's buffers alias ``self.alive``/``self.freq``/``self.slow``, so
        it is a live window, not a snapshot."""
        from .clusterview import ClusterView
        return ClusterView(self.dp0, self.pp, self.global_batch,
                           self.num_micro, self.seq,
                           list(self.layer_assignment),
                           alive=self.alive, freq=self.freq, slow=self.slow,
                           mem_cap=self.engine.mem_cap)

    def plan_event(self, ev: ElasticEvent) -> RecoveryPlan:
        """Mark the event's (single) rank dead and ask the ScheduleEngine for
        a joint Dataflow/Graph/DVFS/RNG RecoveryPlan (paper §4)."""
        rank = ev.ranks[0]
        d, p = rank // self.pp, rank % self.pp
        st = self.stages[p]
        if d not in st.dp_ranks:
            raise ValueError(
                f"rank {rank} (dp={d}, stage={p}) was already removed from "
                f"the stage's DP group; scenario traces must not re-fail a "
                f"recovered rank")
        with span("recover.plan"):
            self.alive[d, p] = False
            old_sample_rank = self._current_sample_assignment()
            return self.engine.plan_view(
                ev, self.build_view(), failed_dp_ranks=[d],
                old_sample_rank=old_sample_rank, dp=len(st.dp_ranks))

    def recover_fail_stop(self, d: int, p: int, t_detect: float = 0.5,
                          ) -> Dict[str, float]:
        """Full ElasWave recovery: plan + communicator edit + live remap +
        layer migration + dataflow/DVFS/RNG application."""
        ev = ElasticEvent(EventKind.FAIL_STOP, self.step_count,
                          (d * self.pp + p,))
        return self.apply_plan(self.plan_event(ev), t_detect=t_detect)

    def apply_plan(self, plan: RecoveryPlan, t_detect: float = 0.5,
                   drain: bool = False) -> Dict[str, float]:
        """Execute a shrink RecoveryPlan (the paper's event -> plan -> apply
        path): snapshot verification, communicator edit, live remap, layer
        migration, dataflow resize, DVFS top-up.  Returns the itemized MTTR
        record.

        ``drain=True`` is the proactive PREEMPT_NOTICE path: the departing
        rank's device state is still addressable (corrupt snapshots re-derive
        from it bit-for-bit), and the communicator/remap/migration work
        overlaps the notice window — only the part exceeding
        ``plan.event.deadline`` stalls training; the hidden part is recorded
        as ``overlap_saved``."""
        ev = plan.event
        rank = ev.ranks[0]
        d, p = rank // self.pp, rank % self.pp

        # --- snapshot integrity: verify (and repair) recovery sources ---
        t_verify, n_degraded = self._verify_snapshot_sources(
            p, failed=[d], drain=drain)

        # --- communicator: in-place edit ---
        with span("recover.communicator"):
            comm_stats = self.comm.apply(GroupDelta.shrink([d * self.pp + p]),
                                         "edit")

        # --- live remap of stage p's optimizer state ---
        with span("recover.remap") as sp:
            t_remap, remap_plan = self._live_remap_stage(p, failed=[d])
            # state that changes rank: plan intervals count fp32 elements
            sp.set_metadata(nbytes=remap_plan.total_bytes * ADAM_STATE_BYTES)

        # --- layer migrations (graph plan) ---
        t_migr = 0.0
        if plan.graph.feasible and plan.migrations:
            t_migr = self._apply_migrations(plan.migrations,
                                            list(plan.graph.stage_ranges))

        # --- dataflow: resize micro batches over surviving width ---
        self._apply_dataflow()

        # --- DVFS ---
        for dv in plan.dvfs:
            if dv.rank >= 0:
                for dd in range(self.dp0):
                    if self.alive[dd, dv.rank]:
                        self.freq[dd, dv.rank] = max(self.freq[dd, dv.rank], dv.freq)

        # the departed rank leaves the Agent's monitored set (it must not
        # accrue misses forever; a SCALE_OUT rejoin re-registers it)
        self.agent.remove_rank(rank)

        # --- overlap accounting (proactive drain only) ---
        t_comm = comm_stats.seconds
        overlap_saved = 0.0
        work = t_comm + t_remap + t_migr
        if drain and work > 0:
            stall = max(0.0, work - ev.deadline)
            scale = stall / work
            overlap_saved = work - stall
            t_comm *= scale
            t_remap *= scale
            t_migr *= scale

        rec = _recovery_record(
            detect=t_detect, plan=plan.plan_seconds,
            communicator=t_comm, remap=t_remap, migration=t_migr,
            verify=t_verify,
            rng_moves=len(plan.rng.layer_stream_moves)
            + len(plan.rng.sample_stream_moves),
            degraded=n_degraded, overlap_saved=overlap_saved)
        self.recoveries.append(rec)
        return rec

    def drain_rank(self, d: int, p: int, deadline: float = 120.0,
                   ) -> Dict[str, float]:
        """Proactive drain on PREEMPT_NOTICE: run the full shrink recovery —
        verified snapshot flush, communicator edit, live remap, migration —
        *inside* the notice window, before the preemption lands.  Detection
        cost is zero (the scheduler told us) and up to ``deadline`` seconds
        of recovery work overlap ongoing training."""
        ev = ElasticEvent(EventKind.PREEMPT_NOTICE, self.step_count,
                          (d * self.pp + p,), deadline=deadline)
        return self.apply_plan(self.plan_event(ev), t_detect=0.0, drain=True)

    def _verify_snapshot_sources(self, p: int, failed: List[int],
                                 drain: bool = False) -> Tuple[float, int]:
        """Online verification (paper §5.1) of the ring-snapshot shards the
        remap is about to trust, with graceful degradation:

        * checksum intact → use the shard (``verified``);
        * corrupt + rank still draining → re-derive bit-for-bit from the
          departing rank's device shard (``rederived``);
        * corrupt + rank dead → rebuild the fp32 master from the replicated
          model parameters (bit-exact: after write-back params == masters)
          with zeroed Adam moments (``rebuilt``, counted as degraded).

        Repairs land in ``pool.host`` *before* ``_live_remap_stage`` reads
        it, so the remap itself stays untouched.
        Returns (modeled verify seconds, degraded-shard count).
        """
        if not self.snapshot_enabled:
            return 0.0, 0
        st = self.stages[p]
        pool = self.snapshots[p]
        if not pool.integrity:
            return 0.0, 0
        t_verify, degraded, nbytes = 0.0, 0, 0
        old_ranks = list(st.dp_ranks)
        with span("recover.verify") as sp:
            for f in failed:
                j = old_ranks.index(f)
                held = pool.host[pool.holder_of(j)]
                if held is None:
                    continue    # holder dead: remap skips this source anyway
                t_verify += pool.verify_cost_seconds(j)
                nbytes += sum(v.nbytes for v in held.values())
                tier, _ = pool.verify_and_repair(
                    j,
                    device_state=st.shard(f) if drain else None,
                    master_fallback=None if drain else
                    (lambda jj=j: self._master_shard_from_params(p, jj)))
                if tier == "rebuilt":
                    degraded += 1
            sp.set_metadata(nbytes=nbytes)
        return t_verify, degraded

    def _master_shard_from_params(self, p: int, j: int) -> np.ndarray:
        """Tolerance-tier rebuild source: shard ``j`` of stage ``p``'s fp32
        master, regenerated from the replicated model parameters (which equal
        the masters bit-for-bit after ``_write_params_from_masters``)."""
        st = self.stages[p]
        vecs = [self.flattener.flatten_entry(e, self._entry_tree(e))
                for e in st.entries]
        full = np.concatenate(vecs) if vecs else np.zeros(0, np.float32)
        return st.table.split(st.table.gather(full))[j]

    def recover_scale_out(self, d: int, p: int) -> Dict[str, float]:
        """Worker (d, p) (re)joins: communicator edit (only the new member's
        links), reverse live-remap widening the stage's ZeRO group, dataflow
        resize back to the wider DP width (paper Fig. 8 scale-up)."""
        assert not self.alive[d, p], "worker already alive"
        self.alive[d, p] = True
        # dynamic rank registration: the (re)joining worker gets fresh
        # heartbeat/step-time tracking (clears any stale dead verdict, so a
        # rejoin that later fails again is re-detected)
        self.agent.add_rank(d * self.pp + p, stage=p)
        self.controller.note_join(d * self.pp + p)
        comm_stats = self.comm.apply(
            GroupDelta.grow([(g, d * self.pp + p)
                             for g in self.comm.groups
                             if g == f"dp_stage{p}_tp0"]), "edit")
        t_remap = self._widen_stage(p, joining=[d])
        self._apply_dataflow()
        rec = _recovery_record(communicator=comm_stats.seconds, remap=t_remap)
        self.recoveries.append(rec)
        return rec

    def _widen_stage(self, p: int, joining: List[int]) -> float:
        """Reverse remap: redistribute the stage state over a WIDER group.
        Sources: current owners' device shards; targets: new layout."""
        st = self.stages[p]
        old_ranks = list(st.dp_ranks)
        tbl = st.table
        new_ranks = old_ranks + [j for j in joining if j not in old_ranks]
        pre = {c: st.full(c) for c in COMPONENTS}
        device_parts = {r: tbl.owner_intervals(old_ranks.index(r))
                        for r in old_ranks}
        new_tbl = get_table(st.layout_kind, st.sizes, len(new_ranks))
        target_parts = {r: new_tbl.owner_intervals(j)
                        for j, r in enumerate(new_ranks)}
        plan = self.remapper.compute_plan(st.total, device_parts, {},
                                          target_parts)
        shards = st.shards      # views, built once for all components
        empty = np.zeros(0, np.float32)
        new_shards: Dict[int, Dict[str, np.ndarray]] = {r: {} for r in new_ranks}
        for comp in COMPONENTS:
            device_data = {r: tbl.segments(old_ranks.index(r), shards[r][comp])
                           for r in old_ranks}
            assembled = self.remapper.execute(plan, st.total, device_data, {})
            for r in new_ranks:
                new_shards[r][comp] = assembled.get(r, empty)
        st.replace_shards(new_ranks, new_shards)
        for comp in COMPONENTS:
            assert np.array_equal(st.full(comp), pre[comp]), \
                f"widen corrupted {comp}"
        self.snapshots[p] = SnapshotPool(len(new_ranks), self.adam)
        if self.snapshot_enabled:
            self.snapshots[p].bootstrap(self.step_count,
                                        [st.shard(r) for r in new_ranks])
        return plan.est_seconds

    def recover_fail_slow(self, d: int, p: int, factor: float,
                          t_detect: float = 0.5) -> Dict[str, float]:
        """Straggler mitigation: rebalance layers away from the slow stage +
        DVFS top-up (no state loss)."""
        self.slow[d, p] = max(self.slow[d, p], factor)
        per_micro = self.global_batch // self.num_micro

        def t(pp_, a, b):
            live = [dd for dd in range(self.dp0) if self.alive[dd, pp_]]
            width = max(len(live), 1)
            mbs = -(-per_micro // width)
            worst = max((self.slow[dd, pp_] for dd in live), default=1.0)
            fl = self.seg.seg_fwd_flops(a, b, mbs)
            return 3 * fl / (self.hw.peak_flops * self.hw.mfu / worst)

        def mem(pp_, a, b):
            return self.seg.seg_mem(a, b, per_micro, inflight=self.pp)

        from .planners.graph import minimax_layer_partition
        plan = minimax_layer_partition(self.cfg.num_layers, self.pp, t, mem,
                                       [self.engine.mem_cap] * self.pp)
        t_migr = 0.0
        if plan.feasible:
            old_stage = _stage_of(self.layer_assignment, self.cfg.num_layers)
            new_stage = _stage_of(plan.stage_ranges, self.cfg.num_layers)
            moves = [(lid, old_stage[lid], new_stage[lid])
                     for lid in range(self.cfg.num_layers)
                     if old_stage[lid] != new_stage[lid]]
            if moves:
                t_migr = self._apply_migrations(moves, list(plan.stage_ranges))
        rec = _recovery_record(detect=t_detect, migration=t_migr)
        self.recoveries.append(rec)
        return rec

    # ------------------------------------------------------------------
    # executor pieces
    # ------------------------------------------------------------------
    def _current_sample_assignment(self) -> Dict[int, int]:
        out, cursor = {}, 0
        for r, sz in enumerate(self.per_rank_mbs):
            for _ in range(sz):
                out[cursor] = r
                cursor += 1
        return out

    def _apply_dataflow(self):
        from .planners.dataflow import plan_dataflow
        with span("recover.dataflow"):
            # width of the narrowest stage defines surviving DP for data entry
            widths = [int(self.alive[:, p].sum()) for p in range(self.pp)]
            new_dp = max(min(widths), 1)
            df = plan_dataflow(self.global_batch, self.num_micro, new_dp)
            self.per_rank_mbs = list(df.micro_batch_sizes)
            self.grad_weights = list(df.grad_weights)

    def _live_remap_stage(self, p: int, failed: List[int],
                          ) -> Tuple[float, RemapPlan]:
        st = self.stages[p]
        pool = self.snapshots[p]
        tbl = st.table
        old_ranks = list(st.dp_ranks)
        # record pre-failure full vectors for verification
        pre = {c: self._stage_full_vec_with_snapshots(p, c, failed)
               for c in COMPONENTS}

        surviving = [r for r in old_ranks if r not in failed]
        device_parts = {r: tbl.owner_intervals(old_ranks.index(r))
                        for r in surviving}
        host_parts = {}
        for f in failed:
            holder = pool.holder_of(old_ranks.index(f))
            holder_rank = old_ranks[holder]
            if holder_rank in surviving and pool.host[holder] is not None:
                host_parts[f] = tbl.owner_intervals(old_ranks.index(f))
        new_tbl = get_table(st.layout_kind, st.sizes, len(surviving))
        target_parts = {r: new_tbl.owner_intervals(j)
                        for j, r in enumerate(surviving)}

        plan = self.remapper.compute_plan(st.total, device_parts, host_parts,
                                          target_parts)
        # execute with real arrays, per component; per-rank segment dicts are
        # zero-copy views of the flat buffers
        shards = st.shards
        empty = np.zeros(0, np.float32)
        new_shards: Dict[int, Dict[str, np.ndarray]] = {r: {} for r in surviving}
        for comp in COMPONENTS:
            device_data = {r: tbl.segments(old_ranks.index(r), shards[r][comp])
                           for r in surviving}
            host_data = {}
            for f in failed:
                holder = pool.holder_of(old_ranks.index(f))
                snap = pool.host[holder]
                if snap is None:
                    continue
                host_data[f] = tbl.segments(old_ranks.index(f), snap[comp])
            assembled = self.remapper.execute(plan, st.total, device_data,
                                              host_data)
            for r in surviving:
                new_shards[r][comp] = assembled.get(r, empty)
        st.replace_shards(surviving, new_shards)
        # verification (paper: online verification before resume)
        for comp in COMPONENTS:
            assert np.array_equal(st.full(comp), pre[comp]), \
                f"remap corrupted {comp}"
        # rebuild ring snapshot pool for the shrunken group
        self.snapshots[p] = SnapshotPool(len(surviving), self.adam)
        if self.snapshot_enabled:
            self.snapshots[p].bootstrap(self.step_count,
                                        [st.shard(r) for r in surviving])
        return plan.est_seconds, plan

    def _stage_full_vec_with_snapshots(self, p: int, comp: str,
                                       failed: List[int]) -> np.ndarray:
        """Pre-failure ground truth: survivors' device state + failed ranks'
        snapshot state."""
        st = self.stages[p]
        pool = self.snapshots[p]
        tbl = st.table
        full = np.zeros(st.total, dtype=np.float32)
        for j, r in enumerate(st.dp_ranks):
            if r not in failed:
                src = tbl.shard_view(st.flat[comp], j)
            else:
                snap = pool.host[pool.holder_of(j)]
                if snap is None:
                    continue
                src = snap[comp]
            tbl.scatter_shard(j, src, full)
        return full

    def _apply_migrations(self, moves: List[Tuple[int, int, int]],
                          new_ranges: List[Tuple[int, int]]) -> float:
        """Move layers between stages: optimizer-state slices (per layout) +
        parameter ownership.  Returns modeled stall seconds (MTTR).

        Only the stages whose entry list actually changes are
        rebuilt (a slice-move between two stages leaves the others' flat
        buffers and snapshot pools untouched); entry slices come from one
        gather per component per affected stage."""
        with span("recover.migration") as sp:
            total_stall = 0.0
            # compute per-move timing with the migration model
            step_window = self.simulate_step_time()
            for (lid, src, dst) in moves:
                st_src = self.stages[src]
                pbytes = int(self.seg.param_bytes[lid])
                obytes = int(self.seg.opt_bytes[lid])
                spec = MigrationSpec((lid,), src, dst, pbytes, obytes,
                                     dp=len(st_src.dp_ranks),
                                     zero_layout=self.zero_layout,
                                     blocking=not self.non_blocking_migration)
                timing = migration_timing(spec, self.hw.link_bw, step_window)
                total_stall += timing.stall_seconds
            old_entries = {p: list(self.stages[p].entries)
                           for p in range(self.pp)}
            self.layer_assignment = list(new_ranges)
            new_entries = {p: self._stage_entries(p) for p in range(self.pp)}
            affected = [p for p in range(self.pp)
                        if old_entries[p] != new_entries[p]]
            # batch-slice the moving/retained entry state out of affected
            # stages
            entry_state: Dict[int, Dict[str, np.ndarray]] = {}
            for p in affected:
                st = self.stages[p]
                tbl = st.table
                for comp in COMPONENTS:
                    fullc = st.full(comp)
                    for pos, e in enumerate(st.entries):
                        s_, e_ = tbl.layer_interval(pos)
                        entry_state.setdefault(e, {})[comp] = fullc[s_:e_]
            sp.set_metadata(nbytes=sum(entry_state[lid][c].nbytes
                                       for lid, _, _ in moves
                                       for c in COMPONENTS))
            for p in affected:
                survivors = list(self.stages[p].dp_ranks)
                entries = new_entries[p]
                sizes = [entry_state[e]["master"].size for e in entries]
                full_by_comp = {
                    c: (np.concatenate([entry_state[e][c] for e in entries])
                        if entries else np.zeros(0, np.float32))
                    for c in COMPONENTS}
                new_st = StageState.from_full(entries, sizes,
                                              self.zero_layout, survivors,
                                              full_by_comp)
                self.stages[p] = new_st
                self.snapshots[p] = SnapshotPool(len(survivors), self.adam)
                if self.snapshot_enabled:
                    self.snapshots[p].bootstrap(
                        self.step_count, [new_st.shard(r) for r in survivors])
        return total_stall

    def _entry_from_stage(self, e: int) -> Dict[str, np.ndarray]:
        for st in self.stages:
            if e in st.entries:
                pos = st.entries.index(e)
                s_, e_ = st.table.layer_interval(pos)
                return {c: st.full(c)[s_:e_] for c in COMPONENTS}
        raise KeyError(e)

    # convenience ------------------------------------------------------
    def run(self, steps: int) -> List[float]:
        return [self.train_step() for _ in range(steps)]


def _merge_recovery_records(recs: List[Dict[str, float]]) -> Dict[str, float]:
    """Combine per-rank recovery records of one burst into a single record:
    every itemized phase (and the total) accumulates; counters too."""
    if len(recs) == 1:
        return recs[0]
    out: Dict[str, float] = {}
    for rec in recs:
        for k, v in rec.items():
            out[k] = out.get(k, 0.0) + v
    return out


def _stage_of(ranges: Sequence[Tuple[int, int]], L: int) -> List[int]:
    out = [0] * L
    for p, (a, b) in enumerate(ranges):
        for l in range(a, b + 1):
            out[l] = p
    return out
