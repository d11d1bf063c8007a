"""High-level federated runtime: FedModel + FedOptimizer.

API-parity layer over the SPMD round engine, mirroring the reference's
FedModel/FedOptimizer protocol (fed_aggregator.py:54-463) so the
training scripts keep the same shape:

    model = FedModel(module, params, compute_loss, args)
    opt   = FedOptimizer(optimizer_params, args)
    scheduler = LambdaLR(opt, lambda_fn)
    ...
    scheduler.step()
    metrics = model(batch)     # one federated round (client pass)
    opt.step()                 # server update

What dissolved relative to the reference: worker processes, queues,
shared-memory tensors and the NCCL process group (SURVEY.md §2.9) —
``model(batch)`` runs one jitted SPMD program over the device mesh and
``opt.step()`` a second, replicated one. Only metrics cross to host.

Per-client communication accounting (the reference's distinctive
observability feature, fed_aggregator.py:171-196, 240-300) is kept,
with one simplification: instead of a deque of historical weight
vectors, we track per-coordinate ``last_updated`` round indices (from
the server update's support), so a returning client's download bytes
cover #{coords updated since it last participated} at the configured
downlink width (``accounting.py``; dense f32, or ``--downlink_encoding
delta``'s (idx, val) pairs + repeat bitmap). Identical to the
reference's count except for exact value-reversion collisions
(measure-zero) and without the deque's staleness clamp approximation.
The support of server update r is settled on the host one dispatch
late: ``opt.step()`` starts its copy and leaves it in one pending slot
(``defer_update``), and the next ``model(batch)`` applies it
(``note_update``) right after it has dispatched client program r+1,
so the unpacking runs while the device does and server r -> client
r+1 are enqueued back to back. Only round r+1's byte accounting reads
it, after that round's metrics; whatever else reads or writes the
accounting state (a checkpoint, ``finalize``, a second ``opt.step()``,
a direct ``note_update``) settles the slot first (``settle_update``),
so the state anyone sees is the state settling at once would give.
Uploads bill at the wire dtype: ``--sketch_dtype int8`` tables cost
r x c bytes + r f32 row scales, not 4 x r x c.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu import accounting
from commefficient_tpu.autopilot import (RoundVariantCache, apply_knobs,
                                         build_controller, key_of,
                                         key_str)
from commefficient_tpu.clientstore import (HostClientStore,
                                           StorePrefetcher,
                                           resolve_clientstore,
                                           shard_range, state_fields)
from commefficient_tpu.config import Config, NATURAL_NUM_CLIENTS
from commefficient_tpu.data import staging
from commefficient_tpu.core.rounds import (ClientStates,
                                           build_client_round,
                                           build_server_round,
                                           build_val_fn, round_plan,
                                           server_select_form,
                                           sketch_rot_form)
from commefficient_tpu.core.server import ServerState
from commefficient_tpu.privacy import build_accountant, noise_stream
from commefficient_tpu.telemetry import build_telemetry, clock, trace
from commefficient_tpu.telemetry.core import (compile_delta,
                                              compile_mark, set_current,
                                              setup_span)
from commefficient_tpu.ops.vec import flatten_params
from commefficient_tpu.parallel import make_mesh, make_mesh2d
from commefficient_tpu.parallel.mesh import (client_sharding,
                                             replicated,
                                             server_state_sharding,
                                             shard_batch)

# the most recently constructed FedModel; lets FedOptimizer(args) find
# its runtime without an explicit handle — honest parity with the
# reference's module-level globals (fed_aggregator.py:37-44)
_CURRENT_MODEL: Optional["FedModel"] = None


def _host(arr) -> np.ndarray:
    """Materialise a device array on the host, multi-process safe:
    arrays sharded across processes (per-client metrics on a
    multi-host mesh) are allgathered first — every process returns the
    same global value, preserving the replicated-server invariant."""
    if (getattr(arr, "is_fully_addressable", True)
            or getattr(arr, "is_fully_replicated", False)):
        return np.asarray(arr)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(arr,
                                                        tiled=True))


class _RoundVariant:
    """One lattice point's executable bundle: the knob-substituted
    Config plus its jitted round programs. jit is lazy, so building a
    variant costs a closure — XLA compiles on the variant's first
    dispatch (or under the autopilot's warm-ahead, which AOT-compiles
    into ``aot`` during the previous round's host phase). ``compiled``
    tracks which flavors have been charged to the ledger's per-variant
    ``vcompile_*:<key>`` counters."""

    __slots__ = ("key", "cfg", "round_fn", "round_probed", "server_fn",
                 "aot", "compiled")

    def __init__(self, key, cfg, round_fn, round_probed):
        self.key = key
        self.cfg = cfg
        self.round_fn = round_fn
        self.round_probed = round_probed
        self.server_fn = None   # built by FedOptimizer on first use
        self.aot = {}           # flavor -> AOT-compiled executable
        self.compiled = set()   # flavors already compile-stamped


class FedModel:
    """One federated model + its client-side runtime.

    ``compute_loss(params_pytree, batch, args) -> (loss, metrics...)``
    with masked-mean semantics over ``batch["mask"]`` (the per-task
    callbacks of cv_train.py:67-83 / gpt2_train.py:77-99).
    """

    @setup_span("model_build")
    def __init__(self, module, params, compute_loss: Callable,
                 args: Config, compute_loss_val: Optional[Callable] = None,
                 padded_batch_size: Optional[int] = None,
                 mesh=None, stats_fn: Optional[Callable] = None,
                 init_model_state=None):
        global _CURRENT_MODEL
        args.validate_runtime()
        self.module = module
        self.args = args
        self.compute_loss_train = compute_loss
        # static facts of the program the loss builds, counted on every
        # round record (train/gpt2_train.py: ``head.compact``)
        self._program_counters = dict(
            getattr(compute_loss, "program_counters", None) or {})
        self.compute_loss_val = compute_loss_val or compute_loss
        # BatchNorm running-stats parity mode: ``stats_fn(params,
        # client_batch) -> stats_pytree`` records each participating
        # client's batch statistics; the server blends their round
        # average into ``model_state`` (torch momentum 0.1) and eval
        # reads it — so eval metrics don't depend on eval batch
        # composition (reference models/resnet9.py BN eval). When set,
        # ``compute_loss_val`` must take (params, batch, args, state).
        self.stats_fn = stats_fn
        self.model_state = (jax.tree_util.tree_map(jnp.asarray,
                                                   init_model_state)
                            if stats_fn is not None else None)

        flat, unravel = flatten_params(params)
        args.grad_size = int(flat.size)
        self.unravel = unravel
        if mesh is None:
            devices = jax.devices()
            if args.num_devices > 0:
                if args.num_devices > len(devices):
                    raise ValueError(
                        f"--num_devices {args.num_devices} > "
                        f"{len(devices)} available devices")
                if jax.process_count() > 1:
                    raise ValueError(
                        "--num_devices is a single-host knob; on "
                        "multi-host pods the mesh must span every "
                        "process's devices (leave it at -1)")
                devices = devices[: args.num_devices]
            mesh2d = getattr(args, "mesh2d", None)
            # --mesh CxM: the pod-scale 2D mesh (clients × model).
            # Cx1 shapes behave exactly like the 1-D mesh (every 2D
            # code path gates on model_axis_size > 1); 1x1 compiles
            # the single-device program
            mesh = (make_mesh2d(*mesh2d, devices) if mesh2d
                    else make_mesh(devices))
        self.mesh = mesh

        num_clients = args.num_clients
        if num_clients is None:
            num_clients = NATURAL_NUM_CLIENTS.get(args.dataset_name)
        assert num_clients is not None, "num_clients unresolved"
        self.num_clients = num_clients

        # committed to every device of the mesh: left uncommitted the
        # vector lives on device 0 and each round program re-replicates
        # it on entry
        self.ps_weights = jax.device_put(flat, replicated(self.mesh))
        # per-client state placement (commefficient_tpu/clientstore):
        # device = dense (num_clients, ...) HBM arrays (below); host =
        # budgeted host arena + mmap spill, with only the round's W
        # participant rows materialised on device (gather -> H2D ->
        # round -> D2H -> write-back)
        self.clientstore = resolve_clientstore(args, num_clients)
        self.client_store = None
        self._prefetcher = None
        self._participant_feed = None
        self._store_pending = None
        self._prefetch_after_writeback = False
        if self.clientstore == "host":
            fields = state_fields(
                args, init_weights=(np.asarray(flat)
                                    if getattr(args, "do_topk_down",
                                               False) else None))
            self.client_store = HostClientStore(
                num_clients, fields,
                budget_bytes=args.clientstore_bytes,
                spill_dir=(args.clientstore_dir or None),
                owned=shard_range(num_clients))
            self.client_states = ClientStates(None, None, None)
            # gather/H2D overlap thread: single-process only — the
            # multi-host row exchange is a collective and must stay on
            # the main thread
            if fields and jax.process_count() == 1:
                self._prefetcher = StorePrefetcher(self.client_store)
        else:
            # big per-client buffers created directly sharded over the
            # client axis, row-padded to the mesh size — never
            # materialised replicated (see ClientStates.init)
            self.client_states = ClientStates.init(
                args, num_clients, flat,
                sharding=client_sharding(self.mesh))

        # --async_buffer_size K: buffered-arrival front end
        # (commefficient_tpu/asyncfed). The driver issues each sampled
        # cohort into an arrival queue and hands back a fold batch of
        # up to K arrived updates (dead-padded to the compiled cohort
        # width) plus the per-slot staleness vector the weighted fold
        # consumes. Host store participants get issue-round stamps so
        # the snapshot a buffered fold replays is auditable.
        self.async_k = int(getattr(args, "async_buffer_size", 0) or 0)
        self._async_driver = None
        if self.async_k > 0:
            from commefficient_tpu.asyncfed import AsyncRoundDriver
            stamp = (self.client_store.stamp_rounds
                     if self.client_store is not None else None)
            self._async_driver = AsyncRoundDriver(args, stamp=stamp)

        if padded_batch_size is None:
            padded_batch_size = (args.local_batch_size
                                 if args.local_batch_size > 0 else 1)
        self.padded_batch_size = padded_batch_size

        stats_fn_flat = None
        if stats_fn is not None:
            def stats_fn_flat(flat_params, batch):
                return stats_fn(self.unravel(flat_params), batch)

            def loss_flat_val_state(flat_params, batch, model_state):
                return self.compute_loss_val(
                    self.unravel(flat_params), batch, args,
                    model_state)
        else:
            def loss_flat_val(flat_params, batch):
                return self.compute_loss_val(self.unravel(flat_params),
                                             batch, args)

        # donate the per-client state buffers: the round returns their
        # updated versions and the stale ones are never read again —
        # halves peak memory for local-momentum/-error modes at scale
        def loss_tree(params_tree, batch, loss=compute_loss):
            return loss(params_tree, batch, args)

        # --probe_every/--probe_full: algorithm probes compile INTO
        # the round program (core/rounds.py). Two jitted variants when
        # the expensive recovery probe applies: the cheap one runs
        # off-cadence rounds, the recovery one every probe_period-th
        # round. jit is lazy, so a variant never dispatched never
        # compiles (probe_period == 1 only ever compiles the full one).
        self.probe_period = int(getattr(args, "probe_period", 0) or 0)
        probes_on = self.probe_period > 0

        def _build_round(cfg, with_probes, with_recovery):
            return jax.jit(
                build_client_round(
                    cfg, None, padded_batch_size,
                    mesh=self.mesh, stats_fn=stats_fn_flat,
                    tree_loss=loss_tree,
                    unravel=self.unravel,
                    dense_rows=(self.clientstore == "host"),
                    probes=with_probes,
                    probe_recovery=with_recovery,
                    client_weights=(self.async_k > 0)),
                donate_argnums=(1,))

        # bucketed re-jit cache: round programs live in a bounded LRU
        # keyed by the discrete knob lattice point they were built for
        # (autopilot/). The base variant's config IS ``args`` itself
        # (apply_knobs returns the same object at the base key), so
        # with the autopilot off the dispatched program — and its HLO —
        # is byte-identical to building jax.jit(build_client_round(
        # args, ...)) directly.
        def _build_variant(key):
            cfg = apply_knobs(args, key)
            return _RoundVariant(
                key, cfg, _build_round(cfg, probes_on, False),
                (_build_round(cfg, True, True)
                 if probes_on and cfg.mode == "sketch" else None))

        self._variants = RoundVariantCache(
            _build_variant,
            max_size=int(getattr(args, "autopilot_cache_size", 4) or 4))
        self._variant_key = key_of(args)
        self._autopilot = build_controller(args)
        if self._autopilot is not None:
            # --autopilot_pin starts (and holds) at the pinned point
            self._variant_key = self._autopilot.key
            if self._variant_key != key_of(args):
                self.args = args = apply_knobs(args, self._variant_key)
        self.pending_variant_key = self._variant_key
        # abstract round-call signature (ShapeDtypeStructs incl.
        # shardings), captured at the first dispatch; warm-ahead AOT
        # compiles against it. Input shapes are knob-independent — the
        # lattice only moves the sketch geometry/wire INSIDE the round.
        self._round_abstract = None
        if stats_fn is not None:
            self._val_fn = jax.jit(build_val_fn(
                args, loss_flat_val_state, stateful=True))
        else:
            self._val_fn = jax.jit(build_val_fn(args, loss_flat_val))

        # pending round state consumed by FedOptimizer.step
        self.pending_aggregated = None
        self.pending_client_ids = None
        self.round_index = 0
        self.training = True
        self.diverged = False  # set by trainers on NaN abort
        # fedavg local-SGD LR: ZERO until the first FedOptimizer.step
        # sets it, like the reference's shared g_lr tensor
        # (fed_aggregator.py:98-101, torch.zeros) — clients read the
        # value set by the *previous* round's step, and the trainer's
        # LR==0 "HACK STEP" aligns the schedule. Initialising to 1.0
        # made round 0 take full-gradient local steps (diverges
        # instantly at ResNet9 scale).
        self.fedavg_lr = 0.0
        # round-key stream genesis (data order / client sampling), not
        # a noise source — noise streams live in privacy/mechanism.py
        self._rng = jax.random.PRNGKey(args.seed)  # audit: allow(noise-confinement)

        # communication accounting
        self.last_updated = np.full(args.grad_size, -1, np.int64)
        self.client_last_seen = np.full(num_clients, -1, np.int64)
        self._update_round = 0
        self._rebuild_round_counts()
        # --downlink_encoding delta bookkeeping: the latest update's
        # support indices (None = dense/all coords), how many of them
        # repeat the update before it, and that previous update's
        # support size (the bitmap a round-fresh client holds)
        self._prev_support_idx: Optional[np.ndarray] = np.zeros(
            0, np.int64)
        self._repeat_count = 0
        self._bitmap_bits = 0
        # the one server update whose support is not applied to the
        # state above yet, as ``(support,)`` (``None`` is a support:
        # the dense update), and whether something other than the next
        # client pass settled the last one (defer_update/settle_update)
        self._pending_update: Optional[tuple] = None
        self._settled_early = False

        # round-ledger telemetry (commefficient_tpu/telemetry): spans
        # around each host-side round stage, byte totals unified with
        # the accounting above, memory/compile watermarks. Disabled
        # (no --ledger/--telemetry_console) it's a no-op fast path.
        self.telemetry = build_telemetry(args)
        # a loader built before this model and not handed its
        # telemetry finds it there, while this is the one live model
        set_current(self.telemetry)
        # probe bookkeeping: _probe_host holds materialised client-
        # pass values until the server pass completes the round's
        # dict. The alarm engine is None with probes off; it evaluates
        # even without sinks, so --on_divergence abort works
        # ledgerless.
        self._probe_host = {}
        self._prev_residual = None
        from commefficient_tpu.telemetry.alarms import build_alarm_engine
        self.alarm_engine = build_alarm_engine(args, self.telemetry)
        if self.alarm_engine is not None:
            # trace-derived skew escalates like any probe alarm: the
            # profiler's bucket merge calls straight into the engine
            self.telemetry.on_device_time = \
                self.alarm_engine.check_device_time
        # --dp sketch: the run's RDP accountant (privacy/). Charged
        # once per DISPATCHED round — the round program releases the
        # noised table whether or not its metrics ever materialise.
        # Its cumulative ε lands on the schema-v5 ledger keys and feeds
        # the privacy_budget_exhausted alarm. None with --dp off.
        self._accountant = build_accountant(args)
        from commefficient_tpu.parallel import mesh as mesh_lib
        topo = mesh_lib.topology_summary()
        # live operations plane (telemetry/live.py + flightrec.py):
        # exporter sink + flight recorder must attach BEFORE the meta
        # record below is emitted — the live sink derives clients/s
        # from the plan, the recorder stamps the bundle's meta. Both
        # stay None with the knobs unset (disabled fast path
        # untouched). Labels: the job index is parsed off the ledger
        # shard path (the shard IS the job identity under a
        # fedservice daemon); registry lineage arms only when the run
        # writes a ledger, matching maybe_write_manifest.
        from commefficient_tpu.telemetry.live import attach_live_plane
        from commefficient_tpu.telemetry.registry import config_hash
        from commefficient_tpu.telemetry.sinks import \
            job_index_of_ledger
        ledger = str(getattr(args, "ledger", "") or "")
        job = job_index_of_ledger(ledger)
        labels = {"process": topo["process_index"],
                  "run": config_hash(args)[:8]}
        if job is not None:
            labels["job"] = job
        self.live_sink, self.flightrec = attach_live_plane(
            self.telemetry, args, labels=labels,
            runs_dir="runs" if ledger else "")
        # per-run SLO engine (telemetry/slo.py): None unless a target
        # is set; observed once per round in step()
        from commefficient_tpu.telemetry.slo import build_slo_engine
        self._slo = build_slo_engine(args)
        self.telemetry.emit_meta(
            num_clients=num_clients,
            num_devices=int(np.prod(self.mesh.devices.shape)),
            process_index=topo["process_index"],
            process_count=topo["process_count"],
            clientstore=self.clientstore,
            mesh_shape={str(k): int(v)
                        for k, v in dict(self.mesh.shape).items()},
            plan=round_plan(args))

        _CURRENT_MODEL = self
        # what a loader places each round's batch with, one round
        # ahead (data/staging.py): handed over by the trainer, found in
        # staging.current() by a loader handed nothing. None under
        # --async_buffer_size: every batch is folded into another
        # before its round, and its copy would be made for nothing
        self.placement = (self.place_batch
                          if self._async_driver is None else None)
        if self.placement is not None:
            staging.publish(self.placement)

    # --- reference API surface ------------------------------------------

    def train(self, training: bool):
        self.training = training

    def __call__(self, batch):
        return (self._call_train(batch) if self.training
                else self._call_val(batch))

    def finalize(self):
        """Shutdown protocol parity (fed_aggregator.py:197-204): a
        device barrier, plus host client-store teardown (prefetch
        thread join, final write-back, spill-file removal)."""
        trace.end_round_marker()
        staging.withdraw(self.place_batch)
        self.placement = None
        self.settle_update()
        # audit: allow(host-sync) — the shutdown barrier IS the sync
        jax.block_until_ready(self.ps_weights)
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        self._store_writeback()
        if self.client_store is not None:
            self.client_store.close()
            self.client_store = None
        self.telemetry.close()

    def interrupted(self):
        """Crash-safety cleanup after a mid-round SIGTERM/exception:
        discard the partially-dispatched round's host-side state so
        ``finalize()`` (device barrier, store teardown, telemetry
        close) runs cleanly. Server state and residuals are left
        untouched — the last round-cadence autosave is the consistent
        restore point, and dropping the unfinished round keeps both
        the ledger and the client store free of a round the checkpoint
        never saw (a half-written-back round would desync store rows
        from the checkpointed server state)."""
        self._probe_host = {}
        self.pending_aggregated = None
        self.pending_client_ids = None
        self._store_pending = None

    # --- host client store (commefficient_tpu/clientstore) ---------------

    def attach_participant_feed(self, feed: Callable):
        """``feed() -> next round's participant client ids (or None)``
        — wires the sampler's one-round lookahead
        (data/fed_sampler.py peek_next_client_ids) into the prefetch
        thread so round N+1's gather/H2D overlaps round N's compute."""
        self._participant_feed = feed

    def attach_arrival_process(self, fn):
        """Inject a seeded arrival schedule into the async driver
        (tests/benches/scripts only — the arrival-confinement lint
        rule keeps injection out of package modules, so production
        keeps the punctual default). Requires --async_buffer_size."""
        assert self._async_driver is not None, \
            "attach_arrival_process needs --async_buffer_size > 0"
        self._async_driver.attach_arrival_process(fn)

    def _gather_rows(self, ids_np):
        """Host-side rows for this round's participants, prefetched
        when the lookahead predicted them, synchronous otherwise."""
        ids64 = np.asarray(ids_np, np.int64)
        rows = None
        if self._prefetcher is not None:
            rows = self._prefetcher.take(ids64)
            self.telemetry.count("prefetch_hit" if rows is not None
                                 else "prefetch_miss")
        if rows is None:
            rows, _ = self.client_store.gather(ids64)
        if jax.process_count() > 1 and rows:
            # every process contributed its owned rows (zeros
            # elsewhere): one allgather-sum rebuilds each participant
            # row everywhere. Main thread only — it's a collective.
            from jax.experimental import multihost_utils
            rows = {k: np.asarray(multihost_utils.process_allgather(
                        v, tiled=False)).sum(axis=0, dtype=np.float32)
                    for k, v in rows.items()}
        return rows

    def _rows_to_states(self, rows) -> ClientStates:
        def put(name):
            v = rows.get(name)
            return (None if v is None
                    else shard_batch(self.mesh, jnp.asarray(v)))

        return ClientStates(put("velocities"), put("errors"),
                            put("weights"))

    def _submit_prefetch(self):
        if self._prefetcher is None:
            return
        # buffered arrival: the driver beats the sampler — when the
        # backlog already holds the next fold's full buffer, its ids
        # (in fold-slot order, dead-padded) are known exactly. The
        # sampler lookahead covers the punctual/underfull case; a
        # wrong guess is just a prefetch miss (synchronous fallback).
        ids = (self._async_driver.peek_next_ids()
               if self._async_driver is not None else None)
        if ids is None and self._participant_feed is not None:
            ids = self._participant_feed()
        if ids is not None:
            self._prefetcher.submit(np.asarray(ids, np.int64))

    def _store_writeback(self):
        """D2H the pending round's updated participant rows into the
        store. Runs from FedOptimizer.step (after the server round's
        velocity rewrite, so true_topk's momentum-factor masking is
        captured), and defensively before the next gather, at
        checkpoint save and at shutdown. Dead slots (dropout/padding)
        are excluded, matching the device path's dropped scatters."""
        if self.client_store is None or self._store_pending is None:
            return
        with self.telemetry.span("writeback"):
            ids_np, alive = self._store_pending
            self._store_pending = None
            cs = self.client_states
            self.client_states = ClientStates(None, None, None)
            rows = {}
            for name, val in (("velocities", cs.velocities),
                              ("errors", cs.errors),
                              ("weights", cs.weights)):
                if val is not None:
                    rows[name] = np.asarray(_host(val), np.float32)
            if rows and alive.any():
                self.client_store.write(
                    ids_np[alive],
                    {k: v[alive] for k, v in rows.items()})

    def params(self):
        """Current weights as the module's pytree (the reference's
        lazy state_dict sync, fed_aggregator.py:374-378)."""
        return self.unravel(self.ps_weights)

    def save_pretrained(self, save_dir: str, hf_format: bool = False,
                        torch_format: bool = False):
        """HF-style final-model save (reference fed_aggregator.py:
        205-212 / gpt2_train.py:146): current server weights as a flax
        msgpack blob plus the module's config as JSON.

        ``hf_format=True`` (GPT-2 modules only) additionally writes
        ``pytorch_model.bin`` + an HF-`transformers` ``config.json`` so
        the directory loads with ``GPT2DoubleHeadsModel/GPT2LMHeadModel
        .from_pretrained`` — the model goes back to the torch/HF
        ecosystem the reference lives in. The HF config's field names
        are a superset of GPT2Config's, so this framework's own reload
        path (gpt2_train.build_model_and_tokenizer) reads the same dir
        too.

        ``torch_format=True`` (CV families) additionally writes
        ``state_dict.pt``: a torch ``state_dict`` with the reference
        torch modules' own key names and layouts
        (models/torch_export.py) — the reference's final CV artifact
        is exactly ``torch.save(model.state_dict(), ...)``
        (cv_train.py:420-423), including running BN stats when the
        model tracks them."""
        import dataclasses
        import json
        import os

        from flax import serialization

        os.makedirs(save_dir, exist_ok=True)
        # config first: a dir with weights but no config would rebuild
        # the wrong architecture on reload (gpt2_train reload path)
        cfg = getattr(self.module, "cfg", None)
        if torch_format:
            from commefficient_tpu.models.torch_export import \
                save_torch_state_dict
            save_torch_state_dict(
                self.module, self.params(),
                getattr(self, "model_state", None),
                os.path.join(save_dir, "state_dict.pt"))
        if hf_format:
            import torch

            from commefficient_tpu.models.gpt2 import (GPT2Config,
                                                       convert_gpt2_to_hf)
            if not isinstance(cfg, GPT2Config):
                raise ValueError("hf_format export is defined for "
                                 "GPT-2 modules only")
            sd, hf_cfg = convert_gpt2_to_hf(self.params(), cfg)
            with open(os.path.join(save_dir, "config.json"), "w") as f:
                json.dump(hf_cfg, f, indent=2)
            torch.save({k: torch.from_numpy(
                            np.array(v, copy=True))
                        for k, v in sd.items()},
                       os.path.join(save_dir, "pytorch_model.bin"))
        elif cfg is not None and dataclasses.is_dataclass(cfg):
            blob = {k: v for k, v in dataclasses.asdict(cfg).items()
                    if isinstance(v, (int, float, str, bool,
                                      type(None)))}
            with open(os.path.join(save_dir, "config.json"), "w") as f:
                json.dump(blob, f, indent=2)
        with open(os.path.join(save_dir, "flax_model.msgpack"),
                  "wb") as f:
            f.write(serialization.msgpack_serialize(
                jax.tree_util.tree_map(np.asarray, self.params())))

    # --- rounds ----------------------------------------------------------

    def _call_train(self, batch):
        tel = self.telemetry
        ridx = self.round_index
        tel.begin_round(ridx)
        # device-timeline marker, same lifecycle as the ledger record
        # (closed by the next round's begin): a flag check when no
        # profiler trace window is open
        trace.begin_round_marker(ridx)
        # the parent of every client-pass span: what its children do
        # not cover is its self time
        with tel.span("client_pass"):
            return self._client_pass(batch, ridx)

    def place_batch(self, batch):
        """A round's host batch on this model's mesh: ``(the (W, ...)
        arrays with the client axis sharded, the client ids
        replicated)``, both possibly still in flight. The one placement
        of a train round: ``_client_pass`` runs it where the batch
        comes without a copy, and a loader runs it a round ahead, on
        its own thread (data/staging.py)."""
        dev_batch = shard_batch(self.mesh, jax.tree_util.tree_map(
            jnp.asarray, {k: v for k, v in batch.items()
                          if k != "client_ids"}))
        ids = jax.device_put(
            jnp.asarray(np.asarray(batch["client_ids"]), jnp.int32),
            replicated(self.mesh))
        return dev_batch, ids

    def _client_pass(self, batch, ridx):
        args = self.args
        tel = self.telemetry
        eng = self.alarm_engine
        step_t0 = (clock.tick()
                   if eng is not None and eng.step_time_ratio > 0
                   else None)
        slo_t0 = clock.tick() if self._slo is not None else None
        staleness = None
        if self._async_driver is not None:
            # issue the sampled cohort into the arrival queue, then
            # fold what has actually arrived: the batch the round runs
            # is the buffer's head, dead-padded to the cohort width
            with tel.span("async_fold"):
                batch, staleness = self._async_driver.step(batch)
        ids_np = np.asarray(batch["client_ids"])
        # the copy the loader's thread made of this very batch a round
        # ago (resident, or landing while the previous round ran): no
        # copy is issued here and the program need not wait for one. A
        # batch that anything rebuilt since the loader (the fold above,
        # a chaos wrapper, mixup, ``dict(batch)``) carries none, nor
        # does one staged by another model's placement or with a field
        # replaced: those are placed here, as every batch used to be
        placed = staging.staged_copy(batch, self.place_batch)
        if placed is None:
            tel.count("h2d.inline")
            with tel.span("h2d"):
                placed = self.place_batch(batch)
        else:
            tel.count("h2d.staged")
        # what the loader counted in this batch (the labelled positions
        # of a language-model round) and what the loss says of the
        # program it builds (``head.compact``): engagement, no metric
        for name, n in {**staging.counters_of(batch),
                        **self._program_counters}.items():
            tel.count(name, n)
        dev_batch, ids = placed

        rng = jax.random.fold_in(self._rng, self.round_index)
        cs_in = self.client_states
        if self.client_store is not None:
            # normally a no-op: opt.step() already wrote round N-1's
            # rows back; covers trainers that skip the server step
            self._store_writeback()
            with tel.span("gather"):
                rows = self._gather_rows(ids_np)
            with tel.span("h2d_state"):
                cs_in = self._rows_to_states(rows)
        var = self._variants.get(self._variant_key)
        probed = (var.round_probed is not None
                  and ridx % self.probe_period == 0)
        flavor = "probed" if probed else "plain"
        jit_fn = var.round_probed if probed else var.round_fn
        # prefer the warm-ahead AOT executable when the switch compiled
        # one; otherwise the jit wrapper compiles lazily right here
        round_fn = var.aot.get(flavor, jit_fn)
        # the server pass must consume this aggregate with the SAME
        # variant's program — record the dispatch-time key, not
        # whatever the controller moves to afterwards
        self.pending_variant_key = var.key
        # staleness rides as a seventh positional arg only when the
        # async driver is on — the synchronous call site stays
        # byte-identical (and so does its compiled program)
        sargs = (() if staleness is None
                 else (shard_batch(self.mesh, jnp.asarray(staleness)),))
        rargs = (self.ps_weights, cs_in, dev_batch, ids, rng,
                 jnp.float32(self.fedavg_lr)) + sargs
        if self._round_abstract is None:
            # uncommitted arguments (the round key, the scalar LR) stay
            # unplaced: their default-device sharding would clash with
            # the mesh's when lowering for more than one device
            self._round_abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if a.committed else None),
                rargs)
        cmark = (compile_mark() if flavor not in var.compiled
                 else None)
        with tel.span("round_dispatch"):
            res = round_fn(*rargs)
        if cmark is not None:
            # ledger compile events carry the variant cache key — jit
            # compiles synchronously inside the dispatch, so the delta
            # around a variant's first call is its compile
            var.compiled.add(flavor)
            self._stamp_vcompile(var.key, cmark)
        self.client_states = res.client_states
        self.pending_aggregated = res.aggregated
        # dead slots (dropout / loader padding) must carry the
        # out-of-range sentinel into the SERVER round too: true_topk's
        # velocity masking scatters rows back at these ids, and a dead
        # client's momentum must stay untouched exactly like its
        # client-side state (core/rounds.py _state_ids; regression
        # found by tests/test_fuzz_modes.py)
        from commefficient_tpu.core.rounds import _state_ids
        if self.client_store is not None:
            # host mode: state rows are positional (dense_rows), so the
            # server round's velocity scatter needs slot positions —
            # dead slots keep the sentinel either way
            W = ids_np.shape[0]
            self.pending_client_ids = _state_ids(
                jnp.arange(W, dtype=jnp.int32), dev_batch)
            alive = np.asarray(batch["mask"]).reshape(W, -1) \
                .sum(axis=1) > 0
            self._store_pending = (np.asarray(ids_np, np.int64), alive)
            if int(getattr(args, "overlap_depth", 1)) > 1:
                # latency-hiding pipeline: a prefetch staged now
                # snapshots the store BEFORE opt.step()'s write-back,
                # so take() would synchronously re-gather every repeat
                # participant's row next round. Defer the submit to
                # step(), right after the write-back lands — the
                # background gather then overlaps whatever the trainer
                # does between opt.step() and the next model(batch)
                # (its own bookkeeping, the next batch's fetch) instead
                # of being undone by the write-back. The support's
                # bookkeeping (note_update / _note_delta_support) no
                # longer runs in that stretch: it follows the next
                # dispatch, under the device.
                self._prefetch_after_writeback = True
            else:
                self._submit_prefetch()
        else:
            self.pending_client_ids = _state_ids(ids, dev_batch)
        if self._accountant is not None:
            self._charge_privacy(ridx, var.cfg, staleness,
                                 np.asarray(batch["mask"]))
        self.round_index += 1
        if res.bn_stats is not None:
            # running-stats blend (torch BN momentum 0.1); a fully
            # dropped round contributes nothing. Lazy device ops on
            # per-channel vectors — no host sync.
            new_stats, alive = res.bn_stats
            self.model_state = jax.tree_util.tree_map(
                lambda ra, s: jnp.where(alive > 0,
                                        0.9 * ra + 0.1 * s, ra),
                self.model_state, new_stats)

        # the recorder finishes the previous round's record here, under
        # the program just dispatched, not between two dispatches
        tel.close_round()
        self._settle_after_dispatch()
        with tel.span("metrics_host"):
            metrics = [_host(m) for m in res.metrics]
            probe_vals = (None if res.probes is None else
                          {k: float(_host(v))
                           for k, v in res.probes.items()})
        self._note_metric_counters(ridx, metrics)
        if probe_vals is not None:
            # merge now (so eval-only callers still get them on the
            # ledger); the server pass completes the dict and runs the
            # alarms via _finish_probes
            tel.merge_round_probes(ridx, probe_vals)
            self._probe_host[ridx] = probe_vals
        astats = None
        if self._async_driver is not None:
            # buffered-arrival probes (staleness histogram, buffer
            # occupancy, backlog) are host-side driver state: merged
            # onto the ledger record every round, and routed to the
            # alarm engine through the round's probe dict when probes
            # are compiled in (so _finish_probes checks once) or
            # directly when they are not
            astats = self._async_driver.round_stats()
            tel.merge_round_probes(ridx, astats)
            if probe_vals is not None:
                self._probe_host[ridx].update(astats)
            elif self.alarm_engine is not None:
                self.alarm_engine.check(ridx, astats)
        if step_t0 is not None:
            # wall step time through the metrics sync — evaluated
            # before set_round_bytes so an aborting alarm still lands
            # on the record telemetry.close() will flush
            eng.check_step_time(ridx, clock.tick() - step_t0)
        if slo_t0 is not None:
            self._observe_slo(ridx, clock.tick() - slo_t0, astats)
        acct_ids, acct_mask = ids_np, batch["mask"]
        if self._async_driver is not None:
            # dead pad slots (id 0, mask 0) are queue padding, not
            # participants — they must not bill client 0 a download.
            # Folded ids route through the regular accounting, so a
            # stale client's downlink is priced by how far
            # client_last_seen lags (incl. delta have_prev freshness).
            alive = np.asarray(acct_mask).reshape(
                len(ids_np), -1).sum(axis=1) > 0
            acct_ids = ids_np[alive]
            acct_mask = np.asarray(acct_mask)[alive]
        with tel.span("account"):
            down, up = self._account_bytes(acct_ids, acct_mask,
                                           cfg=var.cfg)
        tel.set_round_bytes(ridx, float(down.sum()), float(up.sum()))
        return metrics + [down, up]

    #: ((counter name, fold over clients), ...) for the loss's extra
    #: results, in their order after the loss itself: what a model
    #: counts inside the round program (an expert layer's loads) lands
    #: on the round record as counters. Set by the trainer.
    metric_counters = ()

    def _note_metric_counters(self, ridx, metrics):
        if self.metric_counters:
            self.telemetry.set_round_counters(ridx, {
                name: float(fold(m)) for (name, fold), m
                in zip(self.metric_counters, metrics[1:])})

    def _charge_privacy(self, ridx: int, cfg, staleness=None,
                        mask=None):
        """Charge round ``ridx``'s DP release to the accountant and
        stamp the schema-v5 ledger keys. σ is the DISPATCHED variant's
        ``dp_noise_mult`` (autopilot geometry moves recalibrate it so
        the absolute table noise holds — autopilot/lattice.py).

        Async staleness-weighted rounds charge the REDUCED
        sensitivity ``weight_scale = (1 + s_min)^{-alpha}`` — the
        largest fold weight among the round's ALIVE slots: DP folds
        normalise by the static W·B capacity (core/rounds.py), so a
        client's released contribution is cw_i·t_i/(W·B), genuinely
        scaled by its weight, and the round's worst-case release is
        the largest alive weight times the full sensitivity. (Against
        the data-dependent Σ cw_i·n_i denominator this discount would
        be unsound — uniform weights cancel out of that release.)
        Fully-dead rounds conservatively charge 1. With a hard budget
        (``--dp_epsilon`` > 0) the post-charge ε routes through the
        alarm engine, so ``--on_divergence abort`` stops the run AT
        the exhausting round."""
        acc = self._accountant
        sigma = float(cfg.dp_noise_mult)
        w = 1.0
        alpha = float(getattr(cfg, "async_staleness_weight", 0.0)
                      or 0.0)
        if staleness is not None and alpha > 0.0:
            s = np.asarray(staleness, np.float64)
            alive = np.asarray(mask).reshape(s.shape[0], -1) \
                .sum(axis=1) > 0
            if alive.any():
                w = float(min(
                    (1.0 + float(s[alive].min())) ** (-alpha), 1.0))
        acc.step(weight_scale=w, sigma=sigma)
        eps = acc.epsilon()
        # ledger σ is the round's effective noise-to-sensitivity
        # ratio σ/w — what the composed curve actually charged
        self.telemetry.set_round_privacy(ridx, eps, acc.delta,
                                         sigma / w)
        budget = float(getattr(cfg, "dp_epsilon", 0.0) or 0.0)
        if self.alarm_engine is not None and budget > 0:
            self.alarm_engine.check(ridx, {
                "dp_epsilon": eps,
                "dp_delta": acc.delta,
                "dp_sigma": sigma / w,
                # projection at full sensitivity: future rounds'
                # staleness weights are unknown, so predict
                # exhaustion at the conservative weight_scale=1
                "dp_rounds_left": acc.rounds_left(budget,
                                                  sigma=sigma)})

    def _observe_slo(self, ridx: int, round_s: float, astats=None):
        """One SLO observation per round: latency is the
        dispatch-through-metrics wall time, staleness comes from the
        async driver's round stats, ε from the accountant's
        post-charge curve. The returned burn probes ride the ledger
        record (where the live plane's ``slo_burn`` gauges read
        them), the per-objective stamp lands on the v6 ``slo`` key,
        and the slo_burn rule evaluates through ``check_slo`` — never
        ``check``, which is stateful and already ran this round."""
        slo = self._slo
        eps = (self._accountant.epsilon()
               if self._accountant is not None else None)
        smax = (astats or {}).get("async_staleness_max")
        probes = slo.observe(ridx, round_s=round_s,
                             staleness_max=smax, dp_epsilon=eps)
        self.telemetry.merge_round_probes(ridx, probes)
        self.telemetry.set_round_slo(ridx, slo.stamp())
        if self.alarm_engine is not None:
            self.alarm_engine.check_slo(ridx, probes)

    def _finish_probes(self, ridx: int, vals: dict):
        """Complete round ``ridx``'s probe dict host-side: fold in any
        stashed client-pass values, derive the residual growth ratio
        from the previous round's residual norm (rounds are finished
        in dispatch order, so the ratio is always consecutive-round),
        merge onto the ledger
        record, and evaluate the alarm rules — which may raise
        DivergenceAbort under ``--on_divergence abort``."""
        full = self._probe_host.pop(ridx, {})
        full.update(vals)
        rn = full.get("residual_norm")
        if rn is not None:
            prev = self._prev_residual
            if prev is not None and prev > 0:
                full["residual_growth"] = rn / prev
            self._prev_residual = rn
        self.telemetry.merge_round_probes(ridx, full)
        if self.alarm_engine is not None:
            self.alarm_engine.check(ridx, full)
        if self._autopilot is not None:
            # between-rounds knob control: one observation per finished
            # round, in dispatch order — the controller (and so its
            # manifest trajectory) sees exactly the probe stream the
            # run saw
            new_key = self._autopilot.observe(ridx, full)
            if new_key is not None:
                self._switch_variant(new_key)

    def _switch_variant(self, key):
        """Move the dispatch point to lattice point ``key``: fetch (or
        lazily build) its variant from the re-jit cache, optionally
        AOT-compile the flavor the NEXT round will dispatch — under the
        CURRENT round's host phase, so the compile hides behind work
        the host was doing anyway, and only ever for the point the
        controller just committed to visiting (warm-ahead never touches
        an unvisited lattice point) — and swap ``self.args`` to the
        variant's config so byte accounting reprices from the next
        round on."""
        tel = self.telemetry
        var = self._variants.get(key)
        self._variant_key = key
        nridx = self.round_index  # next round to dispatch
        probed = (var.round_probed is not None
                  and self.probe_period > 0
                  and nridx % self.probe_period == 0)
        flavor = "probed" if probed else "plain"
        if (getattr(self.args, "autopilot_warm_ahead", True)
                and self._round_abstract is not None
                and flavor not in var.compiled
                and flavor not in var.aot):
            fn = var.round_probed if probed else var.round_fn
            cmark = compile_mark()
            try:
                with tel.span("autopilot_warm"):
                    var.aot[flavor] = fn.lower(
                        *self._round_abstract).compile()
                var.compiled.add(flavor)
                self._stamp_vcompile(var.key, cmark)
            except Exception:
                # AOT lowering is best-effort: the lazy jit wrapper
                # compiles at first dispatch instead
                var.aot.pop(flavor, None)
        self.args = var.cfg
        tel.count("autopilot_moves")

    def _stamp_vcompile(self, key, mark):
        """Charge the compile activity since ``mark`` to lattice point
        ``key`` on the current ledger record: raw jax.monitoring event
        count + seconds, plus one ``vcompile_programs`` unit per
        actually-compiled executable (telemetry_report's per-variant
        compile table reads these)."""
        ev, secs = compile_delta(mark)
        if ev:
            ks = key_str(key)
            tel = self.telemetry
            tel.count(f"vcompile_events:{ks}", ev)
            tel.count(f"vcompile_secs:{ks}", round(secs, 6))
            tel.count(f"vcompile_programs:{ks}", 1)

    def autopilot_record(self):
        """The controller's replayable trajectory record (manifest
        ``autopilot`` block), or None with the autopilot off."""
        return (None if self._autopilot is None
                else self._autopilot.record())

    def _rebuild_round_counts(self):
        """Histogram of ``last_updated`` by round (index = round + 1).
        ``#coords changed since a client last synced at round s`` =
        the suffix sum from index s + 2 — O(k) to maintain per round
        and O(#rounds) to query, replacing the old O(W x grad_size)
        host compare (and, with sparse support, the dense update
        transfer) per round."""
        self._round_counts = np.bincount(
            self.last_updated + 1,
            minlength=self._update_round + 2).astype(np.int64)

    def _account_bytes(self, ids_np, mask=None, cfg=None):
        """Per-round download/upload byte accounting (see module
        docstring; reference fed_aggregator.py:171-196, 240-300).
        ``mask`` (W, B) derives which clients completed the round:
        dropped clients (--dropout_prob) downloaded weights but
        uploaded nothing. All byte widths route through
        ``accounting`` — uploads at the sketch wire dtype, downloads
        dense-f32 or delta-coded per --downlink_encoding. ``cfg`` is
        the config the round was DISPATCHED under (the dispatch-time
        round variant's) so autopilot knob moves reprice exactly from
        the round that first used them."""
        if cfg is None:
            cfg = self.args
        self.settle_update()
        download_bytes = np.zeros(self.num_clients)
        suffix = np.cumsum(self._round_counts[::-1])[::-1]
        q = self.client_last_seen[ids_np] + 2
        changed = np.where(
            q < len(suffix), suffix[np.minimum(q, len(suffix) - 1)], 0)
        if getattr(cfg, "downlink_encoding", "dense") == "delta":
            wire = getattr(cfg, "sketch_dtype", "f32")
            # a client that saw the PREVIOUS broadcast holds its
            # support list, so repeats delta-code against it; anyone
            # staler downloads every changed coord as (idx, val)
            fresh = (self.client_last_seen[ids_np]
                     == self._update_round - 1)
            download_bytes[ids_np] = [
                accounting.delta_downlink_bytes(
                    c, self._repeat_count, self._bitmap_bits, wire,
                    have_prev=bool(hp))
                for c, hp in zip(changed, fresh)]
        else:
            download_bytes[ids_np] = changed * accounting.bytes_of(
                1, "f32")
        self.client_last_seen[ids_np] = self._update_round
        upload_bytes = np.zeros(self.num_clients)
        up_ids = ids_np
        if mask is not None:
            up_ids = ids_np[np.asarray(mask).sum(axis=1) > 0]
        upload_bytes[up_ids] = float(
            cfg.upload_wire_bytes_per_client)
        return download_bytes, upload_bytes

    def _call_val(self, batch):
        dev_batch = shard_batch(self.mesh, jax.tree_util.tree_map(
            jnp.asarray, batch))
        # eval metrics cross to the host like train metrics do —
        # attribute the sync (a no-op span when no round is open)
        with self.telemetry.span("metrics_host"):
            if self.stats_fn is not None:
                out = _host(self._val_fn(self.ps_weights,
                                         self.model_state, dev_batch))
            else:
                out = _host(self._val_fn(self.ps_weights, dev_batch))
        # (S, n_metrics) -> per-shard metric arrays, like the
        # reference's split_results (fed_aggregator.py:617-618), plus
        # per-shard real-sample counts so callers can weight out the
        # padded/empty shards the fixed S-shard layout produces
        counts = np.asarray(batch["mask"]).reshape(
            batch["mask"].shape[0], -1).sum(axis=1)
        return [out[:, i] for i in range(out.shape[1])] + [counts]

    def defer_update(self, support):
        """Leave the server update's ``support`` (a ``note_update``
        form) in the pending slot, its copy to the host started and
        not waited for. At most one update is ever pending: one still
        there is settled first."""
        self.settle_update()
        for leaf in jax.tree_util.tree_leaves(support):
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()
        self._pending_update = (support,)

    def settle_update(self) -> bool:
        """Apply the pending update's support, if there is one: what
        everything that reads or writes the accounting state
        (``last_updated``, ``_round_counts``, ``_update_round``, the
        delta bookkeeping) calls first. True if there was one."""
        if self._pending_update is None:
            return False
        (support,), self._pending_update = self._pending_update, None
        with self.telemetry.span("note_update"):
            self.note_update(support)
        self._settled_early = True
        return True

    def _settle_after_dispatch(self):
        """The client pass's settlement, right after it dispatched its
        program: the support's host work runs while the device does.
        Counts, on this round's record, whether the previous update was
        settled here (``account.deferred``) or by something earlier (a
        checkpoint, a direct call: ``account.inline``); a round with no
        update before it counts neither."""
        early = self._settled_early
        if self.settle_update():
            self.telemetry.count("account.deferred")
        elif early:
            self.telemetry.count("account.inline")
        self._settled_early = False

    def note_update(self, support=None):
        """Record the server update's support for download accounting.

        ``support`` forms:
        - ((k,) indices, (k,) values): sparse support of the weight
          update (values post-LR) — only ~k values cross to the host;
        - None: dense update, every coordinate marked changed with no
          device transfer (the only deviation from the reference's
          value-compare: dense-mode coordinates whose update is
          exactly 0.0 still count as changed — measure-zero under
          momentum);
        - {"bitmap": packed uint8}: device-side ``!= 0`` compare,
          bit-packed before crossing to the host (modes whose update
          is sparse with non-static support size, e.g. local_topk —
          its update's support is the union of past top-k
          selections; 1/32 the transfer of the dense form);
        - a dense update array: host-side ``!= 0`` compare (legacy
          form, kept for direct callers)."""
        self.settle_update()    # updates apply in the order they came
        self._update_round += 1
        r = self._update_round
        if len(self._round_counts) < r + 2:
            self._round_counts = np.concatenate(
                [self._round_counts,
                 np.zeros(r + 2 - len(self._round_counts) + 64,
                          np.int64)])
        if support is None:
            self.last_updated[:] = r
            self._round_counts[:] = 0
            self._round_counts[r + 1] = self.args.grad_size
            self._note_delta_support(None)
            return
        if isinstance(support, tuple):
            idx = np.asarray(support[0])
            vals = np.asarray(support[1])
            idx = idx[vals != 0]
        elif isinstance(support, dict):  # packed changed-coords bitmap
            bits = np.unpackbits(np.asarray(support["bitmap"]))
            idx = np.nonzero(bits[: self.args.grad_size])[0]
        else:
            idx = np.nonzero(np.asarray(support) != 0)[0]
        old = self.last_updated[idx] + 1
        np.subtract.at(self._round_counts, old, 1)
        self._round_counts[r + 1] += len(idx)
        self.last_updated[idx] = r
        self._note_delta_support(idx)

    def _note_delta_support(self, idx):
        """Roll the --downlink_encoding delta bookkeeping forward one
        update: how many of this update's support indices repeat the
        previous update's (those ship as bitmap bits, not int32
        indices, to a client that saw the previous broadcast), and
        the previous support's size (the bitmap's bit count).
        ``idx=None`` means a dense update (every coordinate)."""
        prev = self._prev_support_idx
        d = int(self.args.grad_size)
        prev_n = d if prev is None else len(prev)
        if idx is None:
            self._repeat_count = prev_n
        elif prev is None:
            self._repeat_count = len(idx)
        else:
            self._repeat_count = int(np.intersect1d(
                idx, prev, assume_unique=False).size)
        self._bitmap_bits = prev_n
        self._prev_support_idx = (None if idx is None
                                  else np.asarray(idx, np.int64))


class FedOptimizer:
    """Server-side optimizer (reference FedOptimizer,
    fed_aggregator.py:385-463). ``param_groups`` is torch-shaped so LR
    schedulers port unchanged; per-group LRs become a concatenated LR
    vector (fed_aggregator.py:413-429) via each group's ``size``."""

    @setup_span("model_build")
    def __init__(self, param_groups=None, args: Config = None,
                 model: Optional[FedModel] = None):
        self.model = model or _CURRENT_MODEL
        assert self.model is not None, "construct FedModel first"
        self.args = args or self.model.args
        if param_groups is None:
            param_groups = [{"lr": 1.0}]
        if isinstance(param_groups, dict):
            param_groups = [param_groups]
        self.param_groups = param_groups
        # index-based groups: one device-resident indicator vector per
        # group, built once — get_lr then only ships scalars per step
        self._lr_indicators = None
        if len(param_groups) > 1 and \
                all("index" in g for g in param_groups):
            inds = []
            for group in param_groups:
                v = np.zeros(self.args.grad_size, np.float32)
                v[group["index"]] = 1.0
                inds.append(jnp.asarray(v))
            self._lr_indicators = inds
        # server momentum/error buffers are created in the layout the
        # server round keeps them in: model-sharded on a 2D mesh
        # (per-device server state is 1/M from the first round, never
        # resharded from a replicated allocation), replicated over
        # every device of a Cx1/1-D mesh
        mesh = self._mesh = self.model.mesh
        self.server_state = ServerState.init(
            self.args, sharding=server_state_sharding(
                mesh, self.args.transmit_shape))
        # geometry the live server state was allocated for: a knob
        # move that changes transmit_shape (--autopilot_geometry)
        # re-inits the momentum/error tables at the new shape
        self._server_geom = tuple(self.args.transmit_shape)
        # donate weights + server state: both are replaced by the
        # round's outputs and the stale buffers are never read again —
        # at GPT-2 scale that's ~1 GB of peak HBM saved per step
        self._probes = int(getattr(self.args, "probe_period", 0)
                           or 0) > 0
        self._server_round = jax.jit(
            build_server_round(self.args, probes=self._probes,
                               mesh=mesh),
            donate_argnums=(0, 1))
        self._select_form = server_select_form(self.args, mesh)
        self._rot_form = sketch_rot_form(self.args)
        # legacy --do_dp server-mode noise stream: the seed+1 root key
        # comes from privacy/ (the one module allowed raw jax.random
        # noise — analysis/lint.py noise-confinement)
        self._noise_rng = noise_stream(self.args.seed + 1)
        self._step_count = 0

    def get_lr(self):
        if len(self.param_groups) == 1:
            return self.param_groups[0]["lr"]
        if self._lr_indicators is not None:
            # index-based groups (param_group_indices): per-coordinate
            # LRs aligned with the flat vector regardless of how the
            # group members interleave in parameter order
            return sum(float(g["lr"]) * ind for g, ind in
                       zip(self.param_groups, self._lr_indicators))
        lr_vec = []
        for group in self.param_groups:
            assert "size" in group, \
                "multi-group LR needs per-group 'index' or 'size'"
            lr_vec.append(np.full(group["size"], group["lr"],
                                  np.float32))
        return jnp.asarray(np.concatenate(lr_vec))

    def step(self):
        # the parent of every server-pass span; round ridx's ledger
        # record is still current (the next _call_train's begin_round
        # closes it), so the spans land on the round whose aggregate
        # the step consumes
        with self.model.telemetry.span("server_pass"):
            self._server_pass()

    def _server_pass(self):
        m = self.model
        assert m.pending_aggregated is not None, \
            "call model(batch) before opt.step()"
        lr = self.get_lr()
        # group scalars, so this also covers the vector-LR path
        if all(float(g["lr"]) == 0 for g in self.param_groups):
            print("WARNING: LR is 0")
        if self.args.mode == "fedavg":
            assert np.ndim(lr) == 0, "fedavg supports scalar lr only"
            m.fedavg_lr = float(lr)
            # NB: fedavg also takes the bitmap value-compare below —
            # its round-0 update is all-zero (clients ran at the
            # initial g_lr of 0), and the reference's
            # weight_update != 0 compare charges nothing for it

        self._step_count += 1
        noise_rng = jax.random.fold_in(self._noise_rng,
                                       self._step_count)
        server_fn, svar = self._server_round, None
        if getattr(m, "_autopilot", None) is not None:
            # the aggregate pending on the model was emitted by a
            # specific round variant — its server program (the wire
            # dequant and unsketch geometry are trace-time constants)
            # must match. Variants hold their own jitted server round;
            # the static self._server_round is never dispatched, so it
            # never compiles.
            svar = m._variants.get(m.pending_variant_key)
            if svar.server_fn is None:
                svar.server_fn = jax.jit(
                    build_server_round(svar.cfg, probes=self._probes,
                                       mesh=self._mesh),
                    donate_argnums=(0, 1))
            geom = tuple(svar.cfg.transmit_shape)
            if geom != self._server_geom:
                # geometry move: the sketch-shaped server tables are
                # re-seeded at the new shape (momentum restarts — the
                # controller's geometry steps are opt-in for exactly
                # this reason)
                self.server_state = ServerState.init(
                    svar.cfg,
                    sharding=server_state_sharding(self._mesh, geom))
                self._server_geom = geom
            server_fn = svar.server_fn
        # which selection the program about to run was built with
        # (engagement, as h2d.staged / account.deferred: no metric)
        form = (self._select_form if svar is None
                else server_select_form(svar.cfg, self._mesh))
        if form is not None:
            m.telemetry.count("select." + form[0])
            m.telemetry.count("select.candidates", form[1])
            # and which rotation form its sketch kernels were
            m.telemetry.count("sketch.rot_" + (
                self._rot_form if svar is None
                else sketch_rot_form(svar.cfg)))
        sfirst = svar is not None and "server" not in svar.compiled
        cmark = compile_mark() if sfirst else None
        with m.telemetry.span("server"):
            out = server_fn(
                m.ps_weights, self.server_state,
                m.pending_aggregated,
                jnp.asarray(lr, jnp.float32),
                m.client_states.velocities, m.pending_client_ids,
                noise_rng)
        if cmark is not None:
            svar.compiled.add("server")
            m._stamp_vcompile(svar.key, cmark)
        sprobes = None
        if self._probes:
            new_ps, self.server_state, new_vel, update, support, \
                sprobes = out
        else:
            new_ps, self.server_state, new_vel, update, support = out
        m.ps_weights = new_ps
        if new_vel is not None:
            m.client_states = m.client_states._replace(
                velocities=new_vel)
        m.pending_aggregated = None
        # host client store: the round's participant rows (incl. any
        # server-side velocity rewrite above) go back to the host now
        m._store_writeback()
        if m._prefetch_after_writeback:
            # --overlap_depth > 1: the gather staged here sees the
            # post-write-back row versions, so next round's take() is
            # patch-free while the worker thread hides the gather
            # under the host work between this step and the next
            # round's gather
            m._prefetch_after_writeback = False
            m._submit_prefetch()
        if support is None:
            # dense-update modes. fedavg/momentum updates touch every
            # coordinate; the exceptions that don't: a zero scalar LR
            # (nothing moved) and local_topk (even with virtual
            # momentum the update's support is only the union of past
            # top-k selections, ~W*k coords early on — the reference
            # value-compares weight_update != 0, so marking all
            # grad_size coords would overcount download bytes)
            vector_lr = np.ndim(lr) > 0
            if (self.args.mode != "fedavg" and not vector_lr
                    and float(lr) == 0):
                support = (np.zeros(0, np.int64), np.zeros(0))
            elif self.args.mode in ("local_topk", "fedavg") \
                    or vector_lr:
                # != 0 compare, packed ON DEVICE: shipping the dense
                # f32 update to the host costs 4*d bytes of D2H per
                # round — the bitmap is 1/32 of that
                support = {"bitmap": jnp.packbits(update != 0)}
        # not waited for here: the next client pass applies it once its
        # own program is dispatched (FedModel.defer_update)
        m.defer_update(support)
        if sprobes is not None:
            # the round this server pass belongs to (round_index was
            # already advanced by _call_train)
            sridx = m.round_index - 1
            with m.telemetry.span("metrics_host"):
                svals = {k: float(_host(v))
                         for k, v in sprobes.items()}
            m._finish_probes(sridx, svals)

    def zero_grad(self):
        raise NotImplementedError(
            "functional runtime: there is no gradient to zero")


class TrainRun(NamedTuple):
    """What one trainer invocation (``cv_train.run`` /
    ``gpt2_train.run``) built and returned: the epoch rows plus the
    runtime objects, so the trainers' ``cli`` can turn divergence into
    an exit status and ``chip_smoke.py`` can inspect the programs and
    placements the run actually used."""
    results: list
    model: FedModel
    opt: FedOptimizer
    train_loader: object


class LambdaLR:
    """Minimal torch-compatible LR scheduler: lr = base_lr *
    lr_lambda(step) (used as cv_train.py:394-406 uses torch's)."""

    def __init__(self, optimizer: FedOptimizer, lr_lambda,
                 base_lrs=None):
        self.optimizer = optimizer
        self.lr_lambda = lr_lambda
        self.base_lrs = base_lrs or [g["lr"]
                                     for g in optimizer.param_groups]
        self._step = 0

    def step(self):
        for g, base in zip(self.optimizer.param_groups, self.base_lrs):
            g["lr"] = base * self.lr_lambda(self._step)
        self._step += 1
