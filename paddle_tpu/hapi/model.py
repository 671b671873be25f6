"""hapi.Model: the fit/evaluate/predict trainer (reference:
python/paddle/hapi/model.py:1081 Model, fit at :1807).

TPU-native: train/eval steps run through the eager tape (backward + step);
the flagship path for scale is paddle_tpu.parallel.make_train_step — hapi
keeps the reference's convenience trainer surface.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..observability import metrics as obs_metrics
from ..observability import trace as obs_trace
from .callbacks import Callback, CallbackList, ProgBarLogger

__all__ = ["Model", "summary"]


class _InputSpec:
    def __init__(self, shape=None, dtype="float32", name=None):
        self.shape = shape
        self.dtype = dtype
        self.name = name


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class Model:
    """reference: hapi/model.py Model(network, inputs, labels)."""

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self.stop_training = False
        self.preempted = False
        # static memory audit of the forward pass (ISSUE 10): dict via
        # fit(audit_memory=True) / PADDLE_TPU_AUDIT_MEMORY, else None
        self.memory_audit = None
        # static communication audit of the training step (ISSUE 11):
        # dict via fit(audit_comms=True) / PADDLE_TPU_AUDIT_COMMS
        self.comms_audit = None
        # static roofline audit of the training step (ISSUE 13): dict
        # via fit(audit_roofline=True) / PADDLE_TPU_AUDIT_ROOFLINE
        self.roofline_audit = None
        # generation fit(resume=True) restored from (gang mode: the
        # AGREED generation — every rank reports the same number), or
        # None when the run started fresh (ISSUE 12)
        self.restored_generation = None
        # quantized collectives (ISSUE 15): the last fit()'s resolved
        # FLAGS_quantized_collectives (None until a fit ran) — the
        # audit hooks build the dp step with the SAME wire the
        # training path runs; quantized_dp_steps counts batches that
        # went through the explicit quantized dp-sync step
        self._quantized_collectives = None
        self.quantized_dp_steps = 0

    # ------------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric must be paddle.metric.Metric, got {m}")

    # ------------------------------------------------------------------
    def _compute_loss(self, outputs, labels):
        loss_fn = self._loss
        if loss_fn is None:
            raise RuntimeError("call prepare(loss=...) before training")
        outs = _to_list(outputs)
        labs = _to_list(labels)
        if callable(loss_fn) and not isinstance(loss_fn, (list, tuple)):
            return loss_fn(*outs, *labs)
        raise TypeError("loss must be callable")

    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        inputs = [Tensor(np.asarray(i)) if not isinstance(i, Tensor) else i
                  for i in _to_list(inputs)]
        labels = [Tensor(np.asarray(l)) if not isinstance(l, Tensor) else l
                  for l in _to_list(labels)]
        outputs = self.network(*inputs)
        loss = self._compute_loss(outputs, labels)
        loss.backward()
        if update and self._optimizer is not None:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics = []
        for m in self._metrics:
            m.update(*_to_list(m.compute(*_to_list(outputs), *labels)))
            metrics.append(m.accumulate())
        out = [float(loss.numpy())]
        return (out, metrics) if metrics else out

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        from ..core import tape as _tape

        with _tape.no_grad():
            inputs = [Tensor(np.asarray(i)) if not isinstance(i, Tensor)
                      else i for i in _to_list(inputs)]
            labels = [Tensor(np.asarray(l)) if not isinstance(l, Tensor)
                      else l for l in _to_list(labels)]
            outputs = self.network(*inputs)
            losses = ([float(self._compute_loss(outputs, labels).numpy())]
                      if self._loss is not None and labels else [])
        metrics = []
        for m in self._metrics:
            m.update(*_to_list(m.compute(*_to_list(outputs), *labels)))
            metrics.append(m.accumulate())
        return (losses, metrics) if metrics else losses

    def predict_batch(self, inputs):
        self.network.eval()
        from ..core import tape as _tape

        with _tape.no_grad():
            inputs = [Tensor(np.asarray(i)) if not isinstance(i, Tensor)
                      else i for i in _to_list(inputs)]
            outputs = self.network(*inputs)
        return [o.numpy() for o in _to_list(outputs)]

    # ------------------------------------------------------------------
    def _make_loader(self, data, batch_size, shuffle):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle)
        return data  # iterable of batches

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, checkpoint_dir=None,
            resume=False, checkpoint_freq=None, audit_memory=None,
            audit_comms=None, audit_roofline=None, coordinator=None,
            quantized_collectives=None):
        """reference: hapi/model.py fit (:1807).

        Resilience extensions (paddle_tpu.resilience):
          checkpoint_dir: atomic generation-counted checkpoints (model +
            optimizer + loop position) land here; a preemption signal
            (SIGTERM/SIGINT) observed at a step boundary triggers an
            emergency checkpoint and a clean stop (`self.preempted`).
          resume: restore the newest valid generation from
            checkpoint_dir and continue from the recorded epoch/step
            (deterministic resume needs a deterministic loader —
            shuffle=False or a seeded sampler).
          checkpoint_freq: save every N steps (async, off the step
            path); None saves at epoch boundaries only.
          coordinator: a `resilience.Coordinator` puts checkpointing in
            GANG mode (ISSUE 12): every save is the two-phase
            coordinated commit (all hosts stage + barrier, rank 0
            writes the group manifest, barrier, visible) and
            resume=True routes through generation AGREEMENT — each
            host publishes its newest digest-verified generation and
            all adopt the group min, recorded on
            `self.restored_generation`. A peer that dies mid-protocol
            surfaces as a structured `BarrierTimeout` naming the
            missing rank (the gang supervisor's relaunch signal), and
            the solo emergency checkpoint on preemption is replaced by
            a best-effort gang save that is ABANDONED on barrier
            timeout: a single host cannot commit a group generation,
            the periodic coordinated checkpoints are the recovery
            point. Subprocess workers build one with
            `resilience.coordination.from_env()`.

        Observability (ISSUE 8): with FLAGS_trace / FLAGS_metrics
        armed, every step records `fit.data_fetch` (loader wait),
        `fit.step` (train_batch dispatch, bridged to
        jax.profiler.StepTraceAnnotation so host steps align with a
        live device trace) and `fit.checkpoint_save` spans plus the
        matching `fit_*_s` histograms. Off (default): the loop is
        byte-identical to the uninstrumented one.

        Static memory audit (ISSUE 10): `audit_memory=True` (default:
        FLAGS_audit_memory / PADDLE_TPU_AUDIT_MEMORY, implied by
        PADDLE_TPU_LINT=1) traces the network forward at the first
        batch's shapes through `analysis/memory.py` — a jaxpr-liveness
        peak-HBM estimate over params + activations, no device work —
        stores the report on `self.memory_audit`, and emits a
        `memory.audit` observability event. One-shot per fit call.

        Static communication audit (ISSUE 11): `audit_comms=True`
        (default: FLAGS_audit_comms / PADDLE_TPU_AUDIT_COMMS, implied
        by PADDLE_TPU_LINT=1) traces the TRAINING STEP — loss +
        backward at the first batch's shapes — through
        `analysis/comms.py`. When the global mesh carries a `dp` axis
        (size > 1) the gradient sync is made explicit (batch sharded
        over dp, grads psum'd — the all-reduce GSPMD inserts at
        compile time, surfaced so the static wire pass can count it);
        the bytes-on-wire report + TPU801/802/803 diagnostics land on
        `self.comms_audit` with a `comms.audit` observability event.
        One-shot per fit call; failures degrade to a warning.

        Static roofline audit (ISSUE 13): `audit_roofline=True`
        (default: FLAGS_audit_roofline / PADDLE_TPU_AUDIT_ROOFLINE,
        implied by PADDLE_TPU_LINT=1) traces the TRAINING STEP through
        `analysis/roofline.py` — per-eqn FLOPs + fusion-aware HBM
        bytes against the device-spec table, predicted step time +
        MFU + bound class, TPU901/902/903 diagnostics — onto
        `self.roofline_audit` with a `roofline.audit` event. One-shot
        per fit call; failures degrade to a warning.

        Quantized collectives (ISSUE 15): `quantized_collectives=True`
        (default: FLAGS_quantized_collectives /
        PADDLE_TPU_QUANTIZED_COLLECTIVES, resolved HERE at fit time —
        the training-side program-build point) routes training through
        the EXPLICIT dp step when the global mesh carries a `dp` axis
        (size > 1): loss + backward run as one jitted shard_map program
        with the batch sharded over dp and the gradient sync as the
        QUANTIZED psum (`parallel.collectives.quantized_psum_tree` —
        reduce-scatter on int8 shards + f32 dequant-accumulate +
        all-gather, so accumulation error does not scale with world
        size); the synced mean grads install into the parameters and
        the regular optimizer step applies them. `audit_comms=` /
        `audit_roofline=` trace the SAME step, so the wire report
        prices the int8 payload + f32 sidecar the training actually
        ships. Without a dp mesh (or a batch whose leading dim does
        not divide dp) fit warns and keeps the eager path; flag OFF
        (default) is byte-identical to today.
        """
        if audit_memory is not False:  # False skips the analysis import
            from ..analysis.memory import resolve_audit_memory

            audit_memory = resolve_audit_memory(audit_memory)
        audit_pending = bool(audit_memory)
        if audit_comms is not False:
            from ..analysis.comms import resolve_audit_comms

            audit_comms = resolve_audit_comms(audit_comms)
        comms_pending = bool(audit_comms)
        if audit_roofline is not False:
            from ..analysis.roofline import resolve_audit_roofline

            audit_roofline = resolve_audit_roofline(audit_roofline)
        roofline_pending = bool(audit_roofline)
        from ..parallel.collectives import resolve_quantized_collectives

        self._quantized_collectives = resolve_quantized_collectives(
            quantized_collectives)
        self.quantized_dp_steps = 0
        train_batch_fn = self.train_batch
        if self._quantized_collectives:
            dp_fn = self._make_dp_train_batch()
            if dp_fn is not None:
                train_batch_fn = dp_fn
        loader = self._make_loader(train_data, batch_size, shuffle)
        eval_loader = self._make_loader(eval_data, batch_size, False)
        cbks = CallbackList(_to_list(callbacks) or [ProgBarLogger(log_freq,
                                                                  verbose)])
        cbks.set_model(self)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks.set_params({"epochs": epochs, "steps": steps,
                         "verbose": verbose, "metrics": self._metric_names()})
        self.stop_training = False
        self.preempted = False
        self.restored_generation = None
        from ..resilience import chaos as _chaos

        ckpt_mgr = guard = None
        start_epoch = skip_steps = it_count = 0
        try:
            if checkpoint_dir is not None:
                from ..resilience import preemption as _preemption
                from ..resilience.checkpoint import (
                    CheckpointManager, CheckpointNotFoundError)

                ckpt_mgr = CheckpointManager(checkpoint_dir, max_to_keep=3,
                                             coordinator=coordinator)
                guard = _preemption.install()
                if resume:
                    try:
                        # gang mode: routed through generation
                        # agreement — min over every host's newest
                        # digest-verified group generation
                        ck = ckpt_mgr.restore()
                    except CheckpointNotFoundError:
                        # an EMPTY dir is a legitimate fresh run;
                        # existing-but-unverifiable generations are data
                        # loss and must not silently restart at step 0
                        if ckpt_mgr.generations():
                            raise
                        ck = None
                    if ck is not None:
                        self.network.set_state_dict(ck.value["model"])
                        if self._optimizer is not None \
                                and "optimizer" in ck.value:
                            self._optimizer.set_state_dict(
                                ck.value["optimizer"])
                        self.restored_generation = ck.generation
                        start_epoch = int(ck.meta.get("epoch", 0))
                        skip_steps = int(ck.meta.get("step_in_epoch", 0))
                        it_count = int(ck.meta.get("global_step", 0))
                        if steps is not None and skip_steps >= steps:
                            start_epoch, skip_steps = start_epoch + 1, 0
            cbks.on_train_begin()
            for epoch in range(start_epoch, epochs):
                for m in self._metrics:
                    m.reset()
                cbks.on_epoch_begin(epoch)
                logs = {}
                hit_num_iters = False
                step = -1
                tr = obs_trace.get_tracer()
                mt = obs_metrics.get_metrics()
                batches = loader if tr is None and mt is None \
                    else self._timed_batches(loader, tr, mt)
                for step, batch in enumerate(batches):
                    if epoch == start_epoch and step < skip_steps:
                        continue  # replayed batches of a resumed epoch
                    cbks.on_train_batch_begin(step)
                    ins, labs = self._split_batch(batch)
                    if audit_pending:
                        audit_pending = False
                        self._audit_memory(ins)
                    if comms_pending or roofline_pending:
                        do_c, do_r = comms_pending, roofline_pending
                        comms_pending = roofline_pending = False
                        # ONE trace of the training step serves both
                        # auditors (their passes memoize on the Graph)
                        # — under PADDLE_TPU_LINT=1, which implies
                        # both, the most expensive trace in the repo
                        # must not run twice (same contract as the
                        # engine's shared _traced_inventory)
                        traced = self._trace_step_for_audits(ins, labs) \
                            if do_c and do_r else None
                        if do_c:
                            self._audit_comms(ins, labs, traced=traced)
                        if do_r:
                            self._audit_roofline(ins, labs,
                                                 traced=traced)
                    update = (step + 1) % accumulate_grad_batches == 0
                    if tr is None and mt is None:
                        res = train_batch_fn(ins, labs, update=update)
                    else:
                        t0 = time.perf_counter()
                        if tr is not None:
                            # StepTraceAnnotation bridging: host steps
                            # align with a live XPlane device trace
                            with tr.step_span("fit.step", it_count):
                                res = train_batch_fn(ins, labs,
                                                     update=update)
                        else:
                            res = train_batch_fn(ins, labs,
                                                 update=update)
                        if mt is not None:
                            mt.histogram(
                                "fit_step_s",
                                "train step dispatch+sync").observe(
                                    time.perf_counter() - t0)
                            mt.counter("fit_steps").inc()
                    logs = self._pack_logs(res)
                    cbks.on_train_batch_end(step, logs)
                    it_count += 1
                    _chaos.on_step("fit", it_count)
                    hit_num_iters = num_iters is not None \
                        and it_count >= num_iters
                    if hit_num_iters:
                        self.stop_training = True
                    if guard is not None and guard.requested:
                        # emergency checkpoint: blocking, then a clean
                        # stop — the grace window is for THIS write. In
                        # gang mode the save is the coordinated two-
                        # phase commit and BEST-EFFORT: a peer that was
                        # preempted harder than us (never reaches the
                        # stage barrier) must not wedge our shutdown —
                        # abandon on BarrierTimeout, the periodic gang
                        # generations are the recovery point
                        try:
                            self._save_checkpoint(
                                ckpt_mgr, epoch, step + 1, it_count,
                                blocking=True)
                        except Exception as e:
                            from ..resilience.coordination import (
                                BarrierTimeout)

                            if coordinator is None \
                                    or not isinstance(e, BarrierTimeout):
                                raise
                            import warnings

                            warnings.warn(
                                f"emergency gang checkpoint abandoned "
                                f"({e}); the newest committed group "
                                "generation is the recovery point",
                                RuntimeWarning)
                        self.preempted = True
                        self.stop_training = True
                        from ..observability import record_event

                        record_event("preemption.emergency_checkpoint",
                                     step=it_count, epoch=epoch)
                        break
                    if ckpt_mgr is not None and checkpoint_freq \
                            and it_count % checkpoint_freq == 0:
                        self._save_checkpoint(ckpt_mgr, epoch, step + 1,
                                              it_count, blocking=False)
                    if hit_num_iters:
                        break
                cbks.on_epoch_end(epoch, logs)
                if self.preempted:
                    break  # the emergency save already recorded position
                if ckpt_mgr is not None and checkpoint_freq is None:
                    # a num_iters stop mid-epoch must record the TRUE
                    # position, not epoch+1 (which would skip the rest
                    # of this epoch on resume); a completed epoch rolls
                    # the position forward
                    if hit_num_iters:
                        self._save_checkpoint(ckpt_mgr, epoch, step + 1,
                                              it_count, blocking=False)
                    else:
                        self._save_checkpoint(ckpt_mgr, epoch + 1, 0,
                                              it_count, blocking=False)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_loader, batch_size=batch_size,
                                  verbose=verbose, callbacks=cbks)
                if self.stop_training:
                    break
            cbks.on_train_end()
        finally:
            try:
                if ckpt_mgr is not None:
                    ckpt_mgr.wait()  # async-save barrier + error surface
            finally:
                if guard is not None:
                    from ..resilience import preemption as _preemption

                    _preemption.uninstall()

    @staticmethod
    def _timed_batches(loader, tr, mt):
        """Loader wrapped with `fit.data_fetch` spans / histogram —
        only on the instrumented path (fit falls back to the raw
        loader when observability is off)."""
        it = iter(loader)
        while True:
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                return
            t1 = time.perf_counter()
            if tr is not None:
                tr.complete("fit.data_fetch", int(t0 * 1e9),
                            int(t1 * 1e9))
            if mt is not None:
                mt.histogram("fit_data_fetch_s",
                             "host wait on the data loader").observe(
                                 t1 - t0)
            yield batch

    def _audit_memory(self, ins):
        """One-shot static memory audit of the forward pass at the
        first batch's shapes (fit(audit_memory=True)): host-side
        tracing only. An audit failure must never take down training —
        it degrades to a warning."""
        try:
            from ..analysis import memory as _mem
            from ..observability import record_event

            arrays = [np.asarray(i.numpy() if isinstance(i, Tensor)
                                 else i) for i in _to_list(ins)]
            rep = _mem.audit_memory(self.network, *arrays,
                                    name="fit.forward")
            self.memory_audit = rep.to_dict()
            record_event("memory.audit", target="fit.forward",
                         peak_hbm_bytes=rep.peak_bytes, mp=rep.mp)
        except Exception as e:  # pragma: no cover - defensive
            import warnings

            warnings.warn(f"fit(audit_memory=True) failed: "
                          f"{type(e).__name__}: {e}")

    def _audit_step_target(self, ins, labs):
        """(loss_fn, params, batch) for the static auditors: the pure
        loss-of-(params, batch) function the training step
        differentiates, at the first batch's shapes — shared by the
        comms (ISSUE 11) and roofline (ISSUE 13) audit hooks so both
        trace the SAME step."""
        import jax.numpy as jnp

        from ..core import tape as _tape
        from ..core.tensor import unwrap

        ins_arr = [np.asarray(i.numpy() if isinstance(i, Tensor)
                              else i) for i in _to_list(ins)]
        lab_arr = [np.asarray(l.numpy() if isinstance(l, Tensor)
                              else l) for l in _to_list(labs)]
        n_in = len(ins_arr)
        state = dict(self.network.raw_state())
        # only inexact leaves are differentiable; int/bool buffers
        # ride the closure (their grads would be float0 anyway)
        params = {k: v for k, v in state.items()
                  if jnp.issubdtype(jnp.asarray(v).dtype,
                                    jnp.inexact)}
        rest = {k: v for k, v in state.items() if k not in params}
        has_loss = self._loss is not None and bool(lab_arr)

        def loss_fn(p, *batch):
            with _tape.no_grad():
                out = self.network.func_call(
                    {**rest, **p},
                    *(Tensor(b) for b in batch[:n_in]))
                if has_loss:
                    loss = unwrap(self._compute_loss(
                        out, [Tensor(l) for l in batch[n_in:]]))
                else:
                    loss = sum(jnp.sum(unwrap(o).astype(jnp.float32))
                               for o in _to_list(out))
            return jnp.asarray(loss).astype(jnp.float32)

        return loss_fn, params, tuple(ins_arr + lab_arr)

    def _build_dp_step(self, loss_fn, params, n_batch, dp,
                       quantized=False):
        """The EXPLICIT dp training step: loss + backward under
        shard_map over a dp mesh, batch sharded on dim 0, and the
        gradient sync written out — `lax.psum` (exactly the all-reduce
        GSPMD inserts at compile time, invisible to a traced jaxpr),
        or the QUANTIZED two-hop exchange when
        FLAGS_quantized_collectives resolves ON (ISSUE 15:
        reduce-scatter on int8 shards + f32 dequant-accumulate +
        all-gather via `quantized_psum_tree`). Loss and grads come
        back as dp-MEANS, so the step matches the eager full-batch
        step's math. ONE builder serves the real quantized-dp
        training path AND the comms/roofline audit hooks — the
        audited program IS the trained one."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        dp_mesh = Mesh(np.asarray(jax.devices()[:dp]), ("dp",))
        p_specs = jax.tree.map(lambda _: P(), params)

        def dp_step(p, *b):
            # inside shard_map the dp axis is MANUAL: a model whose
            # forward applies with_sharding_constraint against the
            # GLOBAL mesh (llama's activation specs) would trip the
            # manual-axes check — the body is already per-shard, so
            # the constraints are meaningless here. Clearing the
            # global mesh is trace-scoped (this body runs at trace
            # time only).
            from ..parallel import mesh as mesh_mod

            prev_mesh = mesh_mod.get_global_mesh()
            mesh_mod.set_global_mesh(None)
            try:
                loss, grads = jax.value_and_grad(loss_fn)(p, *b)
            finally:
                mesh_mod.set_global_mesh(prev_mesh)
            if quantized:
                from ..parallel.collectives import quantized_psum_tree

                # THE dp gradient sync, quantized: int8 payload + f32
                # scale sidecar on the wire, accumulation in f32 (one
                # rounding per contribution — error does not scale
                # with dp)
                grads = quantized_psum_tree(grads, "dp")
            else:
                # THE dp gradient sync: one fused all-reduce over
                # every grad leaf — explicit so the wire pass (and
                # TPU803) can see what GSPMD emits
                grads = jax.lax.psum(grads, "dp")
            grads = jax.tree.map(
                lambda g: (g / dp).astype(g.dtype), grads)
            return jax.lax.psum(loss, "dp") / dp, grads

        return shard_map(
            dp_step, mesh=dp_mesh,
            in_specs=(p_specs,) + (P("dp"),) * n_batch,
            out_specs=(P(), p_specs), check_vma=False)

    def _audit_step_program(self, ins, labs, hook):
        """(target, name, params, batch) — the FULL traced training
        step the static auditors price, dp handling included: when the
        global mesh carries a dp axis (size > 1) and the batch shards,
        the step is built under shard_map with the explicit gradient
        psum — quantized (int8 payload + f32 sidecar) when the last
        fit's FLAGS_quantized_collectives resolved ON, so the audit
        prices the wire training actually ships. Shared by the comms
        and roofline hooks so both audit the SAME program; `hook`
        names the caller in the dp-fallback warning."""
        import jax

        from ..parallel import mesh as mesh_mod
        from ..parallel.collectives import resolve_quantized_collectives

        loss_fn, params, batch = self._audit_step_target(ins, labs)

        def step(p, *b):
            return jax.value_and_grad(loss_fn)(p, *b)

        target, name = step, "fit.step"
        quantized = self._quantized_collectives
        if quantized is None:
            quantized = resolve_quantized_collectives(None)
        mesh = mesh_mod.get_global_mesh()
        dp = int(mesh.shape["dp"]) if mesh is not None \
            and "dp" in getattr(mesh, "axis_names", ()) else 1
        dp_shardable = batch and all(
            b.ndim >= 1 and b.shape[0] % dp == 0 for b in batch)
        if dp > 1 and not dp_shardable:
            # the fallback audits the single-chip step — zero
            # collectives — while the REAL compiled step pays the
            # dp gradient all-reduce; a silent clean report here
            # would hide exactly the bytes the audit exists for
            import warnings

            warnings.warn(
                f"fit({hook}=True): global mesh has dp={dp} "
                "but a batch leaf is 0-d or its leading dim does "
                "not divide by dp — auditing the single-chip step; "
                "the dp gradient psum is NOT counted")
        if dp > 1 and dp_shardable:
            target = self._build_dp_step(loss_fn, params, len(batch),
                                         dp, quantized=quantized)
            name = f"fit.step[dp={dp}]" \
                + ("+int8coll" if quantized else "")
        return target, name, params, batch

    def _make_dp_train_batch(self):
        """train_batch-compatible callable running the EXPLICIT
        quantized dp-sync step (ISSUE 15), or None — with a warning —
        when no global mesh carries a dp axis (there is no gradient
        sync to quantize; fit keeps the eager path). Per batch: one
        jitted shard_map step (built at the first batch's shapes,
        cached; `_build_dp_step` with the quantized wire) computes
        (mean loss, synced mean grads); grads ACCUMULATE into the
        parameters like `loss.backward()` does (so
        accumulate_grad_batches composes) and the regular optimizer
        step applies them. Metrics, if any, ride one extra no-grad
        eager forward."""
        import warnings

        from ..parallel import mesh as mesh_mod

        mesh = mesh_mod.get_global_mesh()
        dp = int(mesh.shape["dp"]) if mesh is not None \
            and "dp" in getattr(mesh, "axis_names", ()) else 1
        if dp <= 1:
            warnings.warn(
                "fit(quantized_collectives=True): no global mesh with "
                "a dp axis (size > 1) is set — there is no gradient "
                "sync to quantize; training on the eager single-chip "
                "path")
            return None
        built = {}

        def dp_train_batch(ins, labs, update=True):
            import jax

            self.network.train()
            ins_arr = [np.asarray(i.numpy() if isinstance(i, Tensor)
                                  else i) for i in _to_list(ins)]
            lab_arr = [np.asarray(l.numpy() if isinstance(l, Tensor)
                                  else l) for l in _to_list(labs)]
            batch = ins_arr + lab_arr
            if not batch or not all(b.ndim >= 1 and b.shape[0] % dp == 0
                                    for b in batch):
                if "warned" not in built:
                    built["warned"] = True
                    warnings.warn(
                        f"fit(quantized_collectives=True): a batch "
                        f"leaf is 0-d or its leading dim does not "
                        f"divide dp={dp} — falling back to the eager "
                        "single-chip step for such batches")
                return self.train_batch(ins, labs, update=update)
            key = tuple((b.shape, str(b.dtype)) for b in batch)
            if key not in built:
                # one compiled step per batch shape (kept, not
                # replaced: a short trailing batch must not retrace
                # the full-size step every epoch)
                loss_fn, params, _ = self._audit_step_target(ins, labs)
                built[key] = (sorted(params), jax.jit(
                    self._build_dp_step(loss_fn, params, len(batch),
                                        dp, quantized=True)))
            pkeys, step = built[key]
            raw = self.network.raw_state()
            p = {k: raw[k] for k in pkeys}
            loss, grads = step(p, *batch)
            named = dict(self.network.named_parameters())
            for k, g in grads.items():
                t = named.get(k)
                if t is None or t.stop_gradient:
                    continue
                # accumulate like backward() so update=False batches
                # (accumulate_grad_batches) compose
                t._grad = g if t._grad is None else t._grad + g
            self.quantized_dp_steps += 1
            if update and self._optimizer is not None:
                self._optimizer.step()
                self._optimizer.clear_grad()
            metrics = []
            if self._metrics:
                from ..core import tape as _tape

                with _tape.no_grad():
                    outputs = self.network(
                        *(Tensor(a) for a in ins_arr))
                labels = [Tensor(l) for l in lab_arr]
                for m in self._metrics:
                    m.update(*_to_list(m.compute(
                        *_to_list(outputs), *labels)))
                    metrics.append(m.accumulate())
            out = [float(loss)]
            return (out, metrics) if metrics else out

        return dp_train_batch

    def _trace_step_for_audits(self, ins, labs):
        """(Graph, name) of the training step, traced ONCE for the
        comms + roofline hooks to share; None on failure (each hook
        then traces — and warns — on its own)."""
        try:
            from ..analysis import memory as _mem

            target, name, params, batch = self._audit_step_program(
                ins, labs, "audit_comms/audit_roofline")
            return _mem.trace_auto(target, params, *batch,
                                   name=name), name
        except Exception:
            return None

    def _audit_comms(self, ins, labs, traced=None):
        """One-shot static communication audit of the training step at
        the first batch's shapes (fit(audit_comms=True)): traces loss +
        backward, host-side only. Data parallelism here is batch
        sharding over the global mesh's `dp` axis, and the gradient
        all-reduce is inserted by GSPMD at COMPILE time — invisible to
        a traced jaxpr — so the audit builds the dp step explicitly
        (shard_map over dp, `lax.psum` over the grads: the canonical
        dp gradient sync) and counts exactly the wire bytes the
        compiled step pays. An audit failure must never take down
        training — it degrades to a warning."""
        try:
            from ..analysis import comms as _comms
            from ..analysis import memory as _mem
            from ..analysis.pipeline import analyze as _analyze
            from ..observability import record_event

            if traced is not None:
                g, name = traced
            else:
                target, name, params, batch = self._audit_step_program(
                    ins, labs, "audit_comms")
                g = _mem.trace_auto(target, params, *batch, name=name)
            rep = _comms.audit_graph(g)
            lint = _analyze(None, graph=g,
                            rules=["TPU801", "TPU802", "TPU803"])
            self.comms_audit = {
                **rep.to_dict(),
                "diagnostics": lint.to_dict()["diagnostics"],
            }
            record_event("comms.audit", target=name,
                         bytes_on_wire=rep.total_wire_bytes,
                         n_collectives=rep.n_collectives, mp=rep.mp)
        except Exception as e:  # pragma: no cover - defensive
            import warnings

            warnings.warn(f"fit(audit_comms=True) failed: "
                          f"{type(e).__name__}: {e}")

    def _audit_roofline(self, ins, labs, traced=None):
        """One-shot static roofline audit of the training step at the
        first batch's shapes (fit(audit_roofline=True)): traces loss +
        backward through `analysis/roofline.py` — per-eqn FLOPs +
        fusion-aware HBM bytes against the device-spec table, the
        predicted step time / bound class / MFU `bench.py` measures,
        and the TPU901/902/903 diagnostics. Same traced step as the
        comms audit (`_audit_step_program` — under a dp mesh the
        sharded step with its explicit gradient psum, so the per-chip
        numbers and the wire term are real). Host-side only; failures
        degrade to a warning."""
        try:
            from ..analysis import memory as _mem
            from ..analysis import roofline as _roof
            from ..analysis.pipeline import analyze as _analyze
            from ..observability import record_event

            if traced is not None:
                g, name = traced
            else:
                target, name, params, batch = self._audit_step_program(
                    ins, labs, "audit_roofline")
                g = _mem.trace_auto(target, params, *batch, name=name)
            rep = _roof.audit_graph(g)
            lint = _analyze(
                None, graph=g, rules=["TPU901", "TPU902", "TPU903"],
                rule_config={"TPU901.device": rep.spec.name,
                             "TPU902.device": rep.spec.name,
                             "TPU903.device": rep.spec.name})
            self.roofline_audit = {
                **rep.to_dict(),
                "diagnostics": lint.to_dict()["diagnostics"],
            }
            record_event("roofline.audit", target=name,
                         device=rep.spec.name,
                         predicted_step_ms=rep.predicted_step_ms,
                         predicted_mfu=rep.predicted_mfu,
                         bound=rep.bound, mp=rep.mp)
        except Exception as e:  # pragma: no cover - defensive
            import warnings

            warnings.warn(f"fit(audit_roofline=True) failed: "
                          f"{type(e).__name__}: {e}")

    def _save_checkpoint(self, mgr, epoch, step_in_epoch, global_step,
                         blocking):
        """Model + optimizer + loop position as one atomic generation.
        meta records the NEXT position to run: epoch/step_in_epoch
        point just past the last completed batch."""
        state = {"model": self.network.state_dict()}
        if self._optimizer is not None:
            state["optimizer"] = self._optimizer.state_dict()
        tr = obs_trace.get_tracer()
        mt = obs_metrics.get_metrics()
        t0 = time.perf_counter()
        mgr.save(state, step=global_step,
                 meta={"epoch": epoch, "step_in_epoch": step_in_epoch,
                       "global_step": global_step}, blocking=blocking)
        if tr is not None:
            tr.complete("fit.checkpoint_save", int(t0 * 1e9),
                        time.perf_counter_ns(), step=global_step,
                        blocking=blocking)
        if mt is not None:
            mt.histogram("fit_checkpoint_save_s",
                         "checkpoint snapshot+enqueue (or full write "
                         "when blocking)").observe(
                             time.perf_counter() - t0)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._make_loader(eval_data, batch_size, False)
        cbks = (callbacks if isinstance(callbacks, CallbackList)
                else CallbackList(_to_list(callbacks)
                                  or [ProgBarLogger(log_freq, verbose)]))
        cbks.set_model(self)
        for m in self._metrics:
            m.reset()
        cbks.on_eval_begin()
        logs = {}
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            ins, labs = self._split_batch(batch)
            res = self.eval_batch(ins, labs)
            logs = self._pack_logs(res)
            cbks.on_eval_batch_end(step, logs)
        cbks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False)
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch, labeled=False)
            outputs.append(self.predict_batch(ins))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([b[i] for b in outputs])
                    for i in range(n_out)]
        return outputs

    # ------------------------------------------------------------------
    def _split_batch(self, batch, labeled=True):
        if isinstance(batch, (list, tuple)):
            batch = list(batch)
            if len(batch) > 1:
                # last element is the label (reference: fit assumes
                # (input..., label) batches); predict drops it
                return batch[:-1], (batch[-1:] if labeled else [])
            return batch, []
        return [batch], []

    def _metric_names(self):
        names = ["loss"]
        for m in self._metrics:
            n = m.name()
            names += n if isinstance(n, list) else [n]
        return names

    def _pack_logs(self, res):
        if isinstance(res, tuple):
            losses, metrics = res
        else:
            losses, metrics = res, []
        logs = {"loss": losses}
        for m, v in zip(self._metrics, metrics):
            n = m.name()
            logs[n[0] if isinstance(n, list) else n] = v
        return logs

    # ------------------------------------------------------------------
    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def save(self, path, training=True):
        """reference: hapi/model.py save — params (+ optimizer state)."""
        from ..framework.io import save
        import os

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load

        state = load(path + ".pdparams")
        self.network.set_state_dict(state)
        if not reset_optimizer and self._optimizer is not None:
            try:
                self._optimizer.set_state_dict(load(path + ".pdopt"))
            except FileNotFoundError:
                pass

    def summary(self, input_size=None, dtype=None):
        return summary(self.network, input_size, dtypes=dtype)


def summary(net, input_size=None, dtypes=None, input=None):
    """reference: hapi/model_summary.py — layer table + param counts."""
    rows = []
    total = 0
    trainable = 0
    for name, p in net.named_parameters():
        n = int(np.prod(p.shape))
        total += n
        if not p.stop_gradient:
            trainable += n
        rows.append((name, list(p.shape), n))
    width = max((len(r[0]) for r in rows), default=20) + 2
    lines = [f"{'Layer (param)':<{width}}{'Shape':<20}{'Params':>12}",
             "-" * (width + 32)]
    for name, shape, n in rows:
        lines.append(f"{name:<{width}}{str(shape):<20}{n:>12,}")
    lines.append("-" * (width + 32))
    lines.append(f"Total params: {total:,}")
    lines.append(f"Trainable params: {trainable:,}")
    print("\n".join(lines))
    return {"total_params": total, "trainable_params": trainable}
