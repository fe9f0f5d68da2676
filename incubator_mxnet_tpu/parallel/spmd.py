"""SPMD training: one jitted step over a device mesh.

This is the performance path of the framework — the analog of the reference's
north-star stack (SURVEY.md §3.2 + §3.3 combined): CachedOp forward +
backward + kvstore allreduce + optimizer update, fused into ONE XLA
computation partitioned over a Mesh. Gradients AllReduce over ICI because
the batch is sharded on the ``data`` axis; tensor-parallel parameters shard
per their ``PartitionSpec`` rules; XLA overlaps the collectives with backward
compute (replacing the reference's engine-mediated comm/compute overlap).

Optimizers here are optax transformations (idiomatic jax); the imperative
``mx.optimizer`` names map onto them, so ``SPMDTrainer(net, loss, 'sgd',
{'learning_rate': .1, 'momentum': .9})`` matches ``gluon.Trainer`` semantics.
"""

from __future__ import annotations

import re
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import autograd
from .. import random as _random
from .. import telemetry
from ..gluon.parameter import Parameter, _trace
from ..gluon.block import _Trace
from ..ndarray import NDArray
from .mesh import DATA_AXIS, make_mesh

#: phases of ``SPMDTrainer.step``'s turn-ledger record
#: (docs/OBSERVABILITY.md "Phases and the turn ledger")
_STEP_PHASES = ("h2d", "rng", "dispatch", "meter")


def _to_optax(optimizer, optimizer_params: Optional[dict]):
    """Map mx optimizer names/objects to optax transformations."""
    if isinstance(optimizer, optax.GradientTransformation):
        return optimizer
    p = dict(optimizer_params or {})
    lr = p.pop("learning_rate", 0.01)
    wd = p.pop("wd", 0.0)
    name = optimizer.lower() if isinstance(optimizer, str) else None
    if name == "sgd":
        mom = p.pop("momentum", 0.0)
        tx = optax.sgd(lr, momentum=mom if mom else None)
    elif name == "nag":
        tx = optax.sgd(lr, momentum=p.pop("momentum", 0.9), nesterov=True)
    elif name == "adam":
        tx = optax.adam(lr, b1=p.pop("beta1", 0.9), b2=p.pop("beta2", 0.999),
                        eps=p.pop("epsilon", 1e-8))
    elif name == "adamw":
        tx = optax.adamw(lr, b1=p.pop("beta1", 0.9),
                         b2=p.pop("beta2", 0.999),
                         eps=p.pop("epsilon", 1e-8), weight_decay=wd)
        wd = 0.0
    elif name == "lamb":
        tx = optax.lamb(lr, b1=p.pop("beta1", 0.9), b2=p.pop("beta2", 0.999),
                        eps=p.pop("epsilon", 1e-6), weight_decay=wd)
        wd = 0.0
    elif name == "rmsprop":
        tx = optax.rmsprop(lr, decay=p.pop("gamma1", 0.9),
                           eps=p.pop("epsilon", 1e-8))
    elif name == "adagrad":
        tx = optax.adagrad(lr, eps=p.pop("eps", 1e-7))
    else:
        raise ValueError(f"no optax mapping for optimizer {optimizer!r}")
    if wd:
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    clip = p.pop("clip_gradient", None)
    if clip is not None:
        tx = optax.chain(optax.clip(clip), tx)
    return tx


def collect_params(block) -> "OrderedDict[str, Parameter]":
    """Collect a Block's unique initialized Parameters by structural name
    (shared by SPMDTrainer and PipelineTrainer)."""
    by_name = block._collect_params_with_prefix()
    objs: "OrderedDict[str, Parameter]" = OrderedDict()
    seen = set()
    for name, p in by_name.items():
        if id(p) in seen:
            continue
        seen.add(id(p))
        if p._data is None:
            raise RuntimeError(
                f"parameter {name} not initialized; run one eager forward "
                "(or pass explicit shapes) before building the trainer")
        objs[name] = p
    return objs


def functional_apply(block, objs: "OrderedDict[str, Parameter]", pvals,
                     *args):
    """Apply a Block with parameter values injected functionally via the
    _Trace mechanism. Returns ``(out_jax, aux)`` where ``aux`` maps
    parameter name -> updated value for mutated auxiliary state
    (BatchNorm running stats)."""
    param_map = {id(p): NDArray(pvals[n]) for n, p in objs.items()}
    trace = _Trace(param_map)
    _trace.stack.append(trace)
    try:
        with autograd._RecordingStateScope(False, True):
            out = block.forward(*[NDArray(a) for a in args])
    finally:
        _trace.stack.pop()
    id2name = {id(p): n for n, p in objs.items()}
    aux = {id2name[i]: v for i, (p, v) in trace.aux.items() if i in id2name}
    return out._data, aux


def shard_params(net, rules: Dict[str, PartitionSpec]) -> None:
    """Attach PartitionSpec sharding rules to parameters by regex on the
    structural name — the TP/SP analog of the reference's ``group2ctx``
    manual placement (SURVEY.md §2.4 TP row)."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules.items()]
    for name, p in net._collect_params_with_prefix().items():
        for pat, spec in compiled:
            if pat.search(name):
                p._sharding = spec
                break


def make_functional_loss(net, loss_fn, trainable_objs, frozen_objs):
    """Build the pure ``(train_p, frozen_p, rng, data, labels) ->
    (mean_loss, aux)`` closure over a Block + loss: parameter values are
    injected via the ``_Trace`` mechanism, RNG draws route through
    ``key_provider`` so dropout masks derive from the step's key, and
    ``aux`` carries mutated auxiliary state (BatchNorm running stats) by
    parameter name. Shared by ``SPMDTrainer._build_step`` and the gluon
    ``SuperStep`` engine (gluon/trainer.py) so both compile the same
    step body."""

    def loss_of(train_p, frozen_p, rng, data_arrays, label_arrays):
        param_map = {}
        for n, p in trainable_objs.items():
            param_map[id(p)] = NDArray(train_p[n])
        for n, p in frozen_objs.items():
            param_map[id(p)] = NDArray(frozen_p[n])
        trace = _Trace(param_map)
        _trace.stack.append(trace)
        try:
            with _random.key_provider(rng), \
                    autograd._RecordingStateScope(False, True):
                ins = [NDArray(a) for a in data_arrays]
                out = net.forward(*ins)
                outs = out if isinstance(out, tuple) else (out,)
                labels = [NDArray(a) for a in label_arrays]
                loss = loss_fn(*outs, *labels)
        finally:
            _trace.stack.pop()
        loss_val = jnp.mean(loss._data.astype(jnp.float32))
        id2name = {id(p): n for n, p in frozen_objs.items()}
        id2name.update({id(p): n for n, p in trainable_objs.items()})
        aux = {id2name[i]: v for i, (p, v) in trace.aux.items()
               if i in id2name}
        return loss_val, aux

    return loss_of


class SPMDTrainer:
    """Own the params as a sharded pytree; run fused jitted train steps.

    Usage::

        mesh = parallel.make_mesh({'data': -1})
        st = parallel.SPMDTrainer(net, loss_fn, 'sgd',
                                  {'learning_rate': 0.1}, mesh=mesh)
        loss = st.step(x, y)          # x, y: NDArray/np — sharded on 'data'
        st.sync_to_net()              # write params back into the Block
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh: Optional[Mesh] = None, data_axis: str = DATA_AXIS,
                 *, donate: bool = True,
                 shard_weight_update: bool = False,
                 zero_stage: Optional[int] = None,
                 collective_quant: Optional[str] = None,
                 zero_remat: Optional[bool] = None):
        # donate/shard_weight_update are keyword-only: a removed middle
        # parameter must fail loudly on stale positional call sites
        #
        # ZeRO ladder (docs/TRAINING.md): ``zero_stage`` 0-3 (default:
        # MXTPU_ZERO_STAGE; ``shard_weight_update=True`` is the stage-1
        # back-compat spelling), ``collective_quant`` none/int8/2bit
        # block-quantizes the stage>=2 gradient reduce-scatter (default:
        # MXTPU_COLLECTIVE_QUANT), ``zero_remat`` controls the stage-3
        # just-in-time re-gather in backward (default: on at stage 3).
        self.net = net
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else make_mesh()
        self.data_axis = data_axis
        self.tx = _to_optax(optimizer, optimizer_params)
        self._step_cache: Dict[Any, Callable] = {}
        self._num_steps = 0
        self._donate = donate
        self._telemetry = telemetry.StepMeter("spmd.step")
        self._loop_telemetry = telemetry.StepMeter("spmd.run_steps")
        self._superstep_telemetry = telemetry.StepMeter("spmd.superstep")
        # nominal K of the superstep feed driving this trainer (set by
        # superstep_feed); resilience.Supervisor scales its hung-step
        # deadline by it so a K-times-longer dispatch is not a hang
        self.superstep_window = 1
        self._flops_cache: Dict[Any, Optional[float]] = {}
        telemetry.maybe_start_http()

        self._param_objs = collect_params(net)
        self._trainable = {n: p for n, p in self._param_objs.items()
                           if p.grad_req != "null"}
        self._frozen = {n: p for n, p in self._param_objs.items()
                        if p.grad_req == "null"}

        # ZeRO plan (parallel/zero.py): which stage of the ladder, which
        # tensors shard, whether the collectives quantize. Stage 1 is
        # the pre-existing "Automatic Cross-Replica Sharding of Weight
        # Update" behavior (arXiv:2004.13336): optimizer-state leaves of
        # REPLICATED params shard over the data axis and XLA's SPMD
        # partitioner computes each replica's 1/N update slice. Stages
        # 2/3 swap in the zero.build_step body (in-graph reduce-scatter,
        # parameters sharded at rest).
        from . import zero as zero_mod

        def _is_replicated(p):
            return (p._sharding is None
                    or all(e is None for e in tuple(p._sharding)))

        stage = zero_mod.resolve_stage(zero_stage, shard_weight_update)
        quant = zero_mod.resolve_quant(collective_quant)
        self.zero_plan = None
        if stage or quant != "none":
            self.zero_plan = zero_mod.ZeroPlan(
                self.mesh, data_axis, stage, quant,
                zero_mod.default_block(),
                shapes={n: tuple(p._data._data.shape)
                        for n, p in self._trainable.items()},
                dtypes={n: p._data._data.dtype
                        for n, p in self._trainable.items()},
                replicated={n: _is_replicated(p)
                            for n, p in self._trainable.items()},
                remat=zero_remat)

        # place params on the mesh per their rules (default: replicated;
        # ZeRO-3 shards eligible params at rest)
        def shard_of(p, name=None):
            spec = p._sharding if p._sharding is not None else PartitionSpec()
            if (name is not None and self.zero_plan is not None
                    and _is_replicated(p)):
                rest = self.zero_plan.param_rest_spec(name)
                if rest is not None:
                    spec = rest
            return NamedSharding(self.mesh, spec)

        self.params = {n: jax.device_put(p._data._data, shard_of(p, n))
                       for n, p in self._trainable.items()}
        self.frozen = {n: jax.device_put(p._data._data, shard_of(p))
                       for n, p in self._frozen.items()}
        self.opt_state = self.tx.init(self.params)
        if self.zero_plan is not None and self.zero_plan.stage >= 1:
            self.opt_state = zero_mod.shard_opt_state(
                self.zero_plan, self.opt_state, self.params)
            if self.zero_plan.quantized():
                # error-feedback residual rides inside the donated
                # opt_state (checkpointed / resumed with it)
                self.opt_state = zero_mod.wrap_opt_state(
                    self.opt_state,
                    self.zero_plan.init_residuals(self.params))
        self._batch_sharding = NamedSharding(self.mesh,
                                             PartitionSpec(data_axis))
        # latency-hiding ZeRO-3 decision record (set per compiled step
        # signature by _build_step via _note_overlap)
        self.zero_overlap: Optional[Dict[str, Any]] = None
        self.zero_overlap_fallback: Optional[str] = None
        if self.zero_plan is not None:
            self.zero_last_stats = self.zero_plan.publish(
                "spmd.step", self.params, self.opt_state, self.frozen)
            self._wire_per_step = float(
                self.zero_last_stats["wire_bytes_per_step"])
            self._wire_counter = telemetry.counter(
                "mxtpu_collective_wire_bytes_total",
                "cumulative per-chip bytes-on-wire of the fused step's "
                "collectives (static schedule x steps)", site="spmd.step")
        else:
            self.zero_last_stats = None
            self._wire_per_step = 0.0
            self._wire_counter = None

    # -- the fused step -----------------------------------------------------
    def _build_step(self, n_data: int, n_label: int, example=None):
        # ``example`` = (data_arrays, label_arrays) — arrays or
        # ShapeDtypeStructs of ONE step's batch, the signature the
        # overlap planner validates its scan body against (no example ->
        # the PR 10 unrolled body, reason recorded)
        tx = self.tx
        loss_of = make_functional_loss(self.net, self.loss_fn,
                                       self._trainable, self._frozen)

        from ..config import matmul_precision_for

        precision = matmul_precision_for(
            p.dtype for p in self.params.values())

        if self.zero_plan is not None and self.zero_plan.ingraph():
            # ZeRO-2/3 step body (parallel/zero.py): in-graph gradient
            # reduce-scatter (block-quantized when configured), sharded
            # update, params re-placed to their at-rest layout — same
            # signature/donation contract, so run_steps/run_superstep
            # compile it into their loops unchanged
            from . import zero as zero_mod

            # latency-hiding ZeRO-3 (ISSUE 18): swap the unrolled loss
            # for the double-buffered scan-over-layers body where
            # layer_plan can group the model — build_step compiles
            # whichever loss it is handed, so everything downstream
            # (quantized shard_map, remat, donation) is unchanged
            ov_loss, info = zero_mod.plan_overlap(
                self.zero_plan, self.net, self.loss_fn,
                self._trainable, self._frozen, loss_of,
                example[0] if example else None,
                example[1] if example else None)
            self._note_overlap(info)
            if ov_loss is not None:
                loss_of = ov_loss
            return zero_mod.build_step(self.zero_plan, loss_of, tx,
                                       precision)

        def step(train_p, frozen_p, opt_state, rng, data_arrays,
                 label_arrays):
            # bf16 models trace at DEFAULT matmul precision (native MXU
            # bf16 passes); f32 models keep full precision — overriding
            # the package-global 'highest' for the compiled fast path
            with jax.default_matmul_precision(precision):
                (loss, aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_p, frozen_p, rng,
                                           data_arrays, label_arrays)
                updates, opt_state = tx.update(grads, opt_state, train_p)
                train_p = optax.apply_updates(train_p, updates)
            for n, v in aux.items():
                if n in frozen_p:
                    frozen_p = {**frozen_p, n: v}
                elif n in train_p:
                    train_p = {**train_p, n: v}
            return train_p, frozen_p, opt_state, loss

        return step

    def _jit_step(self, n_data: int, n_label: int, example=None):
        return jax.jit(self._build_step(n_data, n_label, example),
                       donate_argnums=(0, 1, 2) if self._donate else ())

    def _note_overlap(self, info: Dict[str, Any]) -> None:
        """Record the overlap-engagement decision (PR 8 ``last_fallback``
        style): ``zero_overlap`` holds the planner's info dict,
        ``zero_overlap_fallback`` the recorded reason whenever the PR 10
        unrolled body compiles instead of the scan. Publishes the
        ``mxtpu_zero_overlap_engaged`` gauge and a ``kind:
        "zero_overlap"`` JSONL record (tools/telemetry_report.py turns
        ``overlap_fraction`` into ``zero/<site>/overlap_fraction``
        compare keys)."""
        self.zero_overlap = dict(info)
        self.zero_overlap_fallback = info.get("reason")
        telemetry.gauge(
            "mxtpu_zero_overlap_engaged",
            "1 when the double-buffered scan-over-layers ZeRO-3 step "
            "body is compiled, 0 when the unrolled body runs",
            site="spmd.step").set(1.0 if info.get("engaged") else 0.0)
        rec: Dict[str, Any] = {"kind": "zero_overlap", "site": "spmd.step"}
        rec.update(info)
        telemetry.jsonl_emit(rec)

    @staticmethod
    def _as_jax(x):
        from .superstep import as_jax

        return as_jax(x)

    def device_prefetcher(self, source, depth: Optional[int] = None):
        """The preferred feed for :meth:`step` (docs/DATA.md): wrap a
        ``mxtpu.data`` pipeline (or any re-iterable of ``(data, labels)``
        batches) in a :class:`~..data.DevicePrefetcher` that stages the
        next batches on the mesh with THIS trainer's batch sharding, so
        the H2D transfer overlaps the running step and ``step``'s own
        ``device_put`` becomes a no-op::

            feed = st.device_prefetcher(pipe)
            for x, y in feed:
                loss = st.step(x, y)
        """
        from ..data import DevicePrefetcher

        return DevicePrefetcher(source, sharding=self._batch_sharding,
                                depth=depth, site="spmd.data")

    def step(self, data, labels) -> float:
        """One fused forward+backward+update step. ``data``/``labels`` may be
        a single array or a list; they are sharded along the data axis.
        Batches staged by :meth:`device_prefetcher` are already resident
        with the right sharding — the ``device_put`` below is then a
        no-op and the step never blocks on the feed."""
        # chaos sites fire BEFORE the rng draw / any state mutation, so
        # a supervised retry of a failed step is bit-identical
        from ..resilience import chaos

        turn = telemetry.trace.Turn("spmd.step", _STEP_PHASES)
        t0 = time.perf_counter()
        chaos.maybe_inject("step", detail="spmd")
        chaos.maybe_inject("step.slow", detail="spmd")
        data = data if isinstance(data, (list, tuple)) else [data]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        with turn.phase("h2d"):
            data_arrays = [jax.device_put(self._as_jax(d),
                                          self._batch_sharding)
                           for d in data]
            label_arrays = [jax.device_put(self._as_jax(l),
                                           self._batch_sharding)
                            for l in labels]
        key = (tuple((a.shape, str(a.dtype)) for a in data_arrays),
               tuple((a.shape, str(a.dtype)) for a in label_arrays))
        fn = self._step_cache.get(key)
        miss = fn is None
        if miss:
            fn = self._jit_step(len(data_arrays), len(label_arrays),
                                (data_arrays, label_arrays))
            self._step_cache[key] = fn
        self._num_steps += 1
        with turn.phase("rng"):
            rng = _random.next_key()
        # trace/execute under the ambient-mesh scope so mesh-aware ops
        # (e.g. moe_ffn's expert-axis sharding constraint) see self.mesh
        from .mesh import mesh_scope

        h2d = sum(int(a.nbytes) for a in data_arrays + label_arrays)
        with telemetry.trace.span("spmd.step", step=self._num_steps), \
                self._telemetry.step(
                h2d_bytes=h2d,
                flops_fn=lambda: self._flops_for(key, data, labels),
                turn=turn):
            if miss:
                # jax.monitoring-less fallback: the ragged-batch
                # recompile this cache miss implies must still be seen.
                # Inside the meter scope, so its site_compiles tick
                # marks this step compile-dominated (EMA/MFU exclusion)
                # just like a real compile event would.
                telemetry.note_cache_miss("spmd.step", detail=str(key[0]))
            with mesh_scope(self.mesh), turn.phase("dispatch"):
                self.params, self.frozen, self.opt_state, loss = fn(
                    self.params, self.frozen, self.opt_state, rng,
                    data_arrays, label_arrays)
        self._note_wire(1)
        turn.close(t0, time.perf_counter() - t0)
        return loss

    def _note_wire(self, k: int) -> None:
        """Account k steps' worth of collective bytes-on-wire (static
        schedule; mxtpu_collective_wire_bytes_total)."""
        if self._wire_counter is not None and self._wire_per_step:
            self._wire_counter.inc(self._wire_per_step * k)

    def _flops_for(self, key, data, labels) -> Optional[float]:
        """Per-step cost-analysis FLOPs, computed once per step-cache
        signature (an extra AOT compile) and only when the telemetry MFU
        gauge is observed."""
        if key not in self._flops_cache:
            self._flops_cache[key] = self.step_cost_analysis(data, labels)
        return self._flops_cache[key]

    def _compile_step(self, data, labels):
        """Lower + compile the fused step for introspection (cost
        analysis, HLO dump) without executing it; ``None`` on backends
        that cannot compile ahead of time."""
        data = data if isinstance(data, (list, tuple)) else [data]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        data_arrays = [jax.device_put(self._as_jax(d), self._batch_sharding)
                       for d in data]
        label_arrays = [jax.device_put(self._as_jax(l),
                                       self._batch_sharding)
                        for l in labels]
        fn = self._jit_step(len(data_arrays), len(label_arrays),
                            (data_arrays, label_arrays))
        from .mesh import mesh_scope

        try:
            # deliberate introspection compile (MFU probe / HLO dump):
            # probe_scope keeps it off the watchdog's drift radar
            with telemetry.probe_scope(), mesh_scope(self.mesh):
                return fn.lower(
                    self.params, self.frozen, self.opt_state,
                    jax.random.PRNGKey(0), data_arrays,
                    label_arrays).compile()
        except Exception:
            return None

    def step_cost_analysis(self, data, labels):
        """XLA's own cost model for the fused train-step executable:
        returns the per-step ``flops`` estimate (float, model+optimizer,
        fwd+bwd) or ``None`` where the PJRT backend doesn't expose cost
        analysis. Used by ``bench.py`` for MFU accounting — one source of
        truth instead of hand-maintained per-model FLOP formulas."""
        return telemetry.flops_of_compiled(self._compile_step(data, labels))

    def step_hlo_text(self, data, labels) -> Optional[str]:
        """Post-optimization HLO of the compiled fused train-step
        executable (or ``None`` where the backend doesn't expose it).

        The inspectable artifact behind the comm/compute-overlap claim
        (VERDICT r5 item 5 / PROFILE.md "Comm/compute overlap"): on a
        multi-device mesh this text shows the gradient ``all-reduce``
        inside the ONE compiled module next to the backward/optimizer
        compute — the structural property that lets XLA's latency-hiding
        scheduler hoist ``all-reduce-start``/``all-reduce-done`` apart on
        backends with async collectives (TPU). ``tests/test_overlap_hlo.py``
        asserts the pattern."""
        compiled = self._compile_step(data, labels)
        if compiled is None:
            return None
        try:
            return compiled.as_text()
        except Exception:
            return None

    def run_steps(self, n: int, data, labels) -> float:
        """Run ``n`` fused steps ON DEVICE in one dispatch (a
        ``lax.fori_loop`` over the step body, per-iteration rng derived
        with ``fold_in``). One host round-trip regardless of ``n`` — the
        sustained-throughput analog of the reference engine's async op
        pipelining, and the right way to measure small-model training
        throughput where per-dispatch host latency is comparable to the
        step. The batch is reused every iteration (synthetic-benchmark
        semantics)."""
        from jax import lax

        data = data if isinstance(data, (list, tuple)) else [data]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        data_arrays = [jax.device_put(self._as_jax(d), self._batch_sharding)
                       for d in data]
        label_arrays = [jax.device_put(self._as_jax(l),
                                       self._batch_sharding)
                        for l in labels]
        key = ("loop", int(n),
               tuple((a.shape, str(a.dtype)) for a in data_arrays),
               tuple((a.shape, str(a.dtype)) for a in label_arrays))
        fn = self._step_cache.get(key)
        miss = fn is None
        if miss:
            raw = self._build_step(len(data_arrays), len(label_arrays),
                                   (data_arrays, label_arrays))

            def loop(train_p, frozen_p, opt_state, rng, data_arrays,
                     label_arrays):
                def body(i, carry):
                    tp, fp, os_, _ = carry
                    k = jax.random.fold_in(rng, i)
                    return raw(tp, fp, os_, k, data_arrays, label_arrays)

                init = (train_p, frozen_p, opt_state,
                        jnp.zeros((), jnp.float32))
                return lax.fori_loop(0, n, body, init)

            fn = jax.jit(loop, donate_argnums=(0, 1, 2)
                         if self._donate else ())
            self._step_cache[key] = fn
        self._num_steps += n
        rng = _random.next_key()
        from .mesh import mesh_scope

        # MFU for the loop uses the SINGLE-step executable's flops (the
        # loop body is the step body; per-step wall time is dt/n)
        skey = (tuple((a.shape, str(a.dtype)) for a in data_arrays),
                tuple((a.shape, str(a.dtype)) for a in label_arrays))
        h2d = sum(int(a.nbytes) for a in data_arrays + label_arrays)
        with telemetry.trace.span("spmd.run_steps", n=n,
                                  step=self._num_steps), \
                self._loop_telemetry.step(
                h2d_bytes=h2d, count=n,
                flops_fn=lambda: self._flops_for(skey, data, labels)):
            if miss:
                # fallback miss inside the scope: see step()
                telemetry.note_cache_miss("spmd.run_steps", detail=f"n={n}")
            with mesh_scope(self.mesh):
                self.params, self.frozen, self.opt_state, loss = fn(
                    self.params, self.frozen, self.opt_state, rng,
                    data_arrays, label_arrays)
        self._note_wire(n)
        return loss

    # -- superstep: K distinct batches per dispatch -------------------------
    def _window_sharding(self) -> NamedSharding:
        from .superstep import window_spec

        return NamedSharding(self.mesh,
                             window_spec(self._batch_sharding.spec))

    def superstep_feed(self, source, window: Optional[int] = None,
                       depth: Optional[int] = None):
        """The feed for :meth:`run_superstep` (docs/TRAINING.md
        "Superstep"): stacks windows of ``window`` distinct batches from
        ``source`` (an ``mxtpu.data`` pipeline, or any re-iterable of
        ``(data, labels)`` batches) and stages them on the mesh with the
        window sharding, double-buffered — window N+1's H2D overlaps
        window N's training::

            feed = st.superstep_feed(pipe, window=8)
            for win in feed:
                losses = st.run_superstep(*win)   # ONE dispatch, [8] losses

        Resumable like any DevicePrefetcher feed: the window stage's
        cursor counts windows, so a checkpoint at a superstep boundary
        advances the data sidecar by exactly ``window`` batches per
        superstep. The epoch's tail (fewer than ``window`` batches left)
        comes out as a short window — :meth:`run_superstep` runs it as a
        short tail superstep, no sample is dropped."""
        from ..data import DevicePrefetcher
        from ..data.pipeline import Stage, from_iter
        from .superstep import superstep_window

        k = superstep_window() if window is None else max(1, int(window))
        if not isinstance(source, Stage):
            src = from_iter(lambda: iter(source))
        else:
            src = source
        self.superstep_window = k
        return DevicePrefetcher(src.window(k),
                                sharding=self._window_sharding(),
                                depth=depth, site="spmd.superstep.data",
                                steps_per_item=k)

    def run_superstep(self, data, labels):
        """Train on K *distinct* batches in ONE dispatch: ``data``/
        ``labels`` leaves are stacked ``[K, ...]`` windows (from
        :meth:`superstep_feed`, ``data.Stage.window`` or
        ``superstep.stack_window``); the compiled ``lax.fori_loop`` body
        slices batch ``i`` with ``dynamic_index_in_dim`` and runs the
        same fused step body ``step`` compiles. Returns the ``[K]``
        per-step loss array, so the loss stream stays per-step.

        Bit-exactness contract (tests/test_superstep.py): the loss
        stream, every dropout draw, and the final params equal K
        individual ``step()`` calls on the same batches — per-iteration
        keys are the exact ``next_key()`` sequence via
        ``random.reserve_keys``. With ``MXTPU_SUPERSTEP=0`` this method
        transparently falls back to exactly those K dispatches."""
        from .superstep import (per_iteration_key, slice_window,
                                superstep_enabled, window_len)

        # chaos sites fire at superstep entry — before the RNG counter
        # reservation or any state mutation, so a supervised retry of a
        # failed superstep replays the identical K steps
        from ..resilience import chaos

        chaos.maybe_inject("step", detail="spmd.superstep")
        chaos.maybe_inject("step.slow", detail="spmd.superstep")
        data = data if isinstance(data, (list, tuple)) else [data]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        wsh = self._window_sharding()
        data_arrays = [jax.device_put(self._as_jax(d), wsh) for d in data]
        label_arrays = [jax.device_put(self._as_jax(l), wsh)
                        for l in labels]
        k = window_len(data_arrays + label_arrays)
        # advertise the window even when the caller stacked it by hand
        # (no superstep_feed): the Supervisor's hung-step deadline and
        # superstep-loss accounting key off this attribute
        if k > self.superstep_window:
            self.superstep_window = k
        if not superstep_enabled():
            # transparent fallback: the same K steps, host-dispatched
            losses = [self.step([a[i] for a in data_arrays],
                                [a[i] for a in label_arrays])
                      for i in range(k)]
            return jnp.stack([jnp.asarray(l, jnp.float32) for l in losses])
        key = ("superstep", k,
               tuple((a.shape, str(a.dtype)) for a in data_arrays),
               tuple((a.shape, str(a.dtype)) for a in label_arrays))
        fn = self._step_cache.get(key)
        miss = fn is None
        if miss:
            # validate the overlap scan against the PER-STEP signature
            # (the [K, ...] window sliced down one batch)
            per_step = (
                [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                 for a in data_arrays],
                [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                 for a in label_arrays])
            raw = self._build_step(len(data_arrays), len(label_arrays),
                                   per_step)

            def superstep(train_p, frozen_p, opt_state, base_key, c0,
                          data_w, label_w):
                def body(i, carry):
                    tp, fp, os_, losses = carry
                    rng = per_iteration_key(base_key, c0, i)
                    tp, fp, os_, loss = raw(tp, fp, os_, rng,
                                            slice_window(data_w, i),
                                            slice_window(label_w, i))
                    return tp, fp, os_, losses.at[i].set(
                        loss.astype(jnp.float32))

                init = (train_p, frozen_p, opt_state,
                        jnp.zeros((k,), jnp.float32))
                return jax.lax.fori_loop(0, k, body, init)

            fn = jax.jit(superstep, donate_argnums=(0, 1, 2)
                         if self._donate else ())
            self._step_cache[key] = fn
        base_key, c0 = _random.reserve_keys(k)
        from .mesh import mesh_scope

        # per-step MFU uses the SINGLE-step executable's flops; the
        # sliced first batch has exactly the per-step signature
        skey = (tuple((a.shape[1:], str(a.dtype)) for a in data_arrays),
                tuple((a.shape[1:], str(a.dtype)) for a in label_arrays))
        h2d = sum(int(a.nbytes) for a in data_arrays + label_arrays)
        try:
            with telemetry.trace.span("spmd.superstep", k=k,
                                      step=self._num_steps), \
                    self._superstep_telemetry.step(
                    h2d_bytes=h2d, count=k,
                    flops_fn=lambda: self._flops_for(
                        skey, [a[0] for a in data_arrays],
                        [a[0] for a in label_arrays])):
                if miss:
                    telemetry.note_cache_miss("spmd.superstep",
                                              detail=f"k={k}")
                with mesh_scope(self.mesh):
                    (self.params, self.frozen, self.opt_state,
                     losses) = fn(self.params, self.frozen,
                                  self.opt_state, base_key,
                                  jnp.asarray(c0, jnp.uint32),
                                  data_arrays, label_arrays)
        except BaseException:
            # zero steps executed (trace/compile failure, OOM): restore
            # the RNG counter so a supervised retry replays identically
            _random.rollback_keys(c0)
            raise
        self._num_steps += k
        self._note_wire(k)
        return losses

    def apply_zero_placement(self) -> None:
        """Re-place restored state to this trainer's ZeRO at-rest layout
        (called by ``restore_sharded`` after a restore — cross-STAGE
        portability): stage >= 2 plans re-place their eligible
        parameters (stage 2 replicated, stage 3 sharded 1/N over the
        data axis), stages >= 1 re-shard optimizer-state leaves, and a
        quantized plan rebuilds error-feedback residuals whose saved
        device dimension does not match the live mesh (a topology-
        changing restore: the per-device untransmitted remainders of the
        old mesh are meaningless row-wise on the new one — error
        feedback restarts from zero with a warning, training state is
        untouched). Values are never changed; no-op without a plan or
        when layouts already agree. Stage-0/1 trainers (and plan-less
        ones) keep the checkpoint's recorded layout — stage-1 weights
        live sharded after any step regardless.

        Since ISSUE 15, the device-resident re-placement runs through
        ``parallel.migrate`` — every move lowers into ONE in-ICI
        executable (site ``zero.placement``, ``mxtpu_migrate_*``
        telemetry, zero host bytes) instead of per-tensor
        ``device_put`` round-trips; the per-tensor path stays as
        fallback."""
        plan = self.zero_plan
        if plan is None:
            return
        from . import migrate as migrate_mod
        from . import zero as zero_mod

        moves: Dict[Any, Any] = {}
        wants: Dict[Any, Any] = {}
        if plan.stage >= 2:
            for n in list(self.params):
                if n not in plan.eligible:
                    continue
                spec = plan.param_rest_spec(n) or PartitionSpec()
                want = NamedSharding(self.mesh, spec)
                arr = self.params[n]
                if not want.is_equivalent_to(arr.sharding, arr.ndim):
                    moves[("param", n)] = arr
                    wants[("param", n)] = want
        inner, resid = zero_mod.split_opt_state(self.opt_state)
        leaves, treedef = jax.tree_util.tree_flatten(inner)
        if plan.stage >= 1:
            shardings = zero_mod.opt_state_shardings(plan, inner,
                                                     self.params)
            for i, (leaf, want) in enumerate(zip(leaves, shardings)):
                if want is None:
                    continue
                cur = getattr(leaf, "sharding", None)
                if cur is not None \
                        and want.is_equivalent_to(cur, leaf.ndim):
                    continue
                moves[("opt", i)] = leaf
                wants[("opt", i)] = want
        if moves:
            try:
                # donate=False: a partial failure must leave the source
                # arrays alive for the per-tensor fallback below.
                # quant pinned to none: re-placement is a placement
                # change, never a value change — a user's
                # MXTPU_MIGRATE_QUANT (meant for elastic/serving wire
                # compression) must not make restores lossy
                out = migrate_mod.migrate_arrays(
                    moves, wants, quant="none", donate=False,
                    site="zero.placement")
            except Exception as e:      # the slower per-tensor path is
                # always correct; a migrate refusal must not fail a
                # restore
                import logging

                logging.getLogger("mxtpu.zero").debug(
                    "zero placement migrate fell back to device_put: "
                    "%s", e)
                out = {k: jax.device_put(v, wants[k])
                       for k, v in moves.items()}
            for (kind, key), arr in out.items():
                if kind == "param":
                    self.params[key] = arr
                else:
                    leaves[key] = arr
        if plan.stage >= 1:
            inner = jax.tree_util.tree_unflatten(treedef, leaves)
            if resid is not None:
                resid = zero_mod.check_residuals(plan, resid)
            self.opt_state = inner if resid is None \
                else zero_mod.wrap_opt_state(inner, resid)
        if self.zero_last_stats is not None:
            self.zero_last_stats = plan.publish(
                "spmd.step", self.params, self.opt_state, self.frozen)

    def sync_to_net(self) -> None:
        """Write the trainer-owned arrays back into the Block's Parameters
        (for save_parameters / eager inference)."""
        for n, p in self._trainable.items():
            p._data._set_data(self.params[n])
        for n, p in self._frozen.items():
            p._data._set_data(self.frozen[n])
