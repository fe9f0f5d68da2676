#!/usr/bin/env python3
"""Smoke run of the two product paths on one TPU chip.

``python chip_smoke.py`` drives, in one process and through the entry
points a user calls, ``parallel.SPMDTrainer`` training (ResNet-50 v1 as
``bench.py`` builds it) and ``serving.DecodeSession`` decoding (the
117M GPT decoder), after checking that the Pallas kernels of both paths
compile for the chip and agree with plain XLA references. Each phase
prints one JSON line when it ends; a failed check raises and the script
exits non-zero. The last line of a passing run is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``python chip_smoke.py --four-chips`` runs only the multi-chip phase:
BERT-base through ZeRO-3 on ``{"data": 4}`` and tensor-parallel ZeRO-3
on ``{"data": 2, "model": 2}``, each compared step by step with the same
global batch on a one-device mesh in the same process.

The script sets neither ``JAX_PLATFORMS`` nor ``XLA_FLAGS``; without a
TPU it fails at the device phase and prints no ``"ok"`` line. The
compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (``runtime.enable_compile_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shipped sizes. ``tests/test_chip_smoke.py`` overrides them with
    a tiny set (and ``interpret=True``) to run the phase functions on the
    CPU mesh; nothing else does."""

    interpret: bool = False          # Pallas interpreter: CPU test only
    # kernels
    flash_bhtd: tuple = (4, 12, 2048, 64)
    decode_slots: int = 8
    decode_heads: int = 12
    decode_max_len: int = 1024
    # (N, H, W, Ci, Co, k, stride, pad, residual, backward) at
    # ResNet-50 shapes; the stride-2 case is forward only on the chip
    # (CONV_LEFT_OFF)
    conv_cases: tuple = (
        ("s1_56x56x64", 32, 56, 56, 64, 64, 3, 1, 1, False, True),
        ("s2_28x28x256", 32, 28, 28, 256, 256, 3, 2, 1, False, False),
        ("resid_56x56x256to64", 32, 56, 56, 256, 64, 1, 1, 0, True, True),
    )
    # train
    resnet: str = "resnet50_v1"
    classes: int = 1000
    image: int = 224
    batch_per_chip: int = 128
    train_steps: int = 20
    superstep_k: int = 4
    # serve
    gpt: str = "gpt_decoder_117m"
    gpt_kwargs: tuple = ()
    vocab: int = 50257
    max_len: int = 1024
    max_slots: int = 8
    prefill_buckets: tuple = (128, 512, 1024)
    prompt_lens: tuple = (60, 150, 333, 512, 700, 90)
    new_tokens: int = 32
    # four chips
    bert: str = "bert_12_768_12"
    bert_kwargs: tuple = ()
    bert_vocab: int = 30522
    bert_seq: int = 128
    bert_batch: int = 96
    bert_steps: int = 3


#: stated tolerances. Kernel outputs are compared as max|got - ref| over
#: max|ref| against float32 references computed from the same bf16
#: inputs; bf16 carries 8 bits of mantissa (2^-8 = 0.004 per rounding).
TOL_FLASH = 2e-2
TOL_CONV = 3e-2
#: dW, da, db of conv + BatchNorm are sums over N*H*W positions of a
#: cotangent that BatchNorm's backward has made orthogonal to 1 and to
#: the normalised output: most of each sum cancels, and what bf16
#: rounding of y and dy leaves behind does not. A plain XLA bf16
#: conv + BN shows 3-4% against the same float32 reference (CPU, N=8);
#: a wrong kernel is off by O(1).
TOL_CONV_REDUCED = 1e-1
#: serve: the streamed greedy token must score within this many logits
#: of the full forward's best token at every step (bf16 logits of
#: magnitude ~10 resolve to ~0.06), and prefill's last-row logits must
#: agree with the full forward's to the same bound
TOL_LOGIT = 0.25
#: four chips: per-step loss against the one-device run, relative
TOL_LOSS = 2e-2


class CompileLog:
    """Counts the executables the process asked XLA for, from
    ``jax.monitoring``. jax times ``compile_or_get_cached`` as
    ``backend_compile_duration``, so that event fires for every request,
    a persistent-cache hit included; the hits are counted beside it.
    "Zero post-warmup compiles" is zero requests: a hit after warm-up is
    still an executable that was asked for."""

    def __init__(self):
        from jax import monitoring

        self._requests = 0
        self.cache_hits = 0
        self.seconds = 0.0      # summed over threads; can exceed wall time
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self._requests += 1
            self.seconds += float(duration)
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1

    def requests(self) -> int:
        return self._requests

    @property
    def compiles(self) -> int:
        """Requests the backend really compiled."""
        return self._requests - self.cache_hits

    def snapshot(self):
        return self.compiles, self.cache_hits, self.seconds


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


class Phase:
    """Times one phase and prints its JSON line on a clean exit only — a
    failed phase prints nothing and the exception ends the script."""

    def __init__(self, name: str, log: CompileLog):
        self.name, self.log, self.info = name, log, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.log.snapshot()
        return self.info

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        c, h, s = self.log.snapshot()
        total = time.perf_counter() - self.t0
        emit({"phase": self.name, "ok": True, **self.info,
              "seconds": round(total, 2),
              "compile_seconds": round(s - self.c0[2], 2),
              "run_seconds": round(max(0.0, total - (s - self.c0[2])), 2),
              "compiles": c - self.c0[0],
              "cache_hits": h - self.c0[1]})
        return False


def check(cond, msg: str) -> None:
    """An ``assert`` that survives ``python -O``."""
    if not cond:
        raise AssertionError(msg)


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------
def phase_device(cache_dir: str, want_count=None) -> dict:
    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU: jax.devices() is {devs}")
    if want_count is not None and len(devs) != want_count:
        sys.exit(f"chip_smoke: need {want_count} chips, found {len(devs)}")
    emit({"phase": "device", "ok": True, **device, "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "compile_cache": cache_dir})
    return device


# ---------------------------------------------------------------------------
# phase 2: kernels
# ---------------------------------------------------------------------------
def _compiled_with_kernel(fn, args, interpret):
    """Lower + compile ``fn``; unless interpreting, the lowered text must
    hold the Mosaic custom call — an interpreter run cannot pass."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    if not interpret:
        check("tpu_custom_call" in lowered.as_text(),
              "no tpu_custom_call in the lowered kernel program")
    return lowered.compile()


def _flash_checks(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu.ops.pallas_attention import (_flash_core,
                                                          _xla_reference)

    interp = bool(sz.interpret)
    b, h, t, d = sz.flash_bhtd
    scale = 1.0 / d ** 0.5
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v, g = (jax.random.normal(ks[i], (b, h, t, d), jnp.bfloat16)
                  for i in range(4))

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def kern(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _flash_core(q, k, v, None, scale, True,
                                        interp, False), q, k, v)
        return (out,) + vjp(g)

    def ref(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: _xla_reference(q, k, v, None, scale, True),
            q, k, v)
        return (out,) + vjp(g)

    got = _compiled_with_kernel(kern, (q, k, v, g), interp)(q, k, v, g)
    want = jax.jit(ref)(*f32(q, k, v, g))
    errs = {n: rel_err(a, b) for n, a, b in
            zip(("out", "dq", "dk", "dv"), got, want)}

    # the KV-cache decode call: one query token per slot against the
    # [0, lengths) prefix of a max_len buffer
    s, hd, ml = sz.decode_slots, sz.decode_heads, sz.decode_max_len
    qd = jax.random.normal(ks[4], (s, hd, 1, d), jnp.bfloat16)
    kd = jax.random.normal(ks[5], (s, hd, ml, d), jnp.bfloat16)
    vd = jax.random.normal(ks[6], (s, hd, ml, d), jnp.bfloat16)
    lens = jnp.asarray(np.linspace(max(2, ml // 16), ml - 1, s), jnp.int32)

    def dec(q, k, v, l):
        return _flash_core(q, k, v, l, scale, True, interp, True)

    got_d = _compiled_with_kernel(dec, (qd, kd, vd, lens), interp)(
        qd, kd, vd, lens)
    want_d = jax.jit(lambda q, k, v, l: _xla_reference(
        q, k, v, l, scale, True, cache_offset=True))(*f32(qd, kd, vd), lens)
    errs["decode"] = rel_err(got_d, want_d)
    for n, e in errs.items():
        check(e <= TOL_FLASH, f"flash {n}: rel err {e:.4f} > {TOL_FLASH}")
    return {n: round(e, 5) for n, e in errs.items()}


def _conv_reference(x, w, a, b, r, g, stride, pad):
    """relu(a*x + b [+ r]) -> lax.conv -> training-mode BatchNorm, in
    float32; returns the scalar the backward differentiates (with a
    residual, the joined activation the kernel emits is part of it) plus
    the raw conv output and its per-channel sums."""
    import jax.numpy as jnp
    from jax import lax

    xp = a * x + b
    if r is not None:
        xp = xp + r
    xp = jnp.maximum(xp, 0.0)
    y = lax.conv_general_dilated(
        xp, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    s, ss = y.sum((0, 1, 2)), (y * y).sum((0, 1, 2))
    loss = _bn_loss(y, s, ss, g)
    if r is not None:
        loss = loss + xp.sum() * EMIT_WEIGHT
    return loss, (y, s, ss)


#: weight of the emitted junction activation in the residual case's loss
EMIT_WEIGHT = 1e-3


def _bn_loss(y, s, ss, g):
    import jax.numpy as jnp
    from jax import lax

    y = y.astype(jnp.float32)
    count = y.shape[0] * y.shape[1] * y.shape[2]
    mean = s / count
    var = jnp.maximum(ss / count - mean * mean, 0.0)
    return jnp.sum((y - mean) * lax.rsqrt(var + 1e-5) * g)


def _conv_checks(sz: Sizes) -> dict:
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_conv import fused_conv_bn

    interp = bool(sz.interpret)
    out = {}
    for name, n, h, w_, ci, co, k, stride, pad, resid, bwd in sz.conv_cases:
        ks = jax.random.split(jax.random.PRNGKey(len(out) + 1), 6)
        x = jax.random.normal(ks[0], (n, h, w_, ci), jnp.bfloat16)
        w = (jax.random.normal(ks[1], (k, k, ci, co), jnp.float32)
             * (2.0 / (k * k * ci)) ** 0.5).astype(jnp.bfloat16)
        a = 1.0 + 0.1 * jax.random.normal(ks[2], (ci,), jnp.float32)
        b = 0.1 * jax.random.normal(ks[3], (ci,), jnp.float32)
        r = jax.random.normal(ks[4], (n, h, w_, ci), jnp.bfloat16) \
            if resid else None
        ho = (h + 2 * pad - k) // stride + 1
        wo = (w_ + 2 * pad - k) // stride + 1
        g = jax.random.normal(ks[5], (n, ho, wo, co), jnp.float32)

        def kern_loss(x, w, a, b, r):
            res = fused_conv_bn(x, w, a, b, stride=stride, pad=pad,
                                relu=True, resid=r, emit_act=resid,
                                interpret=interp)
            y, s, ss = res[:3]
            loss = _bn_loss(y, s, ss, g)
            if resid:   # the emitted junction activation joins the loss
                act = res[3].astype(jnp.float32)
                loss = loss + act.sum() * EMIT_WEIGHT
            return loss, (y, s, ss)

        def ref_loss(x, w, a, b, r):
            return _conv_reference(x, w, a, b, r, g, stride, pad)

        args = (x, w, a, b, r)
        f32 = [None if t is None else t.astype(jnp.float32) for t in args]
        if bwd:
            wrt = (0, 1, 2, 3, 4) if resid else (0, 1, 2, 3)
            kern = jax.value_and_grad(kern_loss, argnums=wrt, has_aux=True)
            ref = jax.value_and_grad(ref_loss, argnums=wrt, has_aux=True)
            (_, (y, s, ss)), grads = _compiled_with_kernel(
                kern, args, interp)(*args)
            (_, (yr, sr, ssr)), grads_r = jax.jit(ref)(*f32)
        else:
            _, (y, s, ss) = _compiled_with_kernel(
                kern_loss, args, interp)(*args)
            _, (yr, sr, ssr) = jax.jit(ref_loss)(*f32)
            grads = grads_r = ()
        errs = {"y": rel_err(y, yr), "sum": rel_err(s, sr),
                "sumsq": rel_err(ss, ssr)}
        for gn, ga, gb in zip(("dx", "dw", "da", "db", "dr"), grads,
                              grads_r):
            errs[gn] = rel_err(ga, gb)
        for en, e in errs.items():
            tol = TOL_CONV_REDUCED if en in ("dw", "da", "db") else TOL_CONV
            check(e <= tol, f"conv {name} {en}: rel err {e:.4f} > {tol}")
        out[name] = {en: round(e, 5) for en, e in errs.items()}
    return out


def phase_kernels(sz: Sizes, log: CompileLog) -> None:
    with Phase("kernels", log) as info:
        info["interpret"] = bool(sz.interpret)
        info["tolerance"] = {"flash": TOL_FLASH, "conv": TOL_CONV,
                             "conv_dw_da_db": TOL_CONV_REDUCED}
        info["flash_rel_err"] = _flash_checks(sz)
        info["conv_rel_err"] = _conv_checks(sz)
        info["conv_left_off"] = CONV_LEFT_OFF


#: conv kernel variants the v5e compiler refuses or cannot finish (see
#: CHANGES.md, PR 21); they are not on the smoke path and nothing is
#: put in their place
CONV_LEFT_OFF = [
    "stride-2 forward, 'unroll' variant (56x56x128 -> 28x28): the Mosaic "
    "compile did not end within 400 s",
    "stride-2 backward (Pallas dW): the Mosaic compile took 140 s at "
    "14x14x512 and did not end within 400 s at 28x28x256",
]


# ---------------------------------------------------------------------------
# phase 3: train
# ---------------------------------------------------------------------------
def phase_train(sz: Sizes, log: CompileLog) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.parallel.superstep import stack_window

    with Phase("train", log) as info:
        devices = jax.devices()
        n_dev = len(devices)
        batch = sz.batch_per_chip * n_dev
        np.random.seed(0)
        mx.random.seed(0)
        # exactly bench.py's bench_resnet: zoo model, bf16, SGD+momentum,
        # pure data-parallel mesh, donation on (the trainer's default)
        net = getattr(vision, sz.resnet)(classes=sz.classes)
        net.initialize(init="xavier")
        net.cast("bfloat16")
        net(mx.nd.zeros((2, 3, sz.image, sz.image), dtype="bfloat16"))
        mesh = parallel.make_mesh({"data": -1})
        trainer = parallel.SPMDTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
        check(trainer._donate, "donation is off")
        probe = sorted(trainer.params)[:: max(1, len(trainer.params) // 8)]
        before = {n: np.asarray(trainer.params[n], np.float32)
                  for n in probe}

        def batch_fn(i):
            rs = np.random.RandomState(i)
            return (rs.rand(batch, 3, sz.image, sz.image)
                    .astype(np.float32),
                    rs.randint(0, sz.classes, (batch,)).astype(np.float32))

        sharding = NamedSharding(mesh, PartitionSpec("data"))
        bx, by = batch_fn(0)
        x = jax.device_put(jnp.asarray(bx, jnp.bfloat16), sharding)
        y = jax.device_put(jnp.asarray(by), sharding)

        wd = telemetry.get_watchdog()
        losses, times = [], []
        after_first = None
        for i in range(sz.train_steps):
            t0 = time.perf_counter()
            loss = jax.block_until_ready(trainer.step(x, y))
            times.append(time.perf_counter() - t0)
            losses.append(float(loss))
            if i == 0:
                after_first = log.requests()
        check(log.requests() == after_first,
              f"{log.requests() - after_first} compiles after the first "
              "plain step")
        check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        # lr 0.1 with momentum overshoots for a few steps before the
        # fixed batch is memorised: the fall is read first to last
        check(losses[-1] < losses[0],
              f"loss did not fall on a fixed batch: {losses}")

        # one superstep window of K distinct batches per dispatch; the
        # second window reuses the first one's executable
        def window(seed0):
            win = stack_window([batch_fn(seed0 + i)
                                for i in range(sz.superstep_k)])
            ws = trainer._window_sharding()
            return (jax.device_put(jnp.asarray(win[0], jnp.bfloat16), ws),
                    jax.device_put(jnp.asarray(win[1]), ws))

        w1, w2 = window(100), window(200)
        t0 = time.perf_counter()
        sl1 = np.asarray(jax.block_until_ready(
            trainer.run_superstep(w1[0], w1[1])), np.float32)
        t_first = time.perf_counter() - t0
        after_first = log.requests()
        t0 = time.perf_counter()
        sl2 = np.asarray(jax.block_until_ready(
            trainer.run_superstep(w2[0], w2[1])), np.float32)
        t_super = time.perf_counter() - t0
        check(log.requests() == after_first,
              "a compile after the first superstep window")
        check(sl1.shape == (sz.superstep_k,) and np.isfinite(sl1).all()
              and np.isfinite(sl2).all(),
              f"superstep losses {sl1} {sl2}")
        check(not wd.flagged(), f"recompile watchdog: {wd.flagged()}")

        for n in probe:
            now = np.asarray(trainer.params[n], np.float32)
            check(np.isfinite(now).all(), f"non-finite parameter {n}")
            check(not np.array_equal(now, before[n]),
                  f"parameter {n} did not change")
        held = set(devices)
        for n, arr in {**trainer.params, **trainer.frozen}.items():
            check(set(arr.devices()) <= held,
                  f"trainer parameter {n} is on {arr.devices()}")
        trainer.sync_to_net()
        for n, p in net.collect_params().items():
            on = p.data()._data.devices()
            check(on <= held, f"net parameter {n} is on {on}")
        info.update(
            model=sz.resnet, batch=batch, image=sz.image, dtype="bfloat16",
            params=len(trainer.params), donate=True,
            losses=[round(l, 4) for l in losses],
            superstep_k=sz.superstep_k,
            superstep_losses=[round(float(l), 4) for l in sl2],
            step_ms_median=round(1e3 * float(np.median(times[1:])), 2),
            first_step_s=round(times[0], 2),
            superstep_first_s=round(t_first, 2),
            superstep_ms_per_step=round(1e3 * t_super / sz.superstep_k, 2))


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------
def phase_serve(sz: Sizes, log: CompileLog, artifact_dir: str) -> None:
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import serving, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo import get_gpt

    with Phase("serve", log) as info:
        np.random.seed(0)
        mx.random.seed(0)
        net = get_gpt(sz.gpt, vocab_size=sz.vocab, max_length=sz.max_len,
                      dropout=0.0, **dict(sz.gpt_kwargs))
        net.initialize(init="xavier")
        net.cast("bfloat16")

        def session(name):
            return serving.DecodeSession(
                net, max_slots=sz.max_slots, max_len=sz.max_len,
                prefill_buckets=sz.prefill_buckets, donate=True,
                artifact_dir=artifact_dir, name=name,
                max_new_tokens=sz.new_tokens)

        def warm(sess):
            c0, t0 = log.requests(), time.perf_counter()
            sess.warmup()
            return log.requests() - c0, time.perf_counter() - t0

        rs = np.random.RandomState(1)
        prompts = [rs.randint(1, sz.vocab, (n,)).astype(np.int32)
                   for n in sz.prompt_lens]

        def serve_all(sess):
            c0, t0 = log.requests(), time.perf_counter()
            handles = [sess.submit(p, max_new_tokens=sz.new_tokens)
                       for p in prompts]
            streams = [h.result(300) for h in handles]
            dt = time.perf_counter() - t0
            check(log.requests() == c0,
                  f"{log.requests() - c0} compiles after warmup")
            for p, s in zip(prompts, streams):
                check(len(s) == sz.new_tokens,
                      f"prompt of {len(p)}: {len(s)} tokens, want "
                      f"{sz.new_tokens}")
            return streams, dt

        first = session("smoke_gpt")
        try:
            cold_compiles, cold_s = warm(first)
            streams, serve_s = serve_all(first)
            check(first.drain(60), "drain timed out")
            stats = first.stats()
        finally:
            first.close()

        # one request against the full-sequence forward: a single causal
        # pass over prompt + stream gives the oracle's logits at every
        # generated position (teacher-forced on the stream itself)
        i = int(np.argmax([len(p) for p in prompts]))
        seq = np.concatenate([prompts[i], np.asarray(streams[i], np.int32)])
        full = net(mx.nd.array(seq[None, :-1], dtype="int32")).asnumpy()
        full = np.asarray(full[0], np.float32)
        n = len(prompts[i])
        pre = net.prefill(mx.nd.array(prompts[i][None], dtype="int32"))[0]
        pre_last = np.asarray(pre.asnumpy()[0, n - 1], np.float32)
        first_err = float(np.abs(pre_last - full[n - 1]).max())
        check(first_err <= TOL_LOGIT,
              f"prefill logits differ from the full forward by "
              f"{first_err:.4f} > {TOL_LOGIT}")
        exact, worst = 0, 0.0
        for t, tok in enumerate(streams[i]):
            row = full[n - 1 + t]
            gap = float(row.max() - row[tok])
            worst = max(worst, gap)
            exact += int(tok == int(np.argmax(row)))
        check(worst <= TOL_LOGIT,
              f"a streamed token scores {worst:.4f} logits under the full "
              f"forward's best (> {TOL_LOGIT})")

        # a second session on the same artifact directory warms by
        # deserializing: no compile, no cache hit
        second = session("smoke_gpt")
        try:
            warm_compiles, warm_s = warm(second)
            check(warm_compiles == 0,
                  f"second session compiled {warm_compiles} executables")
            again, _ = serve_all(second)
            check(again[i] == streams[i],
                  "the reloaded executables stream different tokens")
            check(second.drain(60), "drain timed out")
            loaded = (second.stats()["prefill_cache"]["artifact_hits"]
                      + second.stats()["engine_cache"]["artifact_hits"])
        finally:
            second.close()
        wd = telemetry.get_watchdog()
        check(not wd.flagged(), f"recompile watchdog: {wd.flagged()}")
        info.update(
            model=sz.gpt, vocab=sz.vocab, max_len=sz.max_len,
            max_slots=sz.max_slots, dtype="bfloat16", donate=True,
            prefill_buckets=list(sz.prefill_buckets),
            prompt_lens=list(sz.prompt_lens), new_tokens=sz.new_tokens,
            requests=len(prompts), tokens=stats["tokens"],
            warmup_compiles=cold_compiles, warmup_s=round(cold_s, 2),
            serve_s=round(serve_s, 2), tolerance_logits=TOL_LOGIT,
            prefill_logit_err=round(first_err, 4),
            stream_exact=f"{exact}/{len(streams[i])}",
            stream_worst_gap=round(worst, 4),
            second_warmup_compiles=warm_compiles,
            second_warmup_s=round(warm_s, 2),
            second_deserialized=loaded, artifact_dir=artifact_dir)


# ---------------------------------------------------------------------------
# --four-chips: BERT-base, ZeRO-3 and tensor parallel, against one device
# ---------------------------------------------------------------------------
def _bert_tp_rules():
    """The tensor-parallel rules the repo uses for BERT
    (``__graft_entry__.py``)."""
    from jax.sharding import PartitionSpec as P

    return {
        r"ffn1\.weight": P("model", None),    # column parallel
        r"ffn2\.weight": P(None, "model"),    # row parallel
        r"(query|key|value)\.weight": P("model", None),
        r"proj\.weight": P(None, "model"),
        r"word_embed": P(None, "model"),      # embedding sharded on units
    }


def _bert_trainer(sz: Sizes, mesh, zero_stage, tp: bool):
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, models, parallel

    np.random.seed(0)
    mx.random.seed(0)
    t = sz.bert_seq
    net = models.get_bert(sz.bert, vocab_size=sz.bert_vocab, dropout=0.0,
                          max_length=512, **dict(sz.bert_kwargs))
    net.initialize(init="xavier")
    net.cast("bfloat16")
    net(mx.nd.zeros((2, t), dtype="int32"),
        mx.nd.zeros((2, t), dtype="int32"),
        mx.nd.array(np.full((2,), t), dtype="int32"))
    if tp:
        parallel.shard_params(net, _bert_tp_rules())
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def pretrain_loss(seq_out, pooled, mlm_scores, nsp_scores,
                      mlm_label, nsp_label):
        return ce(mlm_scores, mlm_label).mean() + \
            ce(nsp_scores, nsp_label).mean()

    return parallel.SPMDTrainer(
        net, pretrain_loss, "sgd", {"learning_rate": 1e-4, "momentum": 0.9},
        mesh=mesh, zero_stage=zero_stage)


def _bert_batches(sz: Sizes):
    import numpy as np

    b, t, v = sz.bert_batch, sz.bert_seq, sz.bert_vocab
    out = []
    for i in range(sz.bert_steps):
        rs = np.random.RandomState(i)
        out.append(([rs.randint(0, v, (b, t)).astype(np.int32),
                     np.zeros((b, t), np.int32),
                     np.full((b,), t, np.int32)],
                    [rs.randint(0, v, (b, t)).astype(np.float32),
                     rs.randint(0, 2, (b,)).astype(np.float32)]))
    return out


def _per_device_bytes(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def _run_layout(sz, mesh, zero_stage, tp, batches):
    import jax
    import numpy as np

    trainer = _bert_trainer(sz, mesh, zero_stage, tp)
    losses, times = [], []
    for data, labels in batches:
        t0 = time.perf_counter()
        loss = jax.block_until_ready(trainer.step(data, labels))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    return trainer, losses, times


def phase_four_chips(sz: Sizes, log: CompileLog) -> None:
    import jax
    import numpy as np

    from incubator_mxnet_tpu import parallel

    with Phase("four_chips", log) as info:
        devices = jax.devices()
        check(len(devices) == 4, f"need 4 devices, have {len(devices)}")
        batches = _bert_batches(sz)

        one = parallel.make_mesh({"data": 1}, devices=devices[:1])
        ref_tr, ref_losses, ref_t = _run_layout(sz, one, 0, False, batches)
        replicated = sum(_per_device_bytes(ref_tr.params).values())
        replicated_opt = sum(_per_device_bytes(ref_tr.opt_state).values())
        del ref_tr
        info.update(model=sz.bert, seq=sz.bert_seq, batch=sz.bert_batch,
                    steps=sz.bert_steps, tolerance_loss=TOL_LOSS,
                    one_device={"losses": [round(l, 4) for l in ref_losses],
                                "param_bytes": replicated,
                                "step_ms": round(1e3 * ref_t[-1], 1)})

        layouts = (("zero3_data4", {"data": 4}, False),
                   ("zero3_data2_model2", {"data": 2, "model": 2}, True))
        for name, axes, tp in layouts:
            mesh = parallel.make_mesh(axes)
            check(len({d.id for d in mesh.devices.flat}) == 4,
                  f"mesh {axes} does not span four devices")
            tr, losses, times = _run_layout(sz, mesh, 3, tp, batches)
            for i, (got, want) in enumerate(zip(losses, ref_losses)):
                check(abs(got - want) <= TOL_LOSS * abs(want),
                      f"{name} step {i}: loss {got} vs one-device {want}")
            pbytes = _per_device_bytes(tr.params)
            obytes = _per_device_bytes(tr.opt_state)
            check(sorted(pbytes) == sorted(d.id for d in devices)
                  and sorted(obytes) == sorted(d.id for d in devices),
                  f"{name}: shards on devices {sorted(pbytes)} / "
                  f"{sorted(obytes)}, want all four")
            share = max(pbytes.values()) / replicated
            oshare = max(obytes.values()) / max(replicated_opt, 1)
            # ZeRO-3 shards a tensor over the data axis where its leading
            # dim divides by it (tensor-parallel leaves are split over
            # the model axis instead); the rest stays whole. A tensor
            # may stay whole only for that reason. At BERT-base's vocab
            # of 30522 the embedding and the MLM decoder do.
            n_data = axes["data"]
            whole = []
            for pn, arr in tr.params.items():
                frac = max(sh.data.nbytes for sh in arr.addressable_shards) \
                    / max(arr.nbytes, 1)
                if frac <= 0.5 if tp else frac <= 0.25:
                    continue
                check(arr.shape[0] % n_data != 0,
                      f"{name}: {pn} {arr.shape} holds {frac:.2f} of its "
                      "bytes per device though ZeRO-3 could shard it")
                if arr.nbytes > (1 << 20):
                    whole.append(pn)
            check(oshare <= share + 0.05,
                  f"{name}: a device holds {share:.3f} of the parameter "
                  f"bytes and {oshare:.3f} of the optimizer bytes")
            text = tr.step_hlo_text(*batches[0])
            check(text is not None, f"{name}: no compiled step text")
            counts = {op: text.count(op + "(") + text.count(op + "-start(")
                      for op in ("all-gather", "reduce-scatter",
                                 "all-reduce", "all-to-all",
                                 "collective-permute")}
            # ZeRO-3 gathers parameters and reduces gradients to their
            # shards (a reduce-scatter; a backend without one lowers it
            # to all-reduce + slice); tensor parallel adds all-reduces
            check(counts["all-gather"] > 0
                  and counts["reduce-scatter"] + counts["all-reduce"] > 0
                  and (not tp or counts["all-reduce"] > 0),
                  f"{name}: collectives in the compiled step: {counts}")
            info[name] = {
                "losses": [round(l, 4) for l in losses],
                "max_rel_loss_err": round(max(
                    abs(g - w) / abs(w)
                    for g, w in zip(losses, ref_losses)), 5),
                "param_share_per_device": round(share, 4),
                "opt_share_per_device": round(oshare, 4),
                "params_left_whole": whole,
                "collectives": {k: v for k, v in counts.items() if v},
                "first_step_s": round(times[0], 2),
                "step_ms": round(1e3 * float(np.median(times[1:])), 1)}
            del tr


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip phase (needs 4 chips)")
    args = ap.parse_args(argv)

    from incubator_mxnet_tpu import runtime

    cache_dir = runtime.enable_compile_cache()
    log = CompileLog()
    sz = Sizes()
    t0 = time.perf_counter()
    if args.four_chips:
        device = phase_device(cache_dir, want_count=4)
        phase_four_chips(sz, log)
    else:
        device = phase_device(cache_dir)
        phase_kernels(sz, log)
        phase_train(sz, log)
        phase_serve(sz, log,
                    os.path.join(HERE, ".serving_artifacts", "chip_smoke"))
    emit({"phase": "total", "seconds": round(time.perf_counter() - t0, 2),
          "compiles": log.compiles, "cache_hits": log.cache_hits})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
