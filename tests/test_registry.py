"""Registry + persistent-artifact tests (ISSUE 14).

The contracts pinned here: an artifact-warmed replica performs ZERO
post-load XLA compiles under the armed recompile watchdog and serves
bit-identical outputs; a stale-fingerprint artifact (wrong
jaxlib/backend/topology/model fingerprint) is REFUSED and falls back to
compile-and-repersist, never deserialized; the registry serves N models
(incl. a ``DecodeSession``) within one stated device-memory budget with
LRU eviction of idle models only (in-flight models are never evicted;
evicted models re-admit from artifacts with zero recompiles); and a
live weight hot-swap under concurrent traffic is atomic — every batch
and every decode step sees exactly the old or the new weights, never a
mix, with zero dropped requests and zero recompiles.
"""

import os
import pickle
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.config import config
from incubator_mxnet_tpu.gluon.model_zoo import get_gpt
from incubator_mxnet_tpu.parallel.spmd import collect_params
from incubator_mxnet_tpu.serving.artifacts import ArtifactStore

VOCAB = 37


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()
    for k in ("MXTPU_SERVING_ARTIFACT_DIR", "MXTPU_REGISTRY_BUDGET_MB",
              "MXTPU_REGISTRY_MAX_RESIDENT",
              "MXTPU_SERVING_WARMUP_THREADS"):
        config.unset(k)


def _dense(out=3, inp=4, seed=0):
    np.random.seed(seed)
    net = mx.gluon.nn.Dense(out, in_units=inp)
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
    return net


def _weights_of(net):
    return {k: p.data().asnumpy() for k, p in collect_params(net).items()}


def _tiny_gpt(seed=0, max_length=32, units=16, layers=2):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, units=units,
                  num_layers=layers, max_length=max_length, dropout=0.0)
    net.initialize(init="xavier")
    return net


def _gpt_oracle(net, prompt, n_new):
    seq = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        lg = net(mx.nd.array(np.array(seq)[None], dtype="int32")).asnumpy()
        tok = int(np.argmax(lg[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


# ---------------------------------------------------------------------------
# persistent artifacts: round trip, zero post-load compiles, refusal
# ---------------------------------------------------------------------------
def test_artifact_roundtrip_bit_identical_zero_compiles(tmp_path):
    net = _dense()
    d = str(tmp_path / "art")
    c1 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2, 4), artifact_dir=d)
    c1.warmup((4,), "float32")
    assert c1.metrics.compiles == 2 and c1.metrics.artifact_hits == 0
    x = np.random.RandomState(0).rand(3, 4).astype(np.float32)
    out1 = np.asarray(c1(x))

    # the artifact-warmed replica: every bucket deserializes, nothing
    # compiles, and — the acceptance bar — the armed watchdog sees NO
    # XLA compile at all from load through serving
    wd = telemetry.get_watchdog()
    base = wd.compile_count
    c2 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2, 4), artifact_dir=d)
    c2.warmup((4,), "float32")
    for n in (1, 2, 3, 4, 3, 1):
        np.testing.assert_array_equal(np.asarray(c2(x[:n])), out1[:n])
    assert c2.metrics.compiles == 0
    assert c2.metrics.artifact_hits == 2
    assert c2.metrics.deserialize_seconds > 0.0
    assert wd.compile_count == base, "artifact warmup must not compile"
    assert wd.flagged() == []


def test_artifact_warmup_seconds_and_registry_families(tmp_path):
    net = _dense()
    d = str(tmp_path / "art")
    c1 = serving.BucketedExecutorCache.from_block(
        net, buckets=(1, 2), artifact_dir=d, name="warm")
    c1.warmup((4,), "float32")
    assert c1.metrics.warmup_seconds > 0
    snap = c1.metrics.snapshot()
    assert snap["executor_cache"]["artifact_misses"] == 2
    text = telemetry.prometheus_text(telemetry.get_registry())
    for family in ("mxtpu_serving_artifact_hits_total",
                   "mxtpu_serving_artifact_misses_total",
                   "mxtpu_serving_warmup_seconds"):
        assert family in text


@pytest.mark.parametrize("field", ["jaxlib", "backend", "device_count",
                                   "fingerprint"])
def test_stale_fingerprint_refused_falls_back_to_compile(tmp_path, field):
    """The CI guard: an artifact recorded under a different jaxlib /
    backend / topology / model fingerprint is refused — the cache
    compiles instead and REPERSISTS, after which warm loads work
    again. A wrong-topology executable is never deserialized."""
    net = _dense()
    d = str(tmp_path / "art")
    c1 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2,), artifact_dir=d)
    c1.warmup((4,), "float32")

    # tamper the stored guard the way a version/topology change would
    store = ArtifactStore(d)
    path = store.path_for(c1.name, {"component": "bucket", "bucket": 2,
                                    "features": (4,),
                                    "dtype": "float32"})
    with open(path, "rb") as f:
        rec = pickle.load(f)
    rec["guard"][field] = "something-else"
    with open(path, "wb") as f:
        pickle.dump(rec, f)

    c2 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2,), artifact_dir=d)
    c2.warmup((4,), "float32")
    assert c2.metrics.compiles == 1          # refused -> compiled
    assert c2.metrics.artifact_refused == 1
    assert c2.metrics.artifact_hits == 0

    # compile-and-repersist: the stale artifact was overwritten
    c3 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2,), artifact_dir=d)
    c3.warmup((4,), "float32")
    assert c3.metrics.compiles == 0 and c3.metrics.artifact_hits == 1


def test_corrupt_artifact_falls_back(tmp_path):
    net = _dense()
    d = str(tmp_path / "art")
    c1 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2,), artifact_dir=d)
    c1.warmup((4,), "float32")
    store = ArtifactStore(d)
    path = store.path_for(c1.name, {"component": "bucket", "bucket": 2,
                                    "features": (4,),
                                    "dtype": "float32"})
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    c2 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2,), artifact_dir=d)
    c2.warmup((4,), "float32")
    assert c2.metrics.compiles == 1          # corrupt -> compiled
    x = np.ones((2, 4), np.float32)
    np.testing.assert_array_equal(np.asarray(c2(x)), np.asarray(c1(x)))


def test_parallel_warmup_compiles_every_bucket(tmp_path):
    """Satellite: bucket compiles fan across a thread pool (XLA
    releases the GIL); all signatures land, each compiled exactly
    once."""
    net = _dense(out=6, inp=8)
    cache = serving.BucketedExecutorCache.from_block(
        net, buckets=(1, 2, 4, 8), artifact_dir="")
    cache.warmup((8,), "float32", threads=4)
    assert cache.metrics.compiles == 4
    assert len(cache.compiled_signatures()) == 4
    x = np.random.RandomState(1).rand(5, 8).astype(np.float32)
    ref = net(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(np.asarray(cache(x)), ref,
                               rtol=1e-5, atol=1e-6)


def test_load_artifacts_eager_scan_needs_no_signature(tmp_path):
    net = _dense()
    d = str(tmp_path / "art")
    c1 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2, 4), artifact_dir=d)
    c1.warmup((4,), "float32")
    c2 = serving.BucketedExecutorCache.from_block(
        net, buckets=(2, 4), artifact_dir="")
    assert c2.load_artifacts(d) == 2
    assert len(c2.compiled_signatures()) == 2
    assert c2.metrics.compiles == 0


def test_decode_session_artifact_warm_start_zero_compiles(tmp_path):
    """The full decode executable set (prefill buckets + joins + the
    decode program) persists and warms back with zero compiles; greedy
    streams stay bit-exact vs the oracle."""
    net = _tiny_gpt()
    d = str(tmp_path / "art")
    prompt = np.random.RandomState(5).randint(
        1, VOCAB, (6,)).astype(np.int32)
    want = _gpt_oracle(net, prompt, 5)     # eager compiles, outside the
    s1 = serving.DecodeSession(net, max_slots=2, max_len=32,  # clock
                               prefill_buckets=(8,), artifact_dir=d,
                               name="gpt")
    try:
        s1.warmup()
        assert s1.engine_metrics.compiles == 2      # join + decode
        assert s1._prefill.metrics.compiles == 1
        assert s1.generate(prompt, max_new_tokens=5) == want
    finally:
        s1.close()

    wd = telemetry.get_watchdog()
    base = wd.compile_count
    s2 = serving.DecodeSession(net, max_slots=2, max_len=32,
                               prefill_buckets=(8,), artifact_dir=d,
                               name="gpt")
    try:
        s2.warmup()
        assert s2.engine_metrics.compiles == 0
        assert s2.engine_metrics.artifact_hits == 2
        assert s2._prefill.metrics.artifact_hits == 1
        assert s2.generate(prompt, max_new_tokens=5) == want
        assert wd.compile_count == base
        assert wd.flagged() == []
    finally:
        s2.close()


def test_decode_artifact_guard_covers_cache_shape(tmp_path):
    """A session with a different slot count must NOT deserialize the
    other topology's decode executable (kv_shape rides the guard)."""
    net = _tiny_gpt()
    d = str(tmp_path / "art")
    s1 = serving.DecodeSession(net, max_slots=2, max_len=32,
                               prefill_buckets=(8,), artifact_dir=d,
                               name="gpt")
    try:
        s1.warmup()
    finally:
        s1.close()
    s2 = serving.DecodeSession(net, max_slots=4, max_len=32,
                               prefill_buckets=(8,), artifact_dir=d,
                               name="gpt")
    try:
        s2.warmup()
        assert s2.engine_metrics.compiles == 2      # refused, recompiled
        assert s2.engine_metrics.artifact_hits == 0
    finally:
        s2.close()


@pytest.mark.parametrize("stored", ["absent", 1, 3, 8])
def test_decode_artifact_of_older_program_refused(tmp_path, monkeypatch,
                                                  stored):
    """A decode artifact persisted by an older PROGRAM (the parent
    of PR 26 wrote no ``program`` field; later ones write a lower
    revision, 3 the one whose GPT cache kept a head a row, 8 the one
    whose GPT prefill attended through a dense score tensor) agrees with the guard on compiler, device and shapes. It
    is refused by the field's name and recompiled, never deserialized
    into the session."""
    from jax.experimental import serialize_executable

    net = _tiny_gpt()
    d = str(tmp_path / "art")
    prompt = np.random.RandomState(5).randint(
        1, VOCAB, (6,)).astype(np.int32)
    want = _gpt_oracle(net, prompt, 5)
    kw = dict(max_slots=2, max_len=32, prefill_buckets=(8,),
              artifact_dir=d, name="gpt")
    with serving.DecodeSession(net, **kw) as s1:
        s1.warmup()
        guard = dict(s1._guard)
    assert guard["program"] == serving.decode._PROGRAM_REVISION

    store = ArtifactStore(d)
    path = store.path_for("gpt", {"component": "decode"})
    with open(path, "rb") as f:
        rec = pickle.load(f)
    if stored == "absent":
        del rec["guard"]["program"]
    else:
        rec["guard"]["program"] = stored
    with open(path, "wb") as f:
        pickle.dump(rec, f)
    assert store.load("gpt", {"component": "decode"}, guard) == \
        (None, "refused:program")

    loaded = []
    real = serialize_executable.deserialize_and_load

    def spy(*a, **k):
        ex = real(*a, **k)
        loaded.append(ex)
        return ex

    monkeypatch.setattr(serialize_executable, "deserialize_and_load", spy)
    with serving.DecodeSession(net, **kw) as s2:
        s2.warmup()
        assert s2.engine_metrics.artifact_refused == 1
        assert s2.engine_metrics.compiles == 1          # the decode
        assert s2.engine_metrics.artifact_hits == 1     # the join
        assert all(ex is not s2._dec_ex for ex in loaded)
        assert s2.generate(prompt, max_new_tokens=5) == want
    monkeypatch.undo()
    # compile-and-repersist: the next replica warms from the new one
    ex, reason = store.load("gpt", {"component": "decode"}, guard)
    assert reason == "ok" and ex is not None


# ---------------------------------------------------------------------------
# live weight hot-swap
# ---------------------------------------------------------------------------
def test_hot_swap_atomic_under_concurrent_predict():
    """Concurrent predict traffic across a publish_weights flip: every
    answer equals EXACTLY the old or the new model's output (never a
    mix of versions inside one forward), nothing drops, nothing
    recompiles, and unchanged params alias the resident device buffer
    zero-copy."""
    net_a = _dense(out=3, inp=4, seed=0)
    net_b = _dense(out=3, inp=4, seed=1)
    new = _weights_of(net_b)
    new["bias"] = _weights_of(net_a)["bias"]     # identical -> aliased
    srv = serving.ModelServer(net_a, buckets=(1, 2, 4), max_wait_ms=0.5,
                              name="swap", artifact_dir="")
    try:
        srv.warmup((4,), "float32")
        x = np.random.RandomState(2).rand(4).astype(np.float32)
        out_a = np.asarray(srv.predict(x))
        net_b.bias.set_data(net_a.bias.data())
        out_b = net_b(mx.nd.array(x[None])).asnumpy()[0]
        assert not np.allclose(out_a, out_b)

        wd = telemetry.get_watchdog()
        base = wd.compile_count
        i_bias = srv._cache.param_names.index("bias")
        old_bias = srv._cache._params[i_bias]
        results, errors = [], []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    results.append(np.asarray(srv.predict(x, timeout=10)))
                except Exception as e:   # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        stats = srv.publish_weights(new, version="v2")
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(10)

        assert not errors, f"hot swap dropped requests: {errors[:3]}"
        assert results
        n_a = n_b = 0
        for r in results:
            if np.array_equal(r, out_a):
                n_a += 1
            else:
                np.testing.assert_allclose(r, out_b, rtol=1e-6,
                                           atol=1e-7)
                n_b += 1
        assert n_b > 0, "no request saw the new version"
        assert stats["aliased"] >= 1 and stats["updated"] >= 1
        assert srv._cache._params[i_bias] is old_bias   # zero-copy
        assert srv.weights_version == "v2"
        assert wd.compile_count == base, "a weight swap must not compile"
        assert srv.healthz()["ready"]
    finally:
        srv.close()


def test_hot_swap_rejects_architecture_changes():
    srv = serving.ModelServer(_dense(), buckets=(1,), artifact_dir="")
    try:
        srv.warmup((4,), "float32")
        with pytest.raises(ValueError, match="signature-frozen"):
            srv.publish_weights({"weight": np.zeros((7, 9), np.float32)})
        with pytest.raises(ValueError, match="unknown parameter"):
            srv.publish_weights({"nope": np.zeros((3, 4), np.float32)})
    finally:
        srv.close()


def test_hot_swap_from_sharded_checkpoint(tmp_path):
    """publish_weights ingests a sharded training checkpoint prefix
    through the PR 7 slice reader — only the served tensors are read,
    optimizer state never touched."""
    from incubator_mxnet_tpu import parallel

    net_a = _dense(out=3, inp=4, seed=0)
    net_b = _dense(out=3, inp=4, seed=3)
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        net_b, lambda y, t: ((y - t) ** 2).mean(), "sgd",
        {"learning_rate": 0.0}, mesh=mesh)
    prefix = str(tmp_path / "ckpt" / "step0")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    parallel.save_sharded(prefix, trainer)

    srv = serving.ModelServer(net_a, buckets=(1,), artifact_dir="")
    try:
        srv.warmup((4,), "float32")
        stats = srv.publish_weights(prefix, version=7)
        assert stats["version"] == 7
        x = np.random.RandomState(4).rand(4).astype(np.float32)
        ref = net_b(mx.nd.array(x[None])).asnumpy()[0]
        np.testing.assert_allclose(np.asarray(srv.predict(x)), ref,
                                   rtol=1e-5, atol=1e-6)
    finally:
        srv.close()


def test_decode_hot_swap_per_version_streams():
    """Streams fully served before the flip match the old oracle;
    streams admitted after it match the new oracle; an in-flight
    request across the flip completes without error (its suffix runs
    under the new weights over the old KV cache — each step exactly
    one version)."""
    net_a = _tiny_gpt(seed=0)
    net_b = _tiny_gpt(seed=1)
    prompt = np.random.RandomState(6).randint(
        1, VOCAB, (5,)).astype(np.int32)
    sess = serving.DecodeSession(net_a, max_slots=2, max_len=32,
                                 prefill_buckets=(8,), name="hs",
                                 artifact_dir="")
    try:
        sess.warmup()
        assert sess.generate(prompt, max_new_tokens=4) \
            == _gpt_oracle(net_a, prompt, 4)

        # in-flight sequence spanning the flip: must finish, not drop
        h = sess.submit(prompt, max_new_tokens=12)
        first = next(iter(h))
        assert first == _gpt_oracle(net_a, prompt, 1)[0]
        stats = sess.publish_weights(_weights_of(net_b), version=2)
        assert stats["version"] == 2
        full = h.result(60)
        assert len(full) == 12 and full[0] == first

        # post-flip admissions are pure new-version streams
        assert sess.generate(prompt, max_new_tokens=4) \
            == _gpt_oracle(net_b, prompt, 4)
        assert sess.weights_version == 2
        assert sess.healthz()["ready"]
    finally:
        sess.close()


# ---------------------------------------------------------------------------
# the model registry
# ---------------------------------------------------------------------------
def _register_three(reg, net_a, net_b, gpt):
    reg.register("a", lambda ad: serving.ModelServer(
        net_a, buckets=(1, 2), artifact_dir=ad, name="a"),
        warmup=lambda s: s.warmup((4,), "float32"))
    reg.register("b", lambda ad: serving.ModelServer(
        net_b, buckets=(1, 2), artifact_dir=ad, name="b"),
        warmup=lambda s: s.warmup((4,), "float32"))
    reg.register("gpt", lambda ad: serving.DecodeSession(
        gpt, max_slots=2, max_len=32, prefill_buckets=(8,),
        artifact_dir=ad, name="gpt"),
        kind="decode", warmup=lambda s: s.warmup())


def test_registry_serves_three_models_within_budget_with_lru(tmp_path):
    """The acceptance scenario: >= 3 models (incl. a DecodeSession)
    behind one front door and one stated budget; using a third model
    evicts the LRU idle one; the evicted model re-admits FROM ARTIFACTS
    with zero recompiles and identical outputs."""
    net_a, net_b, gpt = _dense(seed=0), _dense(seed=1), _tiny_gpt()
    d = str(tmp_path / "art")
    x = np.random.RandomState(7).rand(4).astype(np.float32)
    prompt = np.random.RandomState(8).randint(
        1, VOCAB, (5,)).astype(np.int32)

    # measure real footprints with no budget, then state one that fits
    # the decode session + one dense model only
    with serving.ModelRegistry(artifact_dir=d, name="probe") as reg:
        _register_three(reg, net_a, net_b, gpt)
        out_a = np.asarray(reg.predict("a", x))
        out_b = np.asarray(reg.predict("b", x))
        toks = reg.generate("gpt", prompt, max_new_tokens=3)
        assert toks == _gpt_oracle(gpt, prompt, 3)
        sizes = {n: e.bytes for n, e in reg._entries.items()}
    budget = sizes["gpt"] + sizes["a"] + sizes["b"] // 2

    reg = serving.ModelRegistry(budget_bytes=budget, artifact_dir=d,
                                name="lru")
    try:
        _register_three(reg, net_a, net_b, gpt)
        np.testing.assert_array_equal(np.asarray(reg.predict("a", x)),
                                      out_a)
        assert reg.generate("gpt", prompt, max_new_tokens=3) == toks
        assert sorted(reg.resident_models()) == ["a", "gpt"]
        assert reg.resident_bytes() <= budget

        # admitting b must evict the LRU idle model (a), not gpt (MRU)
        np.testing.assert_array_equal(np.asarray(reg.predict("b", x)),
                                      out_b)
        assert sorted(reg.resident_models()) == ["b", "gpt"]
        assert reg.metrics.evictions == 1
        assert reg.resident_bytes() <= budget

        # re-admission warms from artifacts: zero compiles
        wd = telemetry.get_watchdog()
        base = wd.compile_count
        np.testing.assert_array_equal(np.asarray(reg.predict("a", x)),
                                      out_a)
        srv_a = reg.server("a")
        assert srv_a.metrics.compiles == 0
        assert srv_a.metrics.artifact_hits == 2
        assert wd.compile_count == base
        assert reg.metrics.admissions >= 4
        h = reg.healthz()
        assert h["ready"] and h["budget_bytes"] == budget
    finally:
        reg.close()


def test_registry_never_evicts_in_flight_model(tmp_path):
    """With every resident model in flight and no room, admission
    raises QueueFullError(retry_after) instead of evicting under a
    live request; the in-flight model finishes untouched."""
    net_a, net_b, gpt = _dense(seed=0), _dense(seed=1), _tiny_gpt()
    d = str(tmp_path / "art")
    reg = serving.ModelRegistry(max_resident=1, artifact_dir=d,
                                name="inflight")
    try:
        _register_three(reg, net_a, net_b, gpt)
        prompt = np.random.RandomState(9).randint(
            1, VOCAB, (5,)).astype(np.int32)
        h = reg.submit("gpt", prompt, max_new_tokens=20)
        # the decode session is mid-generation: in flight
        next(iter(h))
        with pytest.raises(serving.QueueFullError) as ei:
            reg.predict("a", np.zeros(4, np.float32), timeout=5)
        assert ei.value.retry_after > 0
        assert reg.resident_models() == ["gpt"]
        assert len(h.result(120)) == 20          # finished untouched
        # once idle, the eviction goes through
        _ = np.asarray(reg.predict("a", np.zeros(4, np.float32)))
        assert reg.resident_models() == ["a"]
    finally:
        reg.close()


def test_registry_slo_admission_control(tmp_path):
    """Per-model deadline: a request whose estimated wait already
    exceeds it is rejected at the front door (layered above in-queue
    shedding) and counted."""
    net = _dense()
    reg = serving.ModelRegistry(artifact_dir=str(tmp_path / "a"),
                                name="slo")
    try:
        gate = threading.Event()

        def slow_build(ad):
            srv = serving.ModelServer(net, buckets=(1,), max_wait_ms=0.1,
                                      max_queue=64, artifact_dir=ad,
                                      name="slow")
            srv.warmup((4,), "float32")
            inner = srv._batcher._runner

            def blocked(batch):
                gate.wait(10)
                return inner(batch)

            srv._batcher._runner = blocked
            return srv

        reg.register("slow", slow_build, deadline_ms=1.0)
        x = np.zeros(4, np.float32)
        # pile a backlog behind the gated runner until the front door's
        # wait estimate exceeds the 1 ms deadline and it rejects
        rejected = None
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and rejected is None:
            try:
                reg.submit("slow", x)
            except serving.DeadlineExceededError as e:
                rejected = e
            except serving.QueueFullError:
                break
            time.sleep(0.005)
        assert rejected is not None and rejected.retry_after > 0
        assert reg.metrics.slo_rejections >= 1
        gate.set()
    finally:
        gate.set()
        reg.close()


def test_registry_publish_weights_resident_and_deferred(tmp_path):
    net_a, net_b = _dense(seed=0), _dense(seed=5)
    x = np.random.RandomState(1).rand(4).astype(np.float32)
    ref_b = net_b(mx.nd.array(x[None])).asnumpy()[0]
    reg = serving.ModelRegistry(artifact_dir=str(tmp_path / "a"),
                                name="pub")
    try:
        reg.register("m", lambda ad: serving.ModelServer(
            net_a, buckets=(1,), artifact_dir=ad, name="m"),
            warmup=lambda s: s.warmup((4,), "float32"))
        # deferred: published before the first admission, applied on it
        res = reg.publish_weights("m", _weights_of(net_b), version=3)
        assert res.get("deferred")
        np.testing.assert_allclose(np.asarray(reg.predict("m", x)),
                                   ref_b, rtol=1e-6, atol=1e-7)
        assert reg.server("m").weights_version == 3
        # resident: flips live
        ref_a = net_a(mx.nd.array(x[None])).asnumpy()[0]
        stats = reg.publish_weights("m", _weights_of(net_a), version=4)
        assert stats["version"] == 4 and not stats.get("deferred")
        np.testing.assert_allclose(np.asarray(reg.predict("m", x)),
                                   ref_a, rtol=1e-6, atol=1e-7)
        assert reg.metrics.swaps >= 2
    finally:
        reg.close()


def test_hot_swap_under_open_loop_load_zero_drops(tmp_path):
    """The acceptance scenario: a live hot swap under sustained
    open-loop (Poisson) traffic completes with zero dropped requests
    and zero recompiles."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serving_bench", os.path.join(os.path.dirname(__file__), "..",
                                      "benchmark", "serving_bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    net = _dense(out=4, inp=8, seed=0)
    net_b = _dense(out=4, inp=8, seed=1)
    xs = np.random.RandomState(0).rand(64, 8).astype(np.float32)
    srv = serving.ModelServer(net, buckets=(1, 2, 4, 8), max_wait_ms=1.0,
                              max_queue=64, name="ol", artifact_dir="")
    try:
        srv.warmup((8,), "float32")
        wd = telemetry.get_watchdog()
        base = wd.compile_count
        swap_stats = {}

        def flip():
            time.sleep(0.6)
            swap_stats.update(srv.publish_weights(_weights_of(net_b)))

        t = threading.Thread(target=flip, daemon=True)
        t.start()
        res = bench.open_loop(lambda i: srv.submit(xs[i % len(xs)]),
                              rate_rps=60.0, duration_s=1.5)
        t.join(10)
        assert res["errors"] == 0 and res["rejected"] == 0 \
            and res["shed"] == 0
        assert res["completed"] == res["offered"] > 0
        assert swap_stats.get("updated", 0) >= 1
        assert wd.compile_count == base
        assert wd.flagged() == []
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# knobs, JSONL records, report surface
# ---------------------------------------------------------------------------
def test_artifact_dir_knob_engages_store(tmp_path):
    d = str(tmp_path / "knob")
    config.set("MXTPU_SERVING_ARTIFACT_DIR", d)
    try:
        net = _dense()
        c1 = serving.BucketedExecutorCache.from_block(net, buckets=(2,))
        c1.warmup((4,), "float32")
        c2 = serving.BucketedExecutorCache.from_block(net, buckets=(2,))
        c2.warmup((4,), "float32")
        assert c2.metrics.compiles == 0
        assert c2.metrics.artifact_hits == 1
    finally:
        config.unset("MXTPU_SERVING_ARTIFACT_DIR")


def test_registry_jsonl_records_and_report(tmp_path):
    """The registry lifecycle lands in the JSONL sink as
    ``kind:"registry"`` records; telemetry_report prints a registry
    section and exposes registry/<model>/* compare keys."""
    import importlib.util

    jsonl = str(tmp_path / "run.jsonl")
    telemetry.set_jsonl(jsonl)
    net_a, net_b = _dense(seed=0), _dense(seed=1)
    d = str(tmp_path / "art")
    reg = serving.ModelRegistry(max_resident=1, artifact_dir=d,
                                name="rep")
    try:
        reg.register("a", lambda ad: serving.ModelServer(
            net_a, buckets=(1,), artifact_dir=ad, name="a"),
            warmup=lambda s: s.warmup((4,), "float32"))
        reg.register("b", lambda ad: serving.ModelServer(
            net_b, buckets=(1,), artifact_dir=ad, name="b"),
            warmup=lambda s: s.warmup((4,), "float32"))
        x = np.zeros(4, np.float32)
        reg.predict("a", x)
        reg.predict("b", x)                      # evicts a
        reg.publish_weights("b", _weights_of(net_a), version=2)
    finally:
        reg.close()
        telemetry.set_jsonl(None)

    records = telemetry.read_jsonl(jsonl)
    events = {(r.get("model"), r.get("event")) for r in records
              if r.get("kind") == "registry"}
    assert ("a", "warmup") in events and ("a", "admit") in events
    assert ("a", "evict") in events and ("b", "swap") in events

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    text = report.summarize(jsonl)
    assert "registry" in text and "deser" in text
    keys = report._comparable_metrics(records)
    assert "registry/a/warmup_s" in keys
    assert "registry/a/evictions" in keys
    assert keys["registry/b/swaps"] == 1.0
    assert "registry/a/warmup_compiles" in keys


def test_registry_knobs_registered_and_documented():
    from incubator_mxnet_tpu.config import config as cfg

    for knob in ("MXTPU_SERVING_ARTIFACT_DIR",
                 "MXTPU_SERVING_WARMUP_THREADS",
                 "MXTPU_REGISTRY_BUDGET_MB",
                 "MXTPU_REGISTRY_MAX_RESIDENT"):
        assert knob in cfg._knobs, f"{knob} not registered"
    # docs/ENV_VARS.md sync is pinned by test_tooling.py; spot-check the
    # committed file mentions the new family
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "ENV_VARS.md")) as f:
        doc = f.read()
    assert "MXTPU_SERVING_ARTIFACT_DIR" in doc
    assert "MXTPU_REGISTRY_BUDGET_MB" in doc


def test_lone_over_budget_model_still_serves(tmp_path):
    """Review fix: the post-build budget re-check must never evict the
    just-admitted model itself — a lone model larger than the budget
    serves (warned, best-effort) instead of crashing on a nulled
    server."""
    net = _dense()
    reg = serving.ModelRegistry(budget_bytes=1,   # smaller than anything
                                artifact_dir=str(tmp_path / "a"),
                                name="tiny")
    try:
        reg.register("m", lambda ad: serving.ModelServer(
            net, buckets=(1,), artifact_dir=ad, name="m"),
            warmup=lambda s: s.warmup((4,), "float32"))
        x = np.zeros(4, np.float32)
        out = np.asarray(reg.predict("m", x))       # must not crash
        assert out.shape == (3,)
        assert reg.resident_models() == ["m"]
    finally:
        reg.close()


def test_published_version_survives_eviction(tmp_path):
    """Review fix: weights published to a RESIDENT model must survive
    its eviction — re-admission re-applies the latest publish instead
    of silently reverting to build_fn's original weights."""
    net_a, net_b, extra = _dense(seed=0), _dense(seed=6), _dense(seed=7)
    x = np.random.RandomState(2).rand(4).astype(np.float32)
    ref_b = net_b(mx.nd.array(x[None])).asnumpy()[0]
    reg = serving.ModelRegistry(max_resident=1,
                                artifact_dir=str(tmp_path / "a"),
                                name="surv")
    try:
        reg.register("m", lambda ad: serving.ModelServer(
            net_a, buckets=(1,), artifact_dir=ad, name="m"),
            warmup=lambda s: s.warmup((4,), "float32"))
        reg.register("other", lambda ad: serving.ModelServer(
            extra, buckets=(1,), artifact_dir=ad, name="other"),
            warmup=lambda s: s.warmup((4,), "float32"))
        reg.predict("m", x)
        stats = reg.publish_weights("m", _weights_of(net_b), version=2)
        assert not stats.get("deferred")
        reg.predict("other", x)                  # evicts m (resident=1)
        assert reg.resident_models() == ["other"]
        # re-admission must serve v2, not build_fn's original weights
        np.testing.assert_allclose(np.asarray(reg.predict("m", x)),
                                   ref_b, rtol=1e-6, atol=1e-7)
        assert reg.server("m").weights_version == 2
    finally:
        reg.close()


def test_zero_match_checkpoint_publish_refused(tmp_path):
    """Review fix: a checkpoint path whose tensors match NONE of the
    served parameter names must raise, not silently bump the version
    while old weights keep serving."""
    from incubator_mxnet_tpu import parallel

    class Other(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.odd = mx.gluon.nn.Dense(2, in_units=3)

        def hybrid_forward(self, F, x):
            return self.odd(x)

    other = Other()
    other.initialize()
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        other, lambda y, t: ((y - t) ** 2).mean(), "sgd",
        {"learning_rate": 0.0}, mesh=mesh)
    prefix = str(tmp_path / "ckpt" / "other")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    parallel.save_sharded(prefix, trainer)

    srv = serving.ModelServer(_dense(), buckets=(1,), artifact_dir="")
    try:
        srv.warmup((4,), "float32")
        with pytest.raises(ValueError, match="no tensors matching"):
            srv.publish_weights(prefix)
        assert srv.weights_version == 0      # nothing committed
    finally:
        srv.close()
