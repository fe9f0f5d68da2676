"""Decode serving tests (ISSUE 12): KV-cache continuous batching.

The contracts pinned here: greedy decode through the slot cache is
bit-exact against the full-sequence forward oracle across join/leave
churn; steady-state decode over mixed-age sequences performs ZERO
post-warmup compiles under the armed recompile watchdog; the front door
preserves the serving-tier semantics (backpressure, deadline shedding,
drain/healthz); and one decoder config covers
train (SuperStep + ZeRO-2) -> sharded checkpoint -> ``from_checkpoint``
-> decode end-to-end."""

import os
import threading
import time

import jax
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel, serving, telemetry
from incubator_mxnet_tpu.config import config
from incubator_mxnet_tpu.gluon.model_zoo import get_gpt

VOCAB = 61


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()
    for k in ("MXTPU_DECODE_SLOTS", "MXTPU_DECODE_MAX_LEN",
              "MXTPU_DECODE_BUCKETS", "MXTPU_DECODE_MAX_NEW_TOKENS"):
        config.unset(k)


def _tiny_net(seed=0, max_length=48, dropout=0.1, units=32, layers=2):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, units=units,
                  num_layers=layers, max_length=max_length,
                  dropout=dropout)
    net.initialize(init="xavier")
    return net


def _oracle(net, prompt, n_new, eos=None):
    """Greedy reference: re-run the full causal forward per token."""
    seq = list(int(t) for t in prompt)
    out = []
    for _ in range(n_new):
        lg = net(mx.nd.array(np.array(seq)[None], dtype="int32")).asnumpy()
        tok = int(np.argmax(lg[0, -1]))
        out.append(tok)
        seq.append(tok)
        if eos is not None and tok == eos:
            break
    return out


def _prompts(ns, seed=7):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (int(n),)).astype(np.int32) for n in ns]


# ---------------------------------------------------------------------------
# the core contract: bit-exact greedy streams across churn
# ---------------------------------------------------------------------------
def test_greedy_bit_exact_across_join_leave_churn():
    net = _tiny_net()
    sess = serving.DecodeSession(net, max_slots=4, max_len=48,
                                 prefill_buckets=(8, 16), name="churn")
    try:
        sess.warmup()
        prompts = _prompts([5, 11, 3, 16, 7, 9, 13, 4])
        news = [6, 9, 4, 7, 12, 5, 8, 10]
        handles = [sess.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, news)]
        got = [h.result(120) for h in handles]
        for i, (p, n, g) in enumerate(zip(prompts, news, got)):
            assert g == _oracle(net, p, n), f"request {i} diverged"
        s = sess.stats()
        # 8 ragged sequences over 4 slots: continuous batching must have
        # overlapped them (occupancy > 1) and every request finished
        assert s["finished"] == len(prompts)
        assert s["mean_step_occupancy"] > 1.0
        assert s["tokens"] == sum(len(g) for g in got)
        assert 0.0 < s["prefill_frac"] < 1.0
        assert sess.drain(60)
    finally:
        sess.close()


def test_streaming_tokens_arrive_per_step():
    net = _tiny_net()
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="stream") as sess:
        sess.warmup()
        h = sess.submit(_prompts([6])[0], max_new_tokens=5)
        streamed = list(h)                     # iterator ends at finish
        assert streamed == h.result(10)
        assert len(streamed) == 5


def test_a_steps_tokens_are_handed_on_under_the_next_step():
    """Between a fence and the next dispatch the scheduler does only what
    the dispatch needs: when a step is dispatched its caller has not yet
    been handed the token of the step before (it is, under this step's
    device time); an admission's prefill hands on under ITS device time,
    so no token waits behind it, and a stream's last token comes before
    its end."""
    net = _tiny_net(max_length=256)   # a stream long enough to be joined
    with serving.DecodeSession(net, max_slots=2, max_len=256,
                               prefill_buckets=(8,), name="handon") as sess:
        sess.warmup()
        seen = {"step": [], "prefill": []}
        step, prefill = sess._dec_ex, sess._prefill

        def stepping(*args):
            seen["step"].append(len(first.tokens))
            return step(*args)

        class Prefilling:
            def __getattr__(self, name):
                return getattr(prefill, name)

            def __call__(self, prompt):
                if seen["step"]:
                    seen["prefill"].append((len(seen["step"]),
                                            len(first.tokens)))
                return prefill(prompt)

        sess._dec_ex, sess._prefill = stepping, Prefilling()
        first = sess.submit(_prompts([6])[0], max_new_tokens=200)
        while len(seen["step"]) < 3:
            time.sleep(0.005)
        second = sess.submit(_prompts([5], seed=3)[0], max_new_tokens=2)
        assert len(first.result(60)) == 200 and len(second.result(60)) == 2
        assert list(first) == first.result(1)
    # the prefill's token, then one a step: the step before's still held
    held = [1 + i - n for i, n in enumerate(seen["step"])]
    assert held[0] == 0 and set(held[1:]) <= {0, 1} and 1 in held
    # held when the prefill is called, handed on before the step after it
    (n_steps, n_tokens), = seen["prefill"]
    assert n_tokens == n_steps and held[n_steps] == 0


def test_step_records_count_the_rows_fetched():
    """Every ``step`` record carries ``kv_read_rows`` beside
    ``kv_live_rows`` and ``kv_rows``: on the dense path (the CPU's) the
    whole plane of every active slot, ``kv_rows``' share of them, and
    never under the live rows."""
    net = _tiny_net()
    slots = 4
    with serving.DecodeSession(net, max_slots=slots, max_len=48,
                               prefill_buckets=(8,), name="fetched") as sess:
        sess.warmup()
        for h in [sess.submit(p, max_new_tokens=m)
                  for p, m in zip(_prompts([6, 3, 8]), (5, 9, 2))]:
            h.result(30)
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.fetched"
             and r.get("kind") != "prefill"]
    assert steps and {r["active"] for r in steps} > {1}
    for r in steps:
        assert r["kv_read_rows"] * slots == r["kv_rows"] * r["active"]
        assert r["kv_live_rows"] <= r["kv_read_rows"]


def test_eos_stops_generation_inclusive():
    net = _tiny_net(seed=3)
    prompt = _prompts([9], seed=3)[0]
    free_run = _oracle(net, prompt, 8)
    eos = free_run[3]                          # force a mid-stream stop
    want = _oracle(net, prompt, 8, eos=eos)
    assert want[-1] == eos and len(want) <= 8
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(16,), name="eos") as sess:
        got = sess.generate(prompt, max_new_tokens=8, eos_id=eos)
    assert got == want


def test_cache_capacity_finishes_and_frees_slot():
    net = _tiny_net()
    max_len = 24
    prompt = _prompts([20])[0]
    with serving.DecodeSession(net, max_slots=1, max_len=max_len,
                               prefill_buckets=(20,), name="cap") as sess:
        sess.warmup()
        got = sess.generate(prompt, max_new_tokens=100)
        # prefill fills 20; steps write at 20..23 -> 4 more writes, and
        # the step that fills the last position still emits its token
        assert len(got) == max_len - len(prompt) + 1
        assert got == _oracle(net, prompt, len(got))
        # the slot came back: a second request is served, not starved
        got2 = sess.generate(_prompts([4])[0], max_new_tokens=3)
        assert len(got2) == 3


# ---------------------------------------------------------------------------
# a second step in flight: launched before the first one's tokens are fetched
# ---------------------------------------------------------------------------
def _step_records(name):
    return [r for r in telemetry.trace.ring()["steps"]
            if r.get("site") == f"decode.{name}"
            and r.get("kind") != "prefill"]


def _count_calls(sess):
    """Wrap the session's executables: ``calls`` gets ``"p"`` a prefill
    and ``"s"`` / ``"a"`` a decode step dispatched with the host's tokens
    / launched ahead (its ``tokens`` the step before's device output)."""
    calls, last_fed = [], [None]
    step, prefill = sess._dec_ex, sess._prefill

    def stepping(*args):
        calls.append("a" if args[-1] is last_fed[0] else "s")
        outs = step(*args)
        last_fed[0] = outs[-1]
        return outs

    class Prefilling:
        def __getattr__(self, name):
            return getattr(prefill, name)

        def __call__(self, prompt):
            calls.append("p")
            return prefill(prompt)

    sess._dec_ex, sess._prefill = stepping, Prefilling()
    return calls


_CHURN = ([5, 11, 3, 9, 7], [20, 26, 16, 22, 18])


@pytest.mark.parametrize("slots,ahead", [(2, True), (6, False)],
                         ids=["full", "one_slot_free"])
def test_a_full_session_launches_the_next_step_ahead(slots, ahead):
    """While every slot stays busy the next step is dispatched before
    this one's tokens are fetched (``ahead`` 1 in its record) and the
    streams are the oracle's bit for bit across joins and leaves; the
    same streams with a slot left free run one step at a time."""
    net = _tiny_net()
    name = f"ahead{slots}"
    prompts = _prompts(_CHURN[0])
    with serving.DecodeSession(net, max_slots=slots, max_len=48,
                               prefill_buckets=(16,), name=name) as sess:
        sess.warmup()
        handles = [sess.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, _CHURN[1])]
        got = [h.result(120) for h in handles]
        stats = sess.stats()
    for i, (p, n, g) in enumerate(zip(prompts, _CHURN[1], got)):
        assert g == _oracle(net, p, n), f"request {i} diverged"
    steps = _step_records(name)
    flags = [r["ahead"] for r in steps]
    assert len(steps) == stats["steps"] and sum(flags) == stats["steps_ahead"]
    assert stats["tokens"] == sum(_CHURN[1]) and stats["tokens_dropped"] == 0
    assert all(r["dropped"] == 0 for r in steps)
    if ahead:
        assert sum(flags) > len(flags) / 2, flags
        # a step's time starts at the fetch before it where that is later
        # than its dispatch: records of steps launched ahead do not overlap
        for a, b in zip(steps, steps[1:]):
            assert b["t0"] >= a["t0"] + a["dur_s"] - 1e-9
    else:
        assert not any(flags)


@pytest.mark.parametrize("slots", [1, 2])
def test_an_eos_found_one_step_late_drops_one_token(slots):
    """A stream's ``eos_id`` is seen when its token arrives; the step
    launched ahead has computed one more token for that slot, which goes
    nowhere and is counted nowhere, and the request that joins the freed
    slot gets its own tokens only."""
    net = _tiny_net(seed=5)
    p_eos, p_long, p_next = _prompts([9, 6, 4], seed=3)
    free_run = _oracle(net, p_eos, 8)
    eos = free_run[4]
    want = _oracle(net, p_eos, 8, eos=eos)
    assert want[-1] == eos and len(want) == 5   # steps 1-4 run before it
    with serving.DecodeSession(net, max_slots=slots, max_len=48,
                               prefill_buckets=(16,),
                               name=f"late{slots}") as sess:
        sess.warmup()
        go, step = threading.Event(), sess._dec_ex

        def gated(*args):       # every request is queued before step 1
            assert go.wait(30)
            return step(*args)

        sess._dec_ex = gated
        h_eos = sess.submit(p_eos, max_new_tokens=8, eos_id=eos)
        h_long = sess.submit(p_long, max_new_tokens=14) if slots == 2 \
            else None
        h_next = sess.submit(p_next, max_new_tokens=5)
        go.set()
        assert h_eos.result(60) == want
        assert h_next.result(60) == _oracle(net, p_next, 5)
        n = len(want) + 5
        if h_long is not None:
            assert h_long.result(60) == _oracle(net, p_long, 14)
            n += 14
        stats = sess.stats()
    assert stats["tokens_dropped"] == 1 and stats["tokens"] == n
    assert sum(r["dropped"] for r in _step_records(f"late{slots}")) == 1


def test_no_step_is_launched_ahead_of_an_admission():
    """A request queued at a full session is prefilled at the first
    boundary after the ending that frees its slot: the step after a
    stream's last (by ``max_new_tokens``) is never launched ahead."""
    net = _tiny_net()
    a, b = _prompts([6, 5])
    with serving.DecodeSession(net, max_slots=1, max_len=48,
                               prefill_buckets=(8,), name="edge") as sess:
        sess.warmup()
        calls = _count_calls(sess)
        h_a = sess.submit(a, max_new_tokens=12)
        h_b = sess.submit(b, max_new_tokens=4)
        assert h_a.result(60) == _oracle(net, a, 12)
        assert h_b.result(60) == _oracle(net, b, 4)
    # the prefill's token and one a step, then the next prefill at once;
    # all but a stream's first step ride ahead of the fetch before them
    assert "".join(calls) == "p" + "s" + "a" * 10 + "p" + "s" + "a" * 2


def test_a_staged_swap_is_flipped_between_two_steps_of_a_full_session():
    """``publish_weights`` staged while a full session decides about its
    next step: none is launched ahead of the flip, so the swap takes
    effect with the very next step (which takes the host's tokens), every
    step runs under one version, later steps are launched ahead again and
    both streams are whole."""
    net_a, net_b = (_tiny_net(seed=s, dropout=0.0, max_length=256)
                    for s in (0, 1))
    weights = {k: p.data().asnumpy()
               for k, p in parallel.spmd.collect_params(net_b).items()}
    with serving.DecodeSession(net_a, max_slots=2, max_len=256,
                               prefill_buckets=(8,), name="flip") as sess:
        sess.warmup()
        seen, last_fed, step = [], [None], sess._dec_ex
        publisher = threading.Thread(
            target=lambda: sess.publish_weights(weights, version=2))
        staged_at = []

        def stepping(*args):
            if not staged_at and sess.active_slots == 2:
                # the first step of the FULL session, dispatched with the
                # host's tokens: the swap is staged before the scheduler
                # decides about the step after it
                staged_at.append(len(seen))
                publisher.start()
                while sess._pending_swap is None:
                    time.sleep(0.001)
            seen.append((id(args[0]), args[-1] is last_fed[0]))
            outs = step(*args)
            last_fed[0] = outs[-1]
            return outs

        sess._dec_ex = stepping
        handles = [sess.submit(p, max_new_tokens=60)
                   for p in _prompts([6, 5])]
        assert [len(h.result(60)) for h in handles] == [60, 60]
        publisher.join(30)
        assert not publisher.is_alive() and sess.weights_version == 2
    (at,) = staged_at
    versions = [v for v, _ in seen]
    assert len(set(versions[:at + 1])) == 1 == len(set(versions[at + 1:]))
    assert versions[at] != versions[at + 1], "a step was launched ahead"
    assert not seen[at][1] and not seen[at + 1][1]
    assert sum(ahead for _, ahead in seen[at + 2:]) > len(seen) // 2


class _Unfetchable:
    """Stands for a step's ``out`` whose copy to the host fails."""

    def copy_to_host_async(self):
        pass

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected fetch failure")


@pytest.mark.parametrize("fault", ["dispatch", "dispatch_ahead", "fetch"])
def test_a_failing_step_fails_its_streams_and_no_others(fault):
    """A step that fails at its dispatch (with the host's tokens, or
    launched ahead with a step in flight before it) or at its fetch (a
    step in flight behind it, which is discarded) fails exactly the
    streams it was launched for; the session serves the next request."""
    net = _tiny_net()
    p1, p2, p3 = _prompts([6, 5, 7])
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8,),
                               name=f"fault_{fault}") as sess:
        sess.warmup()
        go, step, n_calls = threading.Event(), sess._dec_ex, [0]
        k = {"dispatch": 1, "dispatch_ahead": 4, "fetch": 3}[fault]

        def failing(*args):
            assert go.wait(30)
            n_calls[0] += 1
            if n_calls[0] != k:
                return step(*args)
            if fault == "fetch":
                return (_Unfetchable(), *step(*args)[1:])
            raise RuntimeError("injected dispatch failure")

        sess._dec_ex = failing
        doomed = [sess.submit(p, max_new_tokens=30) for p in (p1, p2)]
        go.set()
        for h in doomed:
            with pytest.raises(RuntimeError, match="injected"):
                h.result(60)
        assert sess.generate(p3, max_new_tokens=6) == _oracle(net, p3, 6)
        assert sess.healthz()["ready"]
        assert sess.stats()["finished"] == 1


def test_close_underneath_two_steps_in_flight_returns():
    net = _tiny_net(max_length=256)
    with serving.DecodeSession(net, max_slots=2, max_len=256,
                               prefill_buckets=(8,), name="closing") as sess:
        sess.warmup()
        step, n_calls, closer = sess._dec_ex, [0], []

        def stepping(*args):
            n_calls[0] += 1
            if n_calls[0] == 6:     # launched ahead: step 5 is in flight
                closer.append(threading.Thread(target=sess.close))
                closer[0].start()
                while sess.healthz()["state"] != "closed":
                    time.sleep(0.001)
            return step(*args)

        sess._dec_ex = stepping
        handles = [sess.submit(p, max_new_tokens=200)
                   for p in _prompts([6, 5])]
        for h in handles:
            with pytest.raises(serving.ServerClosedError):
                h.result(60)
        closer[0].join(30)
        assert not closer[0].is_alive() and not sess._worker.is_alive()
        assert n_calls[0] == 6


# ---------------------------------------------------------------------------
# the cache is updated where it lies: the lowered program, and stale slots
# ---------------------------------------------------------------------------
def test_decode_step_builds_nothing_of_the_caches_shape():
    """The decode step as ``DecodeSession`` lowers it never
    concatenates planes back into a cache-shaped value, and its temp
    memory does not grow with the cache: under two layers' K/V planes
    and under one tensor's whole cache (the step that sliced 6 planes
    out and stacked them back needed 5.6 layers' planes here, 0.93 of
    the K and V cache). Lowered as the CPU serves it, without donation:
    with it, XLA's CPU compiler copies the cache once ahead of the
    writes (the reads of the last layer's attention are not ordered
    before them by data), where the TPU's updates it in place
    (``tests/test_chip_compile.py``)."""
    net = _tiny_net(dropout=0.0, max_length=64, layers=6)
    with serving.DecodeSession(net, max_slots=8, max_len=64,
                               prefill_buckets=(8,), name="inplace",
                               donate=False) as sess:
        lowered = sess._lower_decode()
        kv = sess._kv
        mlir_shape = "tensor<" + "x".join(str(d) for d in kv.shape) + "x"
        joins = [ln for ln in lowered.as_text().splitlines()
                 if "concatenate" in ln and mlir_shape in ln.split("->")[-1]]
        assert joins == [], "a cache-shaped concatenate is back"
        try:
            temp = lowered.compile().memory_analysis().temp_size_in_bytes
        except Exception:           # noqa: BLE001 — backend has none
            pytest.skip("no memory_analysis() on this backend")
        layer_pair = kv.nbytes // kv.shape[0]    # one layer's K and V
        assert temp < 2 * layer_pair
        assert temp < kv.nbytes // 2


def test_stale_full_slot_leaves_other_slots_rows_alone():
    """A freed slot still computes. With a stale ``cache_len`` of
    ``max_len`` its rows are clamped into its OWN last position: the step
    does not fault, and every other slot's planes and tokens are
    bit-identical to a step in which that slot's entry was 0."""
    net = _tiny_net(dropout=0.0)
    T, stale = 48, 1
    with serving.DecodeSession(net, max_slots=4, max_len=T,
                               prefill_buckets=(8,), name="stale") as sess:
        rs = np.random.RandomState(11)
        k0 = rs.standard_normal(sess._kv.shape).astype(np.float32)
        v0 = rs.standard_normal(sess._kv.shape).astype(np.float32)
        tokens = rs.randint(1, VOCAB, (4,)).astype(np.int32)
        step = jax.jit(sess._decode_apply)

        def run(stale_len):
            lens = np.array([3, stale_len, 7, 0], np.int32)
            nxt, k, v, fed = step(sess._params, k0, v0, lens, tokens)
            np.testing.assert_array_equal(fed, nxt)   # no counters here
            return np.asarray(nxt), np.asarray(k), np.asarray(v), lens

        nxt_a, k_a, v_a, lens = run(T)
        nxt_b, k_b, v_b, _ = run(0)
        others = [s for s in range(4) if s != stale]
        # (the stale slot's own new row holds whatever position
        # ``max_len``, outside the table, embeds to)
        assert np.isfinite(k_a[:, others]).all()
        assert np.isfinite(v_a[:, others]).all()
        for got, ref, src in ((k_a, k_b, k0), (v_a, v_b, v0)):
            np.testing.assert_array_equal(got[:, others], ref[:, others])
            for s in others:        # only the new token's row moved
                keep = np.arange(T) != lens[s]
                np.testing.assert_array_equal(got[:, s][:, :, keep],
                                              src[:, s][:, :, keep])
                assert (got[:, s, :, lens[s]] != src[:, s, :, lens[s]]).any()
            # the stale slot wrote inside its own rows: the last one
            np.testing.assert_array_equal(got[:, stale, :, :T - 1],
                                          src[:, stale, :, :T - 1])
        np.testing.assert_array_equal(nxt_a[others], nxt_b[others])


@pytest.mark.parametrize("heads,head_dim,pack", [
    (25, 64, 2),      # GPT-2 XL's heads: 13 stored rows, half of the last a pad
    (4, 16, 8),       # the tiny spec: one stored row, half of it a pad
    (3, 64, 2),       # an odd count of heads
    (2, 128, 1),      # a head fills the lanes: a row is a head
])
def test_stored_rows_serve_the_plain_forward(heads, head_dim, pack):
    """The cache keeps ``pack`` heads side by side in a row of 128 lanes
    (``gpt.py::_kv_pack``). Through it a real session's greedy streams
    are the oracle's and what pads the last stored row stays zero under
    joins and steps; the logits of ``serve_prefill`` and of every
    ``serve_step`` of a greedy run are the plain forward's; and a stale
    slot whose ``cache_len`` is ``max_len`` writes into its own last row
    and moves nothing else (PR 26's clamp, in the stored form)."""
    import jax.numpy as jnp

    T, bucket, slots, stale = 24, 8, 3, 1
    rows, width = -(-heads // pack), pack * head_dim
    used = heads * head_dim - (rows - 1) * width   # of the last row
    np.random.seed(3)
    mx.random.seed(3)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, num_layers=2,
                  units=heads * head_dim, num_heads=heads, hidden_size=64,
                  max_length=T, dropout=0.0)
    net.initialize(init="xavier")

    def forward(seq):
        return net(mx.nd.array(np.array(seq)[None],
                               dtype="int32")).asnumpy()[0, -1]

    with serving.DecodeSession(net, max_slots=slots, max_len=T,
                               prefill_buckets=(bucket,),
                               name=f"stored{heads}x{head_dim}") as sess:
        shape = (2, slots, rows, T, width)
        assert sess.stats()["kv_shapes"] == [shape]
        prompts = _prompts([5, 3, 7, 4], seed=heads)
        handles = [sess.submit(p, max_new_tokens=4) for p in prompts]
        for h, p in zip(handles, prompts):
            assert h.result(120.0) == _oracle(net, p, 4)
        for cache in (np.asarray(sess._kv.k), np.asarray(sess._kv.v)):
            assert cache.shape == shape and cache.any()
            assert not cache[:, :, -1, :, used:].any(), "the pad moved"

        run, params = sess._run, sess._params
        prefill = jax.jit(lambda pv, tok, n: run(net.serve_prefill, pv,
                                                 tok, n))
        step = jax.jit(lambda pv, *a: run(net.serve_step, pv, *a))
        k = v = jnp.zeros(shape, jnp.float32)
        seqs = {0: list(prompts[0]), 2: list(prompts[1])}
        nxt = np.zeros(slots, np.int32)
        for slot, seq in seqs.items():
            padded = np.zeros(bucket, np.int32)
            padded[:len(seq)] = seq
            last, kp, vp = prefill(params, padded, np.int32(len(seq)))
            assert kp.shape == (2, rows, bucket, width)
            np.testing.assert_allclose(last, forward(seq), rtol=1e-4,
                                       atol=1e-4)
            k, v = k.at[:, slot, :, :bucket].set(kp), \
                v.at[:, slot, :, :bucket].set(vp)
            nxt[slot] = int(np.argmax(last))
        for _ in range(5):
            n0, n2 = len(seqs[0]), len(seqs[2])
            logits, k2, v2 = step(params, nxt.copy(),
                                  np.array([n0, T, n2], np.int32), k, v)
            logits_b, k2b, v2b = step(params, nxt.copy(),
                                      np.array([n0, 0, n2], np.int32), k, v)
            for slot, seq in seqs.items():
                seq.append(int(nxt[slot]))
                np.testing.assert_allclose(logits[slot], forward(seq),
                                           rtol=1e-4, atol=1e-4)
                np.testing.assert_array_equal(logits[slot], logits_b[slot])
                nxt[slot] = int(np.argmax(logits[slot]))
            for new, new_b, old in ((k2, k2b, k), (v2, v2b, v)):
                new, new_b, old = map(np.asarray, (new, new_b, old))
                others = [s for s in range(slots) if s != stale]
                # (the stale slot's own new row holds whatever position
                # ``max_len``, outside the table, embeds to)
                assert np.isfinite(new[:, others]).all()
                assert not new[:, others, -1, :, used:].any(), \
                    "the pad moved"
                np.testing.assert_array_equal(new[:, others],
                                              new_b[:, others])
                for slot in others:     # one row a slot, at its length
                    moved = (new[:, slot] != old[:, slot]).any(axis=(0, 1, 3))
                    assert list(np.flatnonzero(moved)) == [len(seqs[slot]) - 1]
                # the stale slot wrote inside its own rows: the last one
                np.testing.assert_array_equal(new[:, stale, :, :T - 1],
                                              old[:, stale, :, :T - 1])
                assert (new[:, stale, :, T - 1] != old[:, stale, :, T - 1]).any()
            k, v = k2, v2


def test_prefill_through_the_forward_kernel_serves_the_plain_forward():
    """At a bucket the rule of ``ops/pallas_attention.py`` sends to the
    forward flash kernel (1024 positions x 20 heads of 64: 21.0M scores,
    over 19 x 2**20; here through the Pallas interpreter, one launch a layer,
    counted where the program is traced) a real session's greedy streams
    are the oracle's, whose plain forward at the prompt's own length
    stays under the crossover and takes the dense chain, and
    ``serve_prefill``'s logits are the plain forward's."""
    layers, heads, bucket = 2, 20, 1024
    np.random.seed(5)
    mx.random.seed(5)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, num_layers=layers,
                  units=heads * 64, num_heads=heads, hidden_size=64,
                  max_length=bucket, dropout=0.0)
    net.initialize(init="xavier")

    def traced(path):
        return telemetry.counter("mxtpu_flash_dispatch_total", path=path,
                                 direction="forward").value

    prompts = _prompts([700, 960], seed=11)
    with serving.DecodeSession(net, max_slots=2, max_len=bucket,
                               prefill_buckets=(bucket,),
                               name="flashfill") as sess:
        kernel, dense = traced("kernel"), traced("dense")
        sess.warmup()
        assert (traced("kernel"), traced("dense")) == (kernel + layers, dense)
        handles = [sess.submit(p, max_new_tokens=3) for p in prompts]
        want = [_oracle(net, p, 3) for p in prompts]
        assert traced("kernel") == kernel + layers      # the oracle: dense
        for h, w in zip(handles, want):
            assert h.result(300.0) == w
        padded = np.zeros(bucket, np.int32)
        padded[:700] = prompts[0]
        last, _, _ = jax.jit(lambda pv, tok, n: sess._run(
            net.serve_prefill, pv, tok, n))(sess._params, padded,
                                            np.int32(700))
    plain = net(mx.nd.array(prompts[0][None], dtype="int32")).asnumpy()
    np.testing.assert_allclose(last, plain[0, -1], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# front-door semantics: backpressure, shedding, drain/healthz
# ---------------------------------------------------------------------------
def test_backpressure_queue_full():
    net = _tiny_net()
    sess = serving.DecodeSession(net, max_slots=1, max_len=48,
                                 prefill_buckets=(8,), max_queue=4,
                                 name="bp")
    try:
        sess.warmup()
        handles = [sess.submit(p, max_new_tokens=20)
                   for p in _prompts([5, 5])]
        with pytest.raises(serving.QueueFullError) as ei:
            for _ in range(30):                # queue capacity is 4
                handles.append(sess.submit(_prompts([5])[0],
                                           max_new_tokens=20))
        assert ei.value.retry_after > 0
        assert sess.stats()["rejected"] >= 1
        for h in handles:
            h.result(120)
    finally:
        sess.close()


def test_deadline_shed_while_queued():
    net = _tiny_net(max_length=448)
    sess = serving.DecodeSession(net, max_slots=1, max_len=448,
                                 prefill_buckets=(8,), deadline_ms=30.0,
                                 name="shed")
    try:
        sess.warmup()
        first = sess.submit(_prompts([6])[0], max_new_tokens=400)
        # wait for the first STREAMED token: the slot is now provably
        # occupied, so the late requests below must queue for ~399 more
        # decode steps — far past the 30 ms deadline — while `first`
        # itself was admitted deadline-free (determinism: the deadline
        # is generous vs worker wakeup, small vs the running sequence)
        it = iter(first)
        next(it)
        late = [sess.submit(p, max_new_tokens=2)
                for p in _prompts([4, 4], seed=9)]
        for h in late:
            with pytest.raises(serving.DeadlineExceededError) as ei:
                h.result(120)
            assert ei.value.retry_after > 0
        # the sweep runs at every step boundary, not only when a slot
        # frees: expired requests fail fast (and stop holding queue
        # room) while the single slot is still mid-generation
        assert not first.done(), "shed should not wait for a free slot"
        assert len(first.result(300)) == 400
        assert sess.stats()["shed"] == len(late)
    finally:
        sess.close()


def test_submit_validation_and_lifecycle():
    net = _tiny_net()
    sess = serving.DecodeSession(net, max_slots=1, max_len=16,
                                 prefill_buckets=(8,), name="val")
    with pytest.raises(ValueError, match="empty"):
        sess.submit([])
    with pytest.raises(ValueError, match="bucket"):
        sess.submit(np.arange(9))              # > largest bucket
    with pytest.raises(ValueError, match="cache room"):
        sess2 = serving.DecodeSession(net, max_slots=1, max_len=8,
                                      prefill_buckets=(8,), name="val2")
        try:
            sess2.submit(np.arange(8))         # prompt == max_len
        finally:
            sess2.close()
    h = sess.healthz()
    assert h["ready"] and h["state"] == "running"
    assert h["slots"] == {"active": 0, "total": 1}
    assert sess.drain(30)
    with pytest.raises(serving.ServerClosedError):
        sess.submit([1, 2])
    assert not sess.healthz()["ready"]
    sess.close()


def test_defaults_come_from_config_knobs():
    config.set("MXTPU_DECODE_SLOTS", 3)
    config.set("MXTPU_DECODE_MAX_LEN", 32)
    config.set("MXTPU_DECODE_BUCKETS", "8,16,64")   # 64 > max_len: drops
    config.set("MXTPU_DECODE_MAX_NEW_TOKENS", 4)
    net = _tiny_net()
    with serving.DecodeSession(net, name="knobs") as sess:
        assert sess.max_slots == 3
        assert sess.max_len == 32
        assert sess.prefill_buckets == (8, 16)
        got = sess.generate(_prompts([5])[0])   # default budget: 4
    assert len(got) == 4


# ---------------------------------------------------------------------------
# the recompile contract (satellite): zero post-warmup compiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("slots", [3, 2], ids=["churn", "full"])
def test_steady_state_decode_zero_recompiles_under_watchdog(slots):
    """Mixed-age churn against the armed PR 4 watchdog: after warmup,
    the fixed executable set must serve ANY mix of prompt lengths,
    sequence ages and slot occupancies without one more XLA compile;
    with two slots every one stays busy and most steps are launched
    ahead, their ``tokens`` the step before's device output."""
    net = _tiny_net()
    wd = telemetry.get_watchdog()
    assert wd is not None
    sess = serving.DecodeSession(net, max_slots=slots, max_len=48,
                                 prefill_buckets=(8, 16), name="steady")
    try:
        sess.warmup()
        # first churn wave drives every executable past the warmup
        # budget (default 10 steps)
        for h in [sess.submit(p, max_new_tokens=n) for p, n in
                  zip(_prompts([5, 12, 3, 9], seed=1), (8, 6, 12, 7))]:
            h.result(120)
        assert telemetry.get_watchdog().steps(
            f"decode.{sess.name}") > int(
                config.get("MXTPU_RECOMPILE_WARMUP_STEPS"))
        compiles_before = wd.compile_count
        # steady state: new lengths-mixes, joins and leaves — same
        # executables
        for h in [sess.submit(p, max_new_tokens=n) for p, n in
                  zip(_prompts([4, 15, 7, 2, 11], seed=2),
                      (9, 5, 11, 6, 8))]:
            h.result(120)
        assert wd.compile_count == compiles_before, \
            "steady-state decode compiled something"
        assert not wd.flagged(), [e.__dict__ for e in wd.flagged()]
        assert sess.stats()["steps_ahead"] > 0
    finally:
        sess.close()


def test_prefill_bucket_policy_compiles_once_per_bucket():
    net = _tiny_net()
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8, 16),
                               name="buckets") as sess:
        sess.warmup()
        pre = sess.stats()["prefill_cache"]
        assert pre["compiles"] == 2            # one per length bucket
        for n in (3, 8, 5):                    # all land in bucket 8
            sess.generate(_prompts([n])[0], max_new_tokens=2)
        sess.generate(_prompts([12])[0], max_new_tokens=2)  # bucket 16
        post = sess.stats()["prefill_cache"]
        assert post["compiles"] == 2           # warmup covered them all
        assert post["hits"] == 4


# ---------------------------------------------------------------------------
# executor-cache extensions the prefill path rides on
# ---------------------------------------------------------------------------
def test_executor_cache_pass_count_and_depad():
    import jax.numpy as jnp

    from incubator_mxnet_tpu.serving import BucketedExecutorCache

    def apply_fn(params, x, n):
        # returns the padded input (depad=False must hand it back whole)
        # and a scalar derived from the TRACED true count
        mask = jnp.arange(x.shape[0]) < n
        return x + params[0], jnp.sum(jnp.where(mask, x, 0.0)
                                      ).astype(jnp.float32)

    cache = BucketedExecutorCache(apply_fn, [np.float32(1.0)],
                                  buckets=(4, 8), pass_count=True,
                                  depad=False, name="ext")
    x = np.arange(3, dtype=np.float32)
    padded, s = cache(x)
    assert padded.shape == (4,)                # bucket-shaped, no de-pad
    np.testing.assert_allclose(np.asarray(padded), [1, 2, 3, 1])
    assert float(s) == 3.0                     # 0+1+2: only true rows


# ---------------------------------------------------------------------------
# telemetry: the mxtpu_decode_* family, JSONL records, report section
# ---------------------------------------------------------------------------
def test_decode_metrics_family_and_report(tmp_path):
    path = str(tmp_path / "decode.jsonl")
    telemetry.set_jsonl(path)
    net = _tiny_net()
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="tele") as sess:
        sess.warmup()
        for h in [sess.submit(p, max_new_tokens=4)
                  for p in _prompts([5, 6, 4], seed=4)]:
            h.result(120)
        snap = sess.stats()
    telemetry.set_jsonl(None)
    assert snap["tokens"] >= 12 and snap["cache_bytes"] > 0
    assert 0 < snap["steps_ahead"] < snap["steps"]
    assert snap["tokens_dropped"] == 0
    text = telemetry.prometheus_text()
    for fam in ("mxtpu_decode_tokens_total", "mxtpu_decode_slots_active",
                "mxtpu_decode_steps_ahead_total",
                "mxtpu_decode_tokens_dropped_total",
                "mxtpu_decode_prefill_seconds_total",
                "mxtpu_decode_seconds_total", "mxtpu_decode_cache_bytes",
                "mxtpu_decode_queue_wait_seconds"):
        assert fam in text, f"{fam} missing from /metrics"
    # one kind:"decode" JSONL record per finished request; the report
    # tool renders them and exposes the --compare keys
    records = telemetry.read_jsonl(path)
    decs = [r for r in records if r.get("kind") == "decode"]
    assert len(decs) == 3
    assert all(r["model"] == "tele" and r["new_tokens"] == 4
               for r in decs)
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    out = telemetry_report.summarize(path)
    assert "decode (per request)" in out and "tele" in out
    assert (f"decode.tele: {snap['steps_ahead']} of {snap['steps']} steps "
            "launched ahead, 0 slot-tokens dropped") in out
    keys = telemetry_report._comparable_metrics(records)
    assert keys["decode/tele/requests"] == 3.0
    assert keys["decode/tele/tokens"] == 12.0


def test_open_loop_serving_rows_compare_keys(tmp_path):
    """The shared open-loop harness emits kind:'serving' rows that
    --compare flattens per rate point."""
    # keys come from the NOMINAL rate, not the measured offered_rps
    # (the Poisson draw differs run to run; see telemetry_report)
    rows = [{"kind": "serving", "mode": "open_loop", "model": "m",
             "rate": 50.0, "offered_rps": 49.84, "achieved_rps": 49.5,
             "p50_ms": 3.0, "p99_ms": 9.0, "shed": 1}]
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report

    keys = telemetry_report._comparable_metrics(rows)
    assert keys["serving/m/rate50/p99_ms"] == 9.0
    assert keys["serving/m/rate50/achieved_rps"] == 49.5


# ---------------------------------------------------------------------------
# end-to-end: train (SuperStep + ZeRO-2) -> checkpoint -> decode
# ---------------------------------------------------------------------------
def test_train_checkpoint_decode_end_to_end(tmp_path):
    """One decoder config through the whole stack: SuperStep + ZeRO-2
    training on the 8-device mesh, sharded checkpoint,
    ``DecodeSession.from_checkpoint`` at M=1, greedy decode bit-exact
    against the TRAINED weights' full-sequence oracle."""
    import jax

    from incubator_mxnet_tpu.parallel.superstep import stack_window

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    B, T = 2 * len(jax.devices()), 12
    net = _tiny_net(seed=5, dropout=0.0)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return ce(logits, labels).mean()

    trainer = parallel.SPMDTrainer(
        net, lm_loss, "sgd", {"learning_rate": 0.05, "momentum": 0.9},
        mesh=parallel.make_mesh({"data": -1}), zero_stage=2)

    def batch(i):
        rs = np.random.RandomState(100 + i)
        return (rs.randint(1, VOCAB, (B, T)).astype(np.int32),
                rs.randint(1, VOCAB, (B, T)).astype(np.float32))

    config.set("MXTPU_SUPERSTEP", "1")
    try:
        win = stack_window([batch(i) for i in range(4)])
        losses = np.asarray(jax.device_get(
            trainer.run_superstep(win[0], win[1])))
        assert losses.shape == (4,) and np.isfinite(losses).all()
    finally:
        config.unset("MXTPU_SUPERSTEP")

    prefix = str(tmp_path / "gpt-ckpt")
    parallel.save_sharded(prefix, trainer)

    # the trained weights, synced back for the oracle
    trainer.sync_to_net()
    prompt = _prompts([7], seed=6)[0]
    want = _oracle(net, prompt, 6)

    # a FRESH block restored from the sharded checkpoint at M=1
    net2 = _tiny_net(seed=99, dropout=0.0)   # different init, overwritten
    sess = serving.DecodeSession.from_checkpoint(
        net2, prefix, max_slots=2, max_len=32, prefill_buckets=(8,),
        name="e2e")
    try:
        got = sess.generate(prompt, max_new_tokens=6)
    finally:
        sess.close()
    assert got == want, "decode from the restored checkpoint diverged " \
                        "from the trained oracle"
