"""``ops/kv_cache.py`` against a by-hand numpy cache: write the new row,
then dense attention over the rows the slot may read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.ops import kv_cache, pallas_decode

L, S, ROWS, D = 2, 3, 6, 16
STALE = 1            # a freed slot whose cache_len stays at ``ROWS``


def _np_step(cache_k, cache_v, q, k_new, v_new, lens, kind):
    """Numpy reference of one step over plain heads: caches
    (L, S, H, ROWS, D), the new rows (L, S, H, D) written at their row
    first, then the queries (L, S, H, G, D) attend over their K/V head's
    readable rows."""
    k, v = cache_k.copy(), cache_v.copy()
    out = np.zeros(q.shape, np.float64)
    for s, n in enumerate(lens):
        row = n % ROWS if kind == "ring" else min(n, ROWS - 1)
        k[:, s, :, row], v[:, s, :, row] = k_new[:, s], v_new[:, s]
        t = min(n + 1, ROWS)
        for l, h, j in np.ndindex(q.shape[0], q.shape[2], q.shape[3]):
            sc = k[l, s, h, :t].astype(np.float64) @ q[l, s, h, j] / D ** 0.5
            w = np.exp(sc - sc.max())
            out[l, s, h, j] = (w / w.sum()) @ v[l, s, h, :t]
    return out, k, v


def _pack(x, g):
    """(..., H, D) heads -> (..., P, g * D) stored rows, the rest zero."""
    h = x.shape[-2]
    p = -(-h // g)
    x = np.concatenate([x, np.zeros(x.shape[:-2] + (p * g - h, D),
                                    x.dtype)], axis=-2)
    return x.reshape(x.shape[:-2] + (p, g * D))


def _stored(cache, g):
    """(L, S, H, ROWS, D) -> the stored (L, S, P, ROWS, g * D)."""
    return np.moveaxis(_pack(np.moveaxis(cache, 2, 3), g), 3, 2)


@pytest.mark.parametrize("g", [1, 2, 8])
@pytest.mark.parametrize("kind", ["full", "ring"])
def test_step_is_write_then_dense_attention(kind, g):
    """address + read + attend + write over steps that wrap a ring, one
    slot stale at ``cache_len == rows``. ``g`` = 1 is the grouped-query
    form (3 queries a K/V head); ``g`` > 1 the stored row: 3 heads in
    rows of ``g``, so the last row is padded."""
    rs = np.random.RandomState(7)
    heads, queries = (2, 3) if g == 1 else (3, 1)
    normal = lambda *shape: rs.standard_normal(shape).astype(np.float32)
    ck, cv = normal(L, S, heads, ROWS, D), normal(L, S, heads, ROWS, D)
    sk, sv = jnp.asarray(_stored(ck, g)), jnp.asarray(_stored(cv, g))
    lens = np.array([1, ROWS, 4])
    live = [s for s in range(S) if s != STALE]
    for _ in range(5):
        q = normal(L, S, heads, queries, D)
        k_new, v_new = normal(L, S, heads, D), normal(L, S, heads, D)
        want, ck2, cv2 = _np_step(ck, cv, q, k_new, v_new, lens, kind)
        n = jnp.asarray(lens, jnp.int32)
        at = row, here, see = kv_cache.address(n, ROWS, kind)
        rows_k = [jnp.asarray(_pack(a, g))[:, :, None] for a in k_new]
        rows_v = [jnp.asarray(_pack(a, g))[:, :, None] for a in v_new]
        for l in range(L):
            # (S, H, G, D) as it is, or the heads packed like a K/V row
            ql = jnp.asarray(q[l] if g == 1
                             else _pack(q[l, :, :, 0], g)[:, :, None])
            got = kv_cache.attend_row(ql, sk, sv, l, rows_k[l], rows_v[l],
                                      n, at, kind, D)
            assert got.shape == ql.shape
            np.testing.assert_array_equal(got, kv_cache.attend(
                ql, kv_cache.read(sk, l, rows_k[l], here),
                kv_cache.read(sv, l, rows_v[l], here), see, D))
            got = np.asarray(got).reshape(S, -1)[:, :heads * queries * D]
            np.testing.assert_allclose(
                got[live], want[l].reshape(S, -1)[live], rtol=2e-5, atol=2e-5)
        sk = kv_cache.write(sk, rows_k, row)
        sv = kv_cache.write(sv, rows_v, row)
        np.testing.assert_array_equal(np.asarray(sk), _stored(ck2, g))
        np.testing.assert_array_equal(np.asarray(sv), _stored(cv2, g))
        # one row a slot moved, the stale slot's inside its own rows
        moved = (ck2 != ck).any(axis=(0, 2, 4))
        assert [list(np.flatnonzero(m)) for m in moved] == [
            [n % ROWS if kind == "ring" else min(n, ROWS - 1)] for n in lens]
        ck, cv = ck2, cv2
        lens = np.where(np.arange(S) == STALE, ROWS, lens + 1)


BLOCK = kv_cache.BLOCK
#: how a length is named -> the length, given the plane's rows
_LENS = {"0": lambda rows: 0, "1": lambda rows: 1,
         "block-1": lambda rows: BLOCK - 1, "block": lambda rows: BLOCK,
         "block+1": lambda rows: BLOCK + 1, "rows-1": lambda rows: rows - 1,
         "rows(stale)": lambda rows: rows}
#: g heads a stored row, G queries a K/V head, the head's width
_FORMS = {"g1-3q": (1, 3, 128), "g1-8q": (1, 8, 128), "g2": (2, 1, 64)}
#: (rows, dtype, pairs in flight): None is what the shapes give (4 in
#: float32, 7 in bfloat16 here); the others are the served depths, over
#: calls of 0 to 13 pairs
_RINGS = [(r, t, None) for r in (2 * BLOCK, 4 * BLOCK)
          for t in ("float32", "bfloat16")] \
    + [(4 * BLOCK, "bfloat16", d) for d in (1, 2, 6, 13)]


@pytest.mark.parametrize("rows,dtype,depth", _RINGS)
@pytest.mark.parametrize("form", sorted(_FORMS))
@pytest.mark.parametrize("lens", sorted(_LENS) + ["unlike"])
def test_block_kernel_is_the_dense_attention(lens, form, rows, dtype, depth):
    """``attend_blocks`` (the kernel, in the interpreter) against
    ``read`` + ``attend`` on the same step: every slot at one length, or
    all seven lengths side by side in one call. Blocks above a slot's
    last live one are NaN in the kernel's cache, so a dead block fetched
    and let through a mask fails; the rows of the last live block above
    ``cache_len``, and the row the new token will take, hold finite
    garbage, which the mask and ``k_new``/``v_new`` keep out. A ring of
    any depth reads the same rows: a call of fewer pairs than the depth
    (every slot at length 0 has none) starts only the pairs it has."""
    g, queries, d = _FORMS[form]
    layers, heads, w, layer = 2, 2, g * d, 1
    ns = [f(rows) for f in _LENS.values()] if lens == "unlike" \
        else [_LENS[lens](rows)] * 2
    rs = np.random.RandomState(len(lens) + rows)
    normal = lambda *shape: jnp.asarray(
        rs.standard_normal(shape).astype(np.float32)).astype(dtype)
    k, v = (normal(layers, len(ns), heads, rows, w) for _ in "kv")
    q = normal(len(ns), heads, queries, w)
    k_new, v_new = normal(len(ns), heads, 1, w), normal(len(ns), heads, 1, w)
    n = jnp.asarray(ns, jnp.int32)
    at = kv_cache.address(n, rows, "full")
    # off the TPU the step's entry reads dense, whatever the plane
    assert kv_cache.blocked(rows, "full")
    want = kv_cache.attend_row(q, k, v, layer, k_new, v_new, n, at, "full", d)
    np.testing.assert_array_equal(want, kv_cache.attend(
        q, kv_cache.read(k, layer, k_new, at[1]),
        kv_cache.read(v, layer, v_new, at[1]), at[2], d))
    dead = np.arange(rows)[None, :] >= np.array(
        [-(-min(n, rows - 1) // BLOCK) * BLOCK for n in ns])[:, None]
    k, v = (jnp.where(dead[None, :, None, :, None], jnp.nan, a)
            for a in (k, v))
    # copies run as they start, and one past the plane's end raises
    eager = pltpu.InterpretParams(dma_execution_mode="eager")
    got = kv_cache.attend_blocks(q, k, v, layer, k_new, v_new, n, d,
                                 interpret=eager) \
        if depth is None else kv_cache._heads_of_a_row(
            lambda q: pallas_decode.attend(q, k, v, layer, k_new, v_new, n,
                                           d, interpret=eager, _depth=depth),
            q, w, d)
    assert got.shape == q.shape and got.dtype == want.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("h,w,planes,itemsize,depth", [
    (13, 128, 2, 2, 1),      # GPT-2 XL: 13 stored rows of two heads
    (8, 128, 2, 2, 2),       # K-EXAONE: 8 K/V heads
    (1, 640, 1, 2, 6),       # Xing4: one latent plane of 640 lanes
    (1, 128, 2, 2, 13),      # Jamba2: one K/V head
    (1, 128, 1, 2, 16),      # a pair of 32 KiB: the ring's deepest
    (13, 128, 2, 4, 1),      # more than the target a pair
])
def test_pairs_in_flight_come_from_the_shapes(h, w, planes, itemsize,
                                              depth):
    """``_in_flight`` keeps GPT-2 XL's one pair of bytes on the way at
    every served shape, clipped to 1-16 pairs, and the counter of traced
    calls goes up by one at that depth for a call at those shapes."""
    assert pallas_decode._in_flight(h, w, planes, itemsize) == depth
    counter = telemetry.counter("mxtpu_decode_attention_depth_total",
                                depth=depth)
    before = counter.value
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    slots, rows, queries = 2, 2 * BLOCK, 8
    cache = jax.ShapeDtypeStruct((1, slots, h, rows, w), dtype)
    row = jax.ShapeDtypeStruct((slots, h, 1, w), dtype)
    jax.eval_shape(
        lambda q, k, v, kn, vn, n: pallas_decode.attend(
            q, k, v, 0, kn, vn, n, w, v_width=w // 2 if v is None else None),
        jax.ShapeDtypeStruct((slots, h, queries, w), dtype), cache,
        None if planes == 1 else cache, row, None if planes == 1 else row,
        jax.ShapeDtypeStruct((slots,), jnp.int32))
    assert counter.value == before + 1


def test_rings_small_and_ragged_planes_are_read_dense():
    """``blocked`` sends to the kernel a full group of more than one
    block of rows, in whole blocks, and nothing else; ``fetched_rows``
    counts what each path reads: the plane, or whole blocks of cached
    rows and the new row, at a block's edge too never under the
    ``cache_len + 1`` rows that are live."""
    assert [kv_cache.blocked(r, "full") for r in
            (48, 128, 200, 256, 1024, 4096)] == [False] * 3 + [True] * 3
    assert not any(kv_cache.blocked(r, "ring") for r in (128, 1024, 4096))
    lens = np.array([0, 1, 127, 128, 129, 1022, 1023, 1024, 4000])
    np.testing.assert_array_equal(kv_cache.fetched_rows(lens, 1024, False),
                                  [1024] * 9)
    by_blocks = kv_cache.fetched_rows(lens, 1024, True)
    np.testing.assert_array_equal(
        by_blocks, [1, 129, 129, 129, 257, 1025, 1025, 1025, 1025])
    assert (by_blocks >= np.minimum(lens + 1, 1024)).all()


@pytest.mark.parametrize("platform,read", [("tpu", 2 * (129 + 257) + 3 * 256),
                                           ("cpu", 2 * 512 + 3 * 256)])
def test_kv_cache_counts_what_its_platform_reads(monkeypatch, platform, read):
    """``KVCache`` asks the rule once, for the platform its arrays lie
    on: a full group of two blocks goes by blocks on the TPU alone, the
    ring and every other platform read their planes whole."""
    import jax

    from incubator_mxnet_tpu import serving

    class On:
        def __init__(self, array):
            self.shape = array.shape

        def devices(self):
            return {type("Device", (), {"platform": platform})}

    monkeypatch.setattr(jax, "device_put", On)
    kv = serving.KVCache(
        [dict(layers=2, heads=2, rows=256, head_dim=128, kind="full"),
         dict(layers=3, heads=2, rows=128, head_dim=128, kind="ring")], 2)
    assert kv.read([128, 129])[0] == read
    assert kv.live_rows([129, 130]) <= read <= kv.rows + 2 * 2


def test_pack_and_store_rows():
    assert [kv_cache.pack(d) for d in (16, 64, 128, 256, 96)] == [8, 2, 1, 1, 1]
    x = np.arange(2 * 5 * 3 * D, dtype=np.float32).reshape(2, 5, 3 * D)
    got = np.asarray(kv_cache.store_rows(jnp.asarray(x), 3, 2))
    want = np.moveaxis(_pack(x.reshape(2, 5, 3, D), 2), 2, 1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [3, ROWS, ROWS + 1, 11])
@pytest.mark.parametrize("kind", ["full", "ring"])
def test_join_of_a_prompt_under_at_and_over_the_rows(kind, n):
    """A ring takes the last ``rows`` positions below the true length,
    each at ``position mod rows``; a full group the plane from row 0."""
    bucket, heads, slot = 12, 2, 1
    rows = ROWS if kind == "ring" else 16
    rs = np.random.RandomState(n)
    cache = rs.standard_normal((L, S, heads, rows, D)).astype(np.float32)
    plane = rs.standard_normal((L, heads, bucket, D)).astype(np.float32)
    got = np.asarray(kv_cache.join(jnp.asarray(cache), jnp.asarray(plane),
                                   jnp.int32(slot), jnp.int32(n), kind))
    others = [s for s in range(S) if s != slot]
    np.testing.assert_array_equal(got[:, others], cache[:, others])
    if kind == "full":
        np.testing.assert_array_equal(got[:, slot, :, :bucket], plane)
        np.testing.assert_array_equal(got[:, slot, :, bucket:],
                                      cache[:, slot, :, bucket:])
        return
    for pos in range(max(0, n - rows), n):
        np.testing.assert_array_equal(got[:, slot, :, pos % rows],
                                      plane[:, :, pos])
    # and a step after it reads exactly those positions
    _, _, see = kv_cache.address(jnp.asarray([0, n, 0], jnp.int32), rows, kind)
    assert int(see[slot].sum()) == min(n + 1, rows)


# -- the ``state`` kind: a recurrent layer's state, no rows --------------------

#: the taps wider than the state, as Mamba-2's convolution over x, B and C
_STATE = dict(layers=3, kind="state", width=256, state=4, taps=3,
              taps_width=384, dtype="float32")


def test_a_state_group_keeps_two_tensors_with_a_dtype_each():
    """The scan's state in the group's own type, the convolution's taps
    in the cache's, each at its own width, minor; a row group's arrays
    take the cache's type and a prefill hands the join a slot's state
    with no position axis."""
    assert kv_cache.tensors("state") == 2
    got = kv_cache.layout(_STATE, 5, "bfloat16")
    assert got == [((3, 5, 4, 256), jnp.dtype("float32")),
                   ((3, 5, 3, 384), jnp.dtype("bfloat16"))]
    rows = dict(layers=2, heads=2, rows=8, head_dim=128, kind="full")
    assert kv_cache.layout(rows, 5, "bfloat16") == [
        ((2, 5, 2, 8, 128), jnp.dtype("bfloat16"))] * 2
    assert kv_cache.layout(dict(rows, kind="latent"), 5, "float32") == [
        ((2, 5, 2, 8, 128), jnp.dtype("float32"))]
    assert kv_cache.plane_shape((3, 5, 4, 256), "state", 64) == (3, 4, 256)
    assert kv_cache.plane_shape((2, 5, 2, 8, 128), "full", 64) \
        == (2, 2, 64, 128)


@pytest.mark.parametrize("n", [1, 7, 500])
def test_a_state_join_replaces_the_slots_state_whatever_the_length(n):
    rs = np.random.RandomState(n)
    cache = rs.standard_normal((3, S, 4, 8)).astype(np.float32)
    plane = rs.standard_normal((3, 4, 8)).astype(np.float32)
    got = np.asarray(kv_cache.join(jnp.asarray(cache), jnp.asarray(plane),
                                   jnp.int32(1), jnp.int32(n), "state"))
    np.testing.assert_array_equal(got[:, 1], plane)
    np.testing.assert_array_equal(got[:, [0, 2]], cache[:, [0, 2]])
    # a float32 state into a bfloat16 array: cast, not refused
    low = kv_cache.join(jnp.asarray(cache).astype(jnp.bfloat16),
                        jnp.asarray(plane), jnp.int32(1), jnp.int32(n),
                        "state")
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(low[:, 1]),
        np.asarray(jnp.asarray(plane).astype(jnp.bfloat16)))


def test_advance_replaces_one_layers_state_and_no_other():
    rs = np.random.RandomState(0)
    cache = rs.standard_normal((3, S, 4, 8)).astype(np.float32)
    new = rs.standard_normal((S, 4, 8)).astype(np.float32)
    got = np.asarray(kv_cache.advance(jnp.asarray(cache), 1,
                                      jnp.asarray(new)))
    np.testing.assert_array_equal(got[1], new)
    np.testing.assert_array_equal(got[[0, 2]], cache[[0, 2]])


def test_kv_cache_holds_a_state_group_beside_rows():
    """Per-array dtypes; the state group is outside ``rows``,
    ``live_rows``, ``read`` and ``max_len``, inside ``nbytes``, and
    ``state_bytes`` is its own: each active slot's state once in and once
    out, as stored."""
    from incubator_mxnet_tpu import serving

    full = dict(layers=2, heads=1, rows=16, head_dim=128, kind="full")
    kv = serving.KVCache([full, _STATE], slots=5, dtype="bfloat16")
    only = serving.KVCache([full], slots=5, dtype="bfloat16")
    assert kv.kinds == ["full", "state"]
    assert kv.array_kinds == ["full", "full", "state", "state"]
    assert kv.shapes == [(2, 5, 1, 16, 128), (3, 5, 4, 256)]
    assert [(a.shape, a.dtype) for a in kv.arrays] \
        == [(s.shape, s.dtype) for s in kv.specs()] \
        == [((2, 5, 1, 16, 128), jnp.bfloat16)] * 2 \
        + [((3, 5, 4, 256), jnp.float32), ((3, 5, 3, 384), jnp.bfloat16)]
    assert kv.slots == 5 and kv.max_len == only.max_len == 16
    assert kv.rows == only.rows == 2 * 5 * 16
    assert kv.live_rows([3, 9]) == only.live_rows([3, 9]) == 2 * 12
    assert kv.read([3, 9]) == only.read([3, 9])
    slot = 3 * (256 * 4 * 4 + 384 * 3 * 2)
    assert kv.nbytes == only.nbytes + 5 * slot
    assert kv.state_bytes(2) == 2 * 2 * slot and kv.state_bytes(0) == 0
    assert only.state_bytes(5) == 0
    # a cache of state alone has no rows at all
    bare = serving.KVCache([_STATE], slots=2)
    assert bare.rows == bare.max_len == 0 and bare.read([4])[0] == 0
    assert [a.dtype for a in bare.arrays] == [jnp.float32, jnp.float32]
