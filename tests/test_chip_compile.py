"""AOT compiles of the main-path Pallas kernels for a described TPU v5e
(ISSUE 21): the chip's own compiler, no chip attached.

Interpret-mode tests cannot see what Mosaic refuses — a renamed
compiler-params class, a working set over the scoped-VMEM limit, a slice
not aligned to the tiling. These compile the kernels at the real widths
``chip_smoke.py`` runs them at, about two seconds each.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file. All such tests live in this one file so
that one worker owns the library; they compile in the test's own
process, with the persistent compilation cache off (an entry written
for a described chip cannot be read back without one).
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Layout
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops.pallas_attention import _flash_core
from incubator_mxnet_tpu.ops.pallas_conv import fused_conv_bn

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(one_chip, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_flash_forward_and_backward_t2048(one_chip):
    q = _spec(one_chip, (4, 12, 2048, 64))
    flash = functools.partial(_flash_core, lens=None, scale=0.125,
                              causal=True, interpret=False,
                              cache_offset=False)
    _compile(lambda q, k, v: flash(q, k, v), q, q, q)
    _compile(jax.grad(lambda q, k, v: flash(q, k, v)
                      .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
             q, q, q)


@pytest.mark.parametrize("slots,tq", [(8, 1), (1, 512)])
def test_flash_cache_offset_decode_shape(one_chip, slots, tq):
    """The KV-cache alignment: Tq=1 over every slot, and a prefill
    bucket, against a max_len=1024 buffer with per-slot lengths."""
    q = _spec(one_chip, (slots, 12, tq, 64))
    kv = _spec(one_chip, (slots, 12, 1024, 64))
    lens = _spec(one_chip, (slots,), jnp.int32)
    _compile(lambda q, k, v, l: _flash_core(q, k, v, l, 0.125, True,
                                            False, True), q, kv, kv, lens)


@pytest.mark.parametrize("hw,c", [(56, 64), (7, 512)])
def test_fused_conv_bn_forward_and_backward_resnet50_shape(one_chip, hw, c):
    """3x3 stride-1 at the first and last ResNet-50 stage, batch 32. The
    7x7x512 shape needs the raised scoped-VMEM limit."""
    x = _spec(one_chip, (32, hw, hw, c))
    w = _spec(one_chip, (3, 3, c, c))
    ab = _spec(one_chip, (c,), jnp.float32)

    def conv(x, w, a, b):
        return fused_conv_bn(x, w, a, b, stride=1, pad=1, relu=True,
                             interpret=False)

    def loss(x, w, a, b):
        y, s, ss = conv(x, w, a, b)
        return y.astype(jnp.float32).sum() + s.sum() + ss.sum()

    _compile(conv, x, w, ab, ab)
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, w, ab, ab)


@pytest.mark.parametrize("shape,queries,head_dim,v_width,depth", [
    # GPT-2 XL: two heads a stored row
    ((48, 16, 13, 1024, 128), 2, 64, None, 1),
    # K-EXAONE: 8 queries a K/V head
    ((2, 32, 8, 4096, 128), 8, 128, None, 2),
    # Xing4: one latent plane, its first 512 lanes the values
    ((40, 32, 1, 2048, 640), 32, 640, 512, 6),
    # Jamba2: 20 queries over one K/V head
    ((2, 64, 1, 4096, 128), 20, 128, None, 13),
])
def test_decode_attention_kernel_benchmark_shapes(one_chip, shape, queries,
                                                  head_dim, v_width, depth):
    """The block kernel of the decode step's attention alone, at the
    served caches' whole shapes and the ring's depth there (it lowers and
    fits the compiler's fast memory): the stacked cache goes in as it
    lies (no slice or copy of a plane ahead of the call)."""
    from incubator_mxnet_tpu.ops import pallas_decode

    _, slots, heads, _, w = shape
    one = v_width is not None
    assert pallas_decode._in_flight(heads, w, 1 if one else 2, 2) == depth
    row = _spec(one_chip, (slots, heads, 1, w))
    compiled = _compile(
        lambda q, k, v, kn, vn, n, layer: pallas_decode.attend(
            q, k, v, layer, kn, vn, n, head_dim, v_width=v_width,
            interpret=False),
        _spec(one_chip, (slots, heads, queries, w)), _spec(one_chip, shape),
        None if one else _spec(one_chip, shape), row, None if one else row,
        _spec(one_chip, (slots,), jnp.int32), _spec(one_chip, (), jnp.int32))
    assert not re.search(r"= bf16\[%d,%d,%d,%d\]\S* (copy|slice|dynamic-slice)"
                         % shape[1:], compiled.as_text())


def _kernel_calls(text):
    return len(re.findall(r"= \S+ custom-call\([^\n]*kv_decode_attention",
                          text))


def _served_params(one_chip, sess, block):
    """The session's parameters as it lowers its executables on the
    described chip: each in the format it would hold it in there (a
    table the block reads by rows row-major where the chip's default
    layout for its shape is not)."""
    from incubator_mxnet_tpu.serving.executor_cache import (
        param_specs, row_table_formats)

    on_chip = [_spec(one_chip, p.shape, p.dtype) for p in sess._params]
    formats = row_table_formats(on_chip, sess._param_names,
                                block.row_tables)
    return param_specs(on_chip, formats), formats


def _entry_copies_of_parameters(text):
    """Ops of the entry computation that copy (relayout) a parameter that
    is an array (a scalar's copy into the chip's scalar memory is none)."""
    params, copies, entry = set(), [], False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            entry = line.startswith("ENTRY")
            continue
        if not entry:
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = \w+\[(\d[^\]]*)\]\S* "
                     r"(parameter|copy)\(%?([\w.\-]*)", line)
        if m and m.group(3) == "parameter":
            params.add(m.group(1))
        elif m and m.group(4) in params:
            copies.append(line.strip()[:120])
    return copies


def _orders(one_chip, compiled, params):
    """Per parameter: the order the executable takes it in, and the one
    the described chip's default layout gives its shape and type."""
    (device,) = one_chip.device_set
    return [(f.layout.major_to_minor, Layout.from_pjrt_layout(
        device.client.get_default_layout(s.dtype, s.shape, device))
        .major_to_minor)
        for f, s in zip(compiled.input_formats[0][0], params)]


def _xl_net(layers=2, t=1024):
    from incubator_mxnet_tpu.gluon.model_zoo import get_gpt

    net = get_gpt("gpt_decoder_345m", num_layers=layers, units=1600,
                  num_heads=25, vocab_size=50257, max_length=t, dropout=0.0)
    net.cast("bfloat16")
    net.initialize()
    return net


def _xl_decode_step(one_chip, layers, slots, t):
    """GPT-2 XL's served decode step (``layers`` of the 48 at its widths,
    ``slots`` of ``t`` positions) compiled for the described chip as
    ``DecodeSession`` lowers it: the caches donated, the parameters in
    the formats the session holds them in there. Returns the executable,
    the parameter specs, their formats and the cache's bytes."""
    from incubator_mxnet_tpu import serving

    net = _xl_net(layers, t)
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(256,), name="xl2",
                               donate=True, artifact_dir="") as sess:
        assert sess._kv.shape == (layers, slots, 13, t, 128)
        cache = _spec(one_chip, sess._kv.shape, sess._kv.dtype)
        vec = _spec(one_chip, (slots,), jnp.int32)
        params, formats = _served_params(one_chip, sess, net)
        compiled = jax.jit(sess._decode_apply, donate_argnums=(1, 2)).lower(
            params, cache, cache, vec, vec).compile()
        return compiled, params, formats, sess._kv.nbytes


def test_decode_step_updates_the_cache_in_place_gpt2_xl_widths(one_chip):
    """The served decode step at GPT-2 XL's widths (two layers of the
    48), 16 slots of 1024 positions, donated as ``DecodeSession`` lowers
    it, its attention the block kernel (one call a layer, handed the
    whole stacked caches: no slice of a plane ahead of it). The cache is
    in the stored form, two heads of 64 side by side in
    a row of 128 lanes (13 rows for the 25 heads), and the compiler
    keeps that row minor (``{4,3,2,1,0}``; a ``[.., 25, 1024, 64]``
    cache it laid out ``T``-minor, a hundred tiles a new row). Both
    caches alias their outputs, and outside fusions the only ops whose
    result has the cache's or a layer plane's shape are the in-place
    ``dynamic-update-slice`` of the new rows — no copy, no relayout, no
    concatenate (a scatter in their place makes this compiler relayout
    the whole cache around it)."""
    layers, slots, t = 2, 16, 1024
    compiled, params, _, kv_nbytes = _xl_decode_step(one_chip, layers,
                                                     slots, t)
    n = len(params)
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and f"{{1}}: ({n}, {{}}" in alias.group(0) \
        and f"{{2}}: ({n + 1}, {{}}" in alias.group(0), "caches not aliased"
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_nbytes
    big = re.compile(r"bf16\[(%d,)?(1,)?%d,13,%d,128\]" % (layers, slots, t))
    whole = "bf16[%d,%d,13,%d,128]{" % (layers, slots, t)
    beside, layouts, fused = {}, [], False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            fused = not line.startswith("ENTRY")   # only the entry's ops
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not big.match(m.group(1)):
            continue
        if m.group(2) == "parameter" and m.group(1).startswith(whole):
            layouts.append(m.group(1)[len(whole):].split(":")[0].rstrip("}"))
        if m.group(2) not in ("parameter", "bitcast", "get-tuple-element"):
            beside[m.group(2)] = beside.get(m.group(2), 0) + 1
    assert layouts == ["4,3,2,1,0"] * 2, layouts
    assert beside == {"dynamic-update-slice": 2 * slots}, beside
    assert _kernel_calls(text) == layers


@pytest.mark.parametrize("bucket,kernels", [(1024, 2), (512, 0)])
def test_prefill_attends_through_the_forward_kernel_gpt2_xl_widths(
        one_chip, bucket, kernels):
    """The served prefill at GPT-2 XL's widths (two layers of the 48), as
    ``DecodeSession`` lowers it: nothing differentiates it, so in the
    bucket the rule sends there (1024: 26M scores a layer, over 19 x 2**20)
    its whole-prompt attention is one forward flash kernel a layer and
    the optimised program holds no score-shaped tensor (the parent wrote
    and re-read ``bf16[25,1024,1024]`` at least twice a layer); the 512
    bucket, whose scores the compiler keeps on the chip, is the
    parent's dense chain. Neither copies a parameter on entry: the two
    tables it gathers rows of come in row-major (taken in the chip's
    default layout, both are relaid every call, ``copy(pvals_0)`` of
    ``bf16[50257,1600]``)."""
    from incubator_mxnet_tpu import serving

    heads = 25
    net = _xl_net()
    with serving.DecodeSession(net, max_slots=16, max_len=1024,
                               prefill_buckets=(bucket,), name="xl2",
                               donate=True, artifact_dir="") as sess:
        params, formats = _served_params(one_chip, sess, net)
        compiled = jax.jit(sess._prefill_apply).lower(
            params, _spec(one_chip, (bucket,), jnp.int32),
            _spec(one_chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    scores = re.findall(r"\[(?:1,)?%d,%d,%d\]" % (heads, bucket, bucket),
                        text)
    assert bool(scores) == (kernels == 0), scores[:3]
    assert _entry_copies_of_parameters(text) == []
    assert [order for (order, _), f in zip(
        _orders(one_chip, compiled, params), formats) if f] == [(0, 1)] * 2


def test_decode_step_reads_its_tables_where_they_lie_gpt2_xl_widths(
        one_chip):
    """GPT-2 XL's served decode step (two layers of the 48, 16 slots of
    1024, as ``DecodeSession`` lowers it for the chip): the word and
    position tables, 1600 wide, which the chip's default layout holds
    column-major (the row index minor), are held and taken row-major, so
    the step gathers its rows where they lie: no op of the entry copies a
    parameter (taken in the default layout, ``bf16[50257,1600]`` and
    ``bf16[1024,1600]`` are relaid every step, 169 MB of temp memory);
    every other parameter keeps the chip's default layout."""
    compiled, params, formats, _ = _xl_decode_step(one_chip, 2, 16, 1024)
    text = compiled.as_text()
    assert not re.findall(r"= bf16\[(?:50257|1024),1600\]\S* copy\(", text)
    assert _entry_copies_of_parameters(text) == []
    assert sum(f is not None for f in formats) == 2
    for (order, default), f in zip(_orders(one_chip, compiled, params),
                                   formats):
        assert order == ((0, 1) if f is not None else default)
    assert {default for (_, default), f in zip(
        _orders(one_chip, compiled, params), formats) if f} == {(1, 0)}
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("config,shape,relaid", [
    ("gpt2_xl word", (50257, 1600), True),
    ("gpt2_xl position", (1024, 1600), True),
    ("gpt2_medium word", (50257, 1024), False),
    ("k_exaone_236b_ep8", (19200, 6144), False),
    ("xing4_29b_ep8", (16384, 3584), False),
    ("jamba2_3b", (65536, 2560), False),
    ("ouro_2p6b", (49152, 2048), False),
])
def test_a_table_is_relaid_only_where_the_chip_lays_it_column_major(
        one_chip, config, shape, relaid):
    """The rule by shape, on the described chip, for the served tables of
    the benchmark's configurations (bfloat16): only GPT-2 XL's 1600-wide
    tables, whose default layout puts the row index minor, are given a
    row-major format; every table of a width that is a multiple of 128
    keeps its default, which is row-major already."""
    from incubator_mxnet_tpu.serving.executor_cache import row_table_formats

    spec = _spec(one_chip, shape)
    (fmt,) = row_table_formats([spec], ["table"], ["table"])
    assert (fmt is not None) == relaid
    if relaid:
        assert fmt.layout.major_to_minor == (0, 1)
    assert row_table_formats([spec], ["table"], []) == [None]


def test_training_step_attends_through_the_dense_chain_gpt2_medium_widths(
        one_chip):
    """The same block under ``jax.grad`` at GPT-2 medium's training shape
    ``(4, 16, 1024, 64)``: the backward kernels lose to XLA's backward
    under 2,048 positions, so a differentiated call's program is the
    parent's, the dense chain with its saved probabilities and no
    attention custom call."""
    from incubator_mxnet_tpu.gluon.model_zoo import get_gpt
    from incubator_mxnet_tpu.parallel.spmd import (collect_params,
                                                   functional_apply)

    net = get_gpt("gpt_decoder_345m", num_layers=2, units=1024,
                  num_heads=16, vocab_size=50257, max_length=1024,
                  dropout=0.0)
    net.cast("bfloat16")
    net.initialize()
    objs = collect_params(net)

    def loss(pvals, tokens):
        out, _ = functional_apply(net, objs, pvals, tokens)
        return jnp.mean(out.astype(jnp.float32) ** 2)

    pvals = {n: _spec(one_chip, p.shape, p.dtype) for n, p in objs.items()}
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(loss)).lower(
            pvals, _spec(one_chip, (4, 1024), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "f32[4,16,1024,1024]" in text


def test_sparse_decoder_step_aliases_both_cache_groups_published_widths(
        one_chip):
    """The data-built decoder's served decode step at K-EXAONE's widths
    (hidden 6144, 64 / 8 heads of 128, window 128; one ``LLLG`` period,
    16 experts held of 128, the FFN widths cut so that the weights are a
    test's), 32 slots of 4096 positions, donated as ``DecodeSession``
    lowers it and under the ambient ``highest`` precision the serving
    tier traces it with: all four cache arrays (K and V of the full
    group, K and V of the ring) alias their outputs, the grouped expert
    products are the compiler's own ragged-dot kernels, and the step's
    temp memory stays under a tenth of the cache."""
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    slots, t = 32, 4096
    net = get_decoder("exaone_moe", num_layers=4, hidden_size=512,
                      expert_hidden=256, experts_held=16, expert_share=0,
                      vocab_size=1024, max_length=t)
    net.cast("bfloat16")
    net.initialize(init="zeros")
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(256,), name="ep8",
                               donate=True, artifact_dir="") as sess:
        assert sess._kv.shapes == [(1, slots, 8, t, 128),
                                   (3, slots, 8, 128, 128)]
        caches = [_spec(one_chip, shape) for shape in sess._kv.shapes
                  for _ in "kv"]
        vec = _spec(one_chip, (slots,), jnp.int32)
        params = [_spec(one_chip, p.shape, p.dtype) for p in sess._params]
        compiled = jax.jit(
            sess._decode_apply, donate_argnums=(1, 2, 3, 4)).lower(
            params, *caches, vec, vec).compile()
        kv_bytes = sess._kv.nbytes
    n = len(params)
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and all(f"{{{j + 1}}}: ({n + j}, {{}}" in alias.group(0)
                         for j in range(4)), "a cache array is not aliased"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < kv_bytes // 10
    assert len(re.findall(r"= \S+ custom-call\([^\n]*ragged-dot-none",
                          text)) == 3 * 3      # gate, up, down x 3 layers
    # the full layer attends by blocks, the three rings dense
    assert _kernel_calls(text) == 1


def test_latent_decoder_step_aliases_its_one_cache_tensor_published_widths(
        one_chip):
    """The data-built decoder's served decode step at Xing4.0-29B-A4B's
    widths (hidden 3584, 32 latent-attention heads over a 512 + 64 row,
    four hyper-connection streams; two layers of the 40, one dense and
    one sparse with 8 experts held of 64), 32 slots of 2048 positions,
    donated as ``DecodeSession`` lowers it. The one cache tensor, a row
    stored 640 lanes wide, aliases its output and keeps its rows minor
    (``{4,3,2,1,0}``: a 576-wide row this compiler lays ``T``-minor, 72
    tiles a new row and a relayout of each plane a step, and Mosaic
    refuses a 576-lane slice of it); outside fusions the only ops of the
    cache's or a plane's shape are the in-place ``dynamic-update-slice``
    of the new rows; each layer attends through the block kernel (one
    plane fetched, its first 512 lanes the values) and each sub-layer's
    Sinkhorn rounds are one kernel."""
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    layers, slots, t, row = 2, 32, 2048, 640
    net = get_decoder("xing4_29b", num_layers=layers, dense_layers=1,
                      experts_held=8, expert_share=0, vocab_size=16384,
                      max_length=t)
    net.cast("bfloat16")
    net.initialize(init="zeros")
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(256,), name="xing2",
                               donate=True, artifact_dir="") as sess:
        assert sess._kv.shapes == [(layers, slots, 1, t, row)]
        (cache,) = [_spec(one_chip, s.shape, s.dtype)
                    for s in sess._kv.specs()]
        vec = _spec(one_chip, (slots,), jnp.int32)
        params = [_spec(one_chip, p.shape, p.dtype) for p in sess._params]
        compiled = jax.jit(sess._decode_apply, donate_argnums=(1,)).lower(
            params, cache, vec, vec).compile()
        kv_bytes = sess._kv.nbytes
    n = len(params)
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and f"{{1}}: ({n}, {{}}" in alias.group(0), \
        "the cache is not aliased"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes == layers * slots * t * row * 2
    assert mem.temp_size_in_bytes < kv_bytes // 10
    big = re.compile(r"bf16\[(%d,)?(1,)?%d,1,%d,%d\]" % (layers, slots, t,
                                                         row))
    whole = "bf16[%d,%d,1,%d,%d]{" % (layers, slots, t, row)
    beside, layouts, fused = {}, [], False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            fused = not line.startswith("ENTRY")   # only the entry's ops
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not big.match(m.group(1)):
            continue
        if m.group(2) == "parameter" and m.group(1).startswith(whole):
            layouts.append(m.group(1)[len(whole):].split(":")[0].rstrip("}"))
        if m.group(2) not in ("parameter", "bitcast", "get-tuple-element"):
            beside[m.group(2)] = beside.get(m.group(2), 0) + 1
    assert layouts == ["4,3,2,1,0"], layouts
    assert beside == {"dynamic-update-slice": slots}, beside
    assert _kernel_calls(text) == layers
    assert len(re.findall(
        r"= \S+ custom-call\([^\n]*hyper_connection_coefficients",
        text)) == 2 * layers
    assert len(re.findall(r"= \S+ custom-call\([^\n]*ragged-dot-none",
                          text)) == 3          # gate, up, down of one layer


def test_state_space_decoder_step_replaces_its_state_in_place_published_widths(
        one_chip):
    """The data-built decoder's served decode step at AI21-Jamba2-3B's
    widths (hidden 2560, a mixer of 5120 channels with 16 of state and 3
    taps a channel, 20 query heads over one K/V head of 128; one period of
    four layers: three state-space layers and an attention layer, the
    vocabulary cut so that the weights are a test's), 64 slots of 4096
    positions, donated as ``DecodeSession`` lowers it. All four cache
    arrays alias their outputs, each in its own type; the stacked state
    keeps ``E`` minor with the 16 second (``{3,2,1,0}``) and is touched by
    nothing but one in-place update fusion a layer (and, at this depth,
    the compiler's own move of the 63 MB through its fast memory,
    ``copy-start`` / ``copy-done``; at 26 layers there is none): no copy
    of it, and temp memory under one layer's state; the attention layer
    goes through the block kernel with 20 queries a stored row."""
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    layers, slots, t = 4, 64, 4096
    net = get_decoder("jamba2_3b", num_layers=layers, dense_layers=layers,
                      attn_layer_period=4, attn_layer_offset=1,
                      vocab_size=4096, hidden_size=512, max_length=t)
    net.cast("bfloat16")
    net.initialize(init="zeros")
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(128,), name="ssm4",
                               donate=True, artifact_dir="") as sess:
        assert [(s.shape, s.dtype.name) for s in sess._kv.specs()] == [
            ((1, slots, 1, t, 128), "bfloat16")] * 2 + [
            ((3, slots, 16, 5120), "float32"),
            ((3, slots, 3, 5120), "bfloat16")]
        caches = [_spec(one_chip, s.shape, s.dtype)
                  for s in sess._kv.specs()]
        vec = _spec(one_chip, (slots,), jnp.int32)
        params = [_spec(one_chip, p.shape, p.dtype) for p in sess._params]
        compiled = jax.jit(
            sess._decode_apply, donate_argnums=(1, 2, 3, 4)).lower(
            params, *caches, vec, vec).compile()
        kv_bytes, state_bytes = sess._kv.nbytes, 3 * slots * 16 * 5120 * 4
    n = len(params)
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and all(f"{{{j + 1}}}: ({n + j}, {{}}" in alias.group(0)
                         for j in range(4)), "a cache array is not aliased"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < state_bytes // 4   # < a layer's state
    whole = "f32[3,%d,16,5120]{" % slots
    beside, layouts, fused = {}, [], False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            fused = not line.startswith("ENTRY")   # only the entry's ops
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not m.group(1).startswith(whole):
            continue
        if m.group(2) == "parameter":
            layouts.append(m.group(1)[len(whole):].split(":")[0].rstrip("}"))
        elif m.group(2) not in ("bitcast", "get-tuple-element",
                                "copy-start", "copy-done"):
            beside[m.group(2)] = beside.get(m.group(2), 0) + 1
    assert layouts == ["3,2,1,0"], layouts
    assert beside == {"fusion": 3}, beside
    assert _kernel_calls(text) == 1


def test_looped_decoder_step_attends_inside_its_pass_loop_published_widths(
        one_chip):
    """The data-built decoder's served decode step at Ouro-2.6B's widths
    (hidden 2048, 16 heads of 128 over as many K/V heads, sandwich norms,
    rotary on every layer; two layers of the 48, the vocabulary cut so
    that the weights are a test's) run 4 passes a token, 8 slots of 512
    positions, donated as ``DecodeSession`` lowers it. The passes are one
    program loop (a ``while``) whose body holds the block kernel once a
    layer, handed the whole stacked caches of the 4 x 2 (pass, layer)
    instances and a traced layer index; both caches alias their outputs,
    and outside fusions the only ops of the cache's shape are the
    in-place ``dynamic-update-slice`` of every pass's rows after the loop:
    no copy of the cache beside the one write, and temp memory under a
    tenth of it. Its table, 2048 wide, is row-major by the chip's default:
    the session relays no parameter (the counter reads 0) and the step
    takes every one in the chip's default layout, copying none."""
    from incubator_mxnet_tpu import serving, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    layers, passes, slots, t = 2, 4, 8, 512
    net = get_decoder("ouro_2p6b", num_layers=layers, dense_layers=layers,
                      vocab_size=4096, max_length=t)
    net.cast("bfloat16")
    net.initialize(init="zeros")
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(128,), name="ouro2",
                               donate=True, artifact_dir="") as sess:
        shape = (passes * layers, slots, 16, t, 128)
        assert sess._kv.shapes == [shape]
        caches = [_spec(one_chip, shape)] * 2
        vec = _spec(one_chip, (slots,), jnp.int32)
        params, formats = _served_params(one_chip, sess, net)
        lowered = jax.jit(sess._decode_apply, donate_argnums=(1, 2)).lower(
            params, *caches, vec, vec)
        compiled = lowered.compile()
        kv_bytes = sess._kv.nbytes
    assert formats == [None] * len(params)
    assert telemetry.counter("mxtpu_serving_row_tables_relaid_total",
                             session="ouro2").value == 0
    assert all(order == default
               for order, default in _orders(one_chip, compiled, params))
    assert _entry_copies_of_parameters(compiled.as_text()) == []
    # one loop, in the program and in what the compiler made of it, whose
    # body calls the kernel once a layer
    program = lowered.as_text()
    assert program.count("stablehlo.while") == 1
    assert len(re.findall(r"custom_call @tpu_custom_call[^\n]*"
                          r"kv_decode_attention", program)) == layers
    n = len(params)
    text = compiled.as_text()
    assert len(re.findall(r"\) while\(", text)) == 1
    assert _kernel_calls(text) == layers
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and all(f"{{{j + 1}}}: ({n + j}, {{}}" in alias.group(0)
                         for j in range(2)), "a cache array is not aliased"
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < kv_bytes // 10
    whole = "bf16[%d,%d,16,%d,128]{" % (passes * layers, slots, t)
    beside, fused = {}, False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            fused = not line.startswith("ENTRY")   # only the entry's ops
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not m.group(1).startswith(whole):
            continue
        if m.group(2) not in ("parameter", "bitcast", "get-tuple-element"):
            beside[m.group(2)] = beside.get(m.group(2), 0) + 1
    assert beside == {"dynamic-update-slice": 2 * slots}, beside


def test_mamba2_decoder_step_updates_its_state_in_one_fusion_published_widths(
        one_chip):
    """The data-built decoder's served decode step at Nemotron-3 Super's
    widths (hidden 4096; Mamba-2 mixers of 128 heads of 64 over 8 groups of
    state 128, the convolution over 10240 lanes; a latent expert layer
    whose router is 512 wide, top-22, 4 experts held so that the weights
    are a test's; attention of 32 query heads over 2 K/V heads of 128; the
    vocabulary cut), 64 slots of 4096 positions, donated as
    ``DecodeSession`` lowers it. Every cache array aliases its output; the
    stacked float32 state ``[2, 64, 1024, 1024]`` (``(N, G, Q)`` a slot)
    is touched by nothing but one fusion a mixer, which updates it where
    it lies, and no state-sized tensor is built beside it (temp memory
    under a quarter of one layer's state): ``B`` and ``C`` broadcast along
    a group's lanes, a channel's decay and input along ``N``. The layer
    parts run under their named scopes."""
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    slots, t = 64, 4096
    net = get_decoder("nemotron3_super", hybrid_override_pattern="ME*M",
                      experts_held=4, expert_share=0, vocab_size=4096,
                      max_length=t)
    net.cast("bfloat16")
    net.initialize(init="zeros")
    with serving.DecodeSession(net, max_slots=slots, max_len=t,
                               prefill_buckets=(128,), name="nemo4",
                               donate=True, artifact_dir="") as sess:
        assert [(s.shape, s.dtype.name) for s in sess._kv.specs()] == [
            ((1, slots, 2, t, 128), "bfloat16")] * 2 + [
            ((2, slots, 128 * 8, 1024), "float32"),
            ((2, slots, 3, 10240), "bfloat16")]
        caches = [_spec(one_chip, s.shape, s.dtype)
                  for s in sess._kv.specs()]
        vec = _spec(one_chip, (slots,), jnp.int32)
        params, _ = _served_params(one_chip, sess, net)
        compiled = jax.jit(
            sess._decode_apply, donate_argnums=(1, 2, 3, 4)).lower(
            params, *caches, vec, vec).compile()
        layer_state = slots * 128 * 8192 * 4
    n = len(params)
    text = compiled.as_text()
    alias = re.search(r"input_output_alias=\{[^\n]*?\}, entry", text)
    assert alias and all(f"{{{j + 1}}}: ({n + j}, {{}}" in alias.group(0)
                         for j in range(4)), "a cache array is not aliased"
    assert compiled.memory_analysis().temp_size_in_bytes < layer_state // 4
    whole = "f32[2,%d,1024,1024]{" % slots
    beside, fused = {}, False
    for line in text.splitlines():
        if line and not line.startswith((" ", "}")):
            fused = not line.startswith("ENTRY")   # only the entry's ops
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if fused or not m or not m.group(1).startswith(whole):
            continue
        if m.group(2) not in ("parameter", "bitcast", "get-tuple-element"):
            beside[m.group(2)] = beside.get(m.group(2), 0) + 1
    assert beside == {"fusion": 2}, beside
    for scope in ("state_space", "router", "latent_down", "grouped_product",
                  "latent_up", "shared_expert", "attention"):
        assert f"jit(_decode_apply)/{scope}/" in text, scope
    assert _kernel_calls(text) == 1


def test_held_experts_stream_their_weights_where_they_lie_nemotron_widths(
        one_chip):
    """The held experts' product at Nemotron-3 Super's decode widths (64
    rows, 128 of 512 experts held, top-22, a latent of 1024 -> 2688 relu2
    -> 1024, bfloat16) takes the dense form: no ``ragged-dot`` kernel in
    the compiled program, the expert weights are read where they lie,
    with no copy or transpose of either stacked operand anywhere in it,
    and the float32 partials of the down product (E, N, C) are never
    written out (temp memory under their size)."""
    from incubator_mxnet_tpu.ops.moe import held_form, moe_held_ffn

    n, c, f, e, k, width = 64, 1024, 2688, 128, 22, 512
    assert held_form(n, k, width) == "dense"

    def held(x, idx, weights, w_up, w_down, live):
        return moe_held_ffn(
            x, idx, weights, None, w_up, w_down, live=live,
            act=lambda a: jnp.square(jax.nn.relu(a)), width=width)

    compiled = jax.jit(held).lower(
        _spec(one_chip, (n, c)), _spec(one_chip, (n, k), jnp.int32),
        _spec(one_chip, (n, k), jnp.float32), _spec(one_chip, (e, c, f)),
        _spec(one_chip, (e, f, c)), _spec(one_chip, (n,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert "ragged" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < e * n * c * 4
    assert not _entry_copies_of_parameters(text)
    relaid = re.findall(r"= bf16\[%d,(?:%d,%d|%d,%d)\]\S* (?:copy|copy-start|"
                        r"transpose)\(" % (e, c, f, f, c), text)
    assert not relaid, relaid
