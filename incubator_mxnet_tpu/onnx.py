"""``mx.onnx`` — deployment-interchange export/import.

Capability parity with reference ``python/mxnet/onnx`` (``mx2onnx``
export / ``onnx2mx`` import): the reference translates symbol graphs to
the ONNX interchange format for serving runtimes. No onnx package exists
in this environment, and the TPU-native serving format is **StableHLO**
(XLA's stable portable IR, produced via ``jax.export``) — so
``export_model`` emits a single serialized StableHLO artifact with the
parameters embedded as constants, loadable by any PJRT runtime (or back
here with ``import_model``). The API mirrors the reference's
file-oriented signature.

    mx.onnx.export_model("net-symbol.json", "net-0000.params",
                         [(1, 3, 224, 224)], "float32", "net.stablehlo")
    fn = mx.onnx.import_model("net.stablehlo")
    out = fn(x_numpy)
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np


def export_model(sym, params, in_shapes=None, in_types="float32",
                 onnx_file_path="model.stablehlo", verbose=False,
                 dynamic=False, run_shape_inference=False):
    """Serialize a symbol+params (file paths or objects) to StableHLO
    (reference ``mx.onnx.export_model`` signature). Returns the path."""
    import jax
    import jax.numpy as jnp
    from jax import export as jexport

    from . import ndarray as ndmod
    from .gluon.block import SymbolBlock
    from .ndarray import NDArray

    if isinstance(sym, str):
        from . import symbol as sym_mod

        symbol = sym_mod.load(sym)
    else:
        symbol = sym
    if isinstance(params, str):
        loaded = ndmod.load(params)
    else:
        loaded = {k: (v if isinstance(v, NDArray) else NDArray(
            jnp.asarray(v))) for k, v in params.items()}

    input_names = [n for n in symbol.list_arguments() if n not in loaded]
    if in_shapes is None:
        raise ValueError("in_shapes is required (one per graph input: "
                         f"{input_names})")
    if isinstance(in_types, (str, np.dtype, type)):
        in_types = [in_types] * len(in_shapes)

    blk = SymbolBlock(symbol, [__import__(
        "incubator_mxnet_tpu.symbol", fromlist=["var"]).var(n)
        for n in input_names])
    blk_params = blk._collect_params_with_prefix()
    for name, p in blk_params.items():
        if name in loaded:
            p.set_data(loaded[name])
        else:
            raise ValueError(f"params file missing {name!r}")

    def pure(*xs):
        outs = blk(*[NDArray(x) for x in xs])
        if isinstance(outs, tuple):
            return tuple(o._data for o in outs)
        return outs._data

    args = [jnp.zeros(s, dtype=t) for s, t in zip(in_shapes, in_types)]
    exported = jexport.export(jax.jit(pure))(*args)
    blob = exported.serialize()
    with open(onnx_file_path, "wb") as f:
        f.write(blob)
    if verbose:
        print(f"exported {len(blob)} bytes of StableHLO to "
              f"{onnx_file_path} (inputs {input_names})")
    return onnx_file_path


def export_for_pjrt_c(net, example_inputs, prefix: str,
                      params_file: Optional[str] = None) -> str:
    """Export a gluon Block for the NATIVE (C) inference path — the
    reference's "load a symbol+params and run it through the C API"
    deployment story (src/c_api/c_predict_api.cc MXPredCreate), redone
    TPU-first: the graph ships as raw StableHLO bytecode that any PJRT
    runtime compiles directly, weights stay in the ``.params``
    checkpoint (NOT baked as constants), and a text manifest records the
    call convention. ``examples/cpp/mxtpu_infer_demo.cc`` consumes all
    three through ``libmxtpu_io.so`` + the PJRT library (``libtpu.so``).

    Writes ``<prefix>.stablehlo`` (mlir bytecode), ``<prefix>.copts``
    (serialized xla CompileOptionsProto), ``<prefix>.manifest``, and —
    unless ``params_file`` points at an existing checkpoint —
    ``<prefix>.params``. Returns the manifest path.

    Manifest grammar (one token-separated record per line)::

        mxtpu-pjrt v1
        input param <checkpoint-key> <typeflag> <ndim> <dims...>
        input data <j> <typeflag> <ndim> <dims...>
        output <i> <typeflag> <ndim> <dims...>
    """
    import jax
    import jax.numpy as jnp
    from jax import export as jexport
    from jax._src.lib import xla_client as xc

    from . import ndarray as ndmod
    from .ndarray import NDArray
    from .parallel.spmd import collect_params, functional_apply

    if not isinstance(example_inputs, (list, tuple)):
        example_inputs = [example_inputs]
    ex = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
          for a in example_inputs]

    objs = collect_params(net)
    names = list(objs)
    pvals = [objs[n]._data._data for n in names]

    def pure(pargs, xs):
        # functional_apply unwraps to a single jax array (single-output
        # inference contract, like the reference predict C API)
        out, _ = functional_apply(net, objs, dict(zip(names, pargs)), *xs)
        return (out,)

    exported = jexport.export(jax.jit(pure))(
        [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in pvals],
        [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in ex])
    with open(prefix + ".stablehlo", "wb") as f:
        f.write(exported.mlir_module_serialized)
    with open(prefix + ".copts", "wb") as f:
        f.write(xc.CompileOptions().SerializeAsString())

    if params_file is None:
        ndmod.save(prefix + ".params",
                   {n: NDArray(v) for n, v in zip(names, pvals)})

    from .native import _DTYPE_CODES  # one shared TypeFlag table

    def _rec(kind, ident, v):
        tf = _DTYPE_CODES.get(str(v.dtype))
        if tf is None:
            raise ValueError(f"dtype {v.dtype} has no TypeFlag code")
        dims = " ".join(str(int(d)) for d in v.shape)
        return f"{kind} {ident} {tf} {len(v.shape)}" + \
            (f" {dims}" if dims else "")

    lines = ["mxtpu-pjrt v1"]
    lines += [_rec("input param", n, v) for n, v in zip(names, pvals)]
    lines += [_rec("input data", j, v) for j, v in enumerate(ex)]
    out_avals = exported.out_avals
    lines += [_rec("output", i, v) for i, v in enumerate(out_avals)]
    with open(prefix + ".manifest", "w") as f:
        f.write("\n".join(lines) + "\n")
    return prefix + ".manifest"


def import_model(model_file: str):
    """Load a StableHLO artifact back as a callable (reference
    ``onnx2mx`` import capability; runs via XLA on the current device)."""
    from jax import export as jexport

    with open(model_file, "rb") as f:
        exported = jexport.deserialize(f.read())

    def fn(*args):
        import jax.numpy as jnp

        from .ndarray import NDArray

        arrs = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in args]
        out = exported.call(*arrs)
        if isinstance(out, (tuple, list)):
            outs = [NDArray(o) for o in out]
            return outs[0] if len(outs) == 1 else tuple(outs)
        return NDArray(out)

    fn.exported = exported
    return fn
