"""``mx.operator`` — custom Python operators.

Capability parity with reference ``python/mxnet/operator.py`` over
``src/operator/custom/custom.cc``: users define ``CustomOp`` (forward/
backward over NDArrays) + ``CustomOpProp`` (shape/type inference,
argument declaration), register by name, and invoke as
``mx.nd.Custom(*data, op_type=name)`` — the escape hatch for ops the
framework lacks.

TPU-native stance: the custom body runs EAGERLY in Python over NDArrays
(which dispatch to XLA per op), and autograd integration goes through a
``jax.custom_vjp`` whose forward/backward call the user's methods
directly on host arrays when eager, or via ``jax.pure_callback`` when
traced — so custom ops also work inside ``hybridize()``/jit at the cost
of a host callback per invocation (the reference pays the same host hop
into Python from its engine thread).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


class CustomOp:
    """Base class for custom operators (reference ``mx.operator.CustomOp``).
    Subclass and implement ``forward``/``backward``."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Honor grad_req semantics (reference ``CustomOp.assign``)."""
        if req == "null":
            return
        if req == "add":
            dst += src
        else:
            dst_data = src
            dst._set_data(dst_data._data if hasattr(dst_data, "_data")
                          else dst_data)


class CustomOpProp:
    """Shape/type/argument declaration (reference ``CustomOpProp``)."""

    def __init__(self, need_top_grad: bool = True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), []

    def create_operator(self, ctx, in_shapes, in_dtypes) -> CustomOp:
        raise NotImplementedError

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps


_REGISTRY: Dict[str, type] = {}


def register(op_type: str):
    """Decorator registering a CustomOpProp subclass (reference
    ``mx.operator.register``)."""

    def deco(prop_cls):
        _REGISTRY[op_type] = prop_cls
        return prop_cls

    return deco


def get_prop(op_type: str) -> Optional[type]:
    return _REGISTRY.get(op_type)


def invoke_custom(op_type: str, inputs, kwargs):
    """Run a registered custom op over NDArray inputs (the ``nd.Custom``
    entry). Differentiable via the autograd tape using the user's
    ``backward``."""
    import jax
    import jax.numpy as jnp

    from . import autograd
    from .device import current_context
    from .ndarray.ndarray import NDArray, invoke

    prop_cls = _REGISTRY.get(op_type)
    if prop_cls is None:
        raise ValueError(f"no custom op registered as {op_type!r}")
    prop = prop_cls(**kwargs)
    in_shapes = [tuple(x.shape) for x in inputs]
    in_dtypes = [x.dtype for x in inputs]
    _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    _, out_dtypes, _ = prop.infer_type(list(in_dtypes))
    op = prop.create_operator(current_context(), in_shapes, in_dtypes)
    n_out = len(prop.list_outputs())

    def run_forward(*arrays):
        """Host-side eager forward over NDArray views."""
        ins = [NDArray(jnp.asarray(a)) for a in arrays]
        outs = [NDArray(jnp.zeros(tuple(s), d))
                for s, d in zip(out_shapes, out_dtypes)]
        op.forward(is_train=True, req=["write"] * n_out, in_data=ins,
                   out_data=outs, aux=[])
        return tuple(np.asarray(o.asnumpy()) for o in outs)

    def run_backward(*arrays):
        """arrays = out_grads + in_data + out_data."""
        ogs = [NDArray(jnp.asarray(a)) for a in arrays[:n_out]]
        ins = [NDArray(jnp.asarray(a))
               for a in arrays[n_out:n_out + len(in_shapes)]]
        outs = [NDArray(jnp.asarray(a))
                for a in arrays[n_out + len(in_shapes):]]
        igs = [NDArray(jnp.zeros(tuple(s), d))
               for s, d in zip(in_shapes, in_dtypes)]
        op.backward(req=["write"] * len(igs), out_grad=ogs, in_data=ins,
                    out_data=outs, in_grad=igs, aux=[])
        return tuple(np.asarray(g.asnumpy()) for g in igs)

    import functools

    @functools.partial(jax.custom_vjp)
    def core(*arrays):
        return _call_fwd(*arrays)

    def _call_fwd(*arrays):
        if not any(isinstance(a, jax.core.Tracer) for a in arrays):
            # eager: the arrays are concrete, so run on the host
            # directly instead of through a callback
            outs = run_forward(*[np.asarray(a) for a in arrays])
            return tuple(jnp.asarray(o) for o in outs)
        out_avals = tuple(
            jax.ShapeDtypeStruct(tuple(s), d)
            for s, d in zip(out_shapes, out_dtypes))
        return jax.pure_callback(run_forward, out_avals, *arrays,
                                 vmap_method=None)

    def core_fwd(*arrays):
        outs = _call_fwd(*arrays)
        return outs, (arrays, outs)

    def core_bwd(res, gs):
        arrays, outs = res
        all_args = tuple(gs) + tuple(arrays) + tuple(outs)
        if not any(isinstance(a, jax.core.Tracer) for a in all_args):
            grads = run_backward(*[np.asarray(a) for a in all_args])
            return tuple(jnp.asarray(g) for g in grads)
        in_avals = tuple(jax.ShapeDtypeStruct(tuple(s), d)
                         for s, d in zip(in_shapes, in_dtypes))
        grads = jax.pure_callback(run_backward, in_avals, *all_args,
                                  vmap_method=None)
        return tuple(grads)

    core.defvjp(core_fwd, core_bwd)

    in_data = [x._data for x in inputs]
    concrete = not any(isinstance(a, jax.core.Tracer) for a in in_data)
    if concrete and autograd.is_recording():
        # eager + recording: run on the host and attach the tape node
        # directly with a host-side vjp — no jax.vjp trace and no
        # callback for a body that is already on the host
        outs_np = run_forward(*[np.asarray(a) for a in in_data])
        outs = [NDArray(jnp.asarray(o)) for o in outs_np]

        def vjp_fn(cts):
            cts_t = tuple(cts) if isinstance(cts, (tuple, list)) else (cts,)
            grads = run_backward(*([np.asarray(c) for c in cts_t]
                                   + [np.asarray(a) for a in in_data]
                                   + list(outs_np)))
            return tuple(jnp.asarray(g) for g in grads)

        autograd.record_op(vjp_fn, list(inputs), outs,
                           name=f"Custom[{op_type}]",
                           pure_fn=core, pure_tuple=True)
        return outs[0] if n_out == 1 else tuple(outs)

    res = invoke(lambda *a: core(*a), list(inputs), {},
                 name=f"Custom[{op_type}]")
    return res if n_out > 1 else (res if not isinstance(res, tuple)
                                  else res[0])
