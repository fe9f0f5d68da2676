"""Mixture-of-Experts ops — the EP (expert parallelism) compute core.

SURVEY.md §2.4 EP row: the reference has no MoE at all ("❌ (no MoE)");
this is a new TPU-native capability. The design is the GShard/Switch
einsum formulation — top-k gating, capacity-bounded dispatch expressed as
dense one-hot einsums — because it is exactly the shape XLA SPMD
partitions well: with the stacked expert weights sharded
``P('expert', ...)`` and a sharding constraint on the dispatched
activations, the ``nec,nd->ecd`` dispatch einsum lowers to the AllToAll
over the ``expert`` mesh axis (ICI), with no manual collective code.

Capacity semantics: each expert processes at most
``C = ceil(k * N / E * capacity_factor)`` tokens; overflow tokens are
dropped (contribute zero for that expert choice), matching Switch/GShard.
Priority is choice-major (all tokens' first choices queue before any
second choice).

Beside it, the dropless form a served sparse decoder uses
(``moe_route`` + ``moe_held_ffn``): the router scores every expert of the
layer, the layer is told which contiguous share of them it holds, and it
computes that share's part of the result for every token that chose one
of them: the token-choices are sorted by held expert and each projection
is ONE grouped product (``jax.lax.ragged_dot``) over the sorted rows.
No capacity, nothing dropped; what the experts held elsewhere would add
is not computed and not stood in for.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register


def _expert_constraint(x):
    """If the ambient mesh has an 'expert' axis, constrain the leading
    (expert) dim of x onto it so XLA partitions expert compute and inserts
    the dispatch/return AllToAll over ICI."""
    from ..parallel.mesh import EXPERT_AXIS, current_mesh
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = current_mesh()
    if mesh is not None and EXPERT_AXIS in mesh.axis_names:
        spec = PartitionSpec(EXPERT_AXIS, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return x


@register("moe_gate_dispatch")
def moe_gate_dispatch(logits, k=2, capacity_factor=1.25, capacity=0):
    """Top-k gating + capacity-bounded dispatch/combine tensors.

    ``logits``: (N, E). Returns ``(dispatch, combine, aux_loss)`` where
    ``dispatch`` (N, E, C) is the 0/1 routing tensor, ``combine`` (N, E, C)
    carries the renormalized top-k gate probabilities, and ``aux_loss`` is
    the Switch load-balancing loss ``E * sum_e(f_e * P_e)``.
    """
    N, E = logits.shape
    k = int(min(k, E))
    C = int(capacity) if capacity else max(
        1, int(math.ceil(k * N / E * capacity_factor)))

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)              # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # (N, k, E)

    # queue position per (token, choice) within its expert, choice-major
    flat = oh.transpose(1, 0, 2).reshape(k * N, E)
    pos = jnp.cumsum(flat, axis=0) - flat                 # (k*N, E)
    pos = pos.reshape(k, N, E).transpose(1, 0, 2)         # (N, k, E)
    pos_in_expert = (pos * oh).sum(-1).astype(jnp.int32)  # (N, k)
    # one_hot is all-zero past C -> capacity overflow drops automatically
    pos_oh = jax.nn.one_hot(pos_in_expert, C, dtype=jnp.float32)

    dispatch = jnp.einsum("nke,nkc->nec", oh, pos_oh)
    combine = jnp.einsum("nke,nkc,nk->nec", oh, pos_oh, gate_vals)

    # fraction of tokens ASSIGNED to each expert — pre-capacity, per the
    # Switch/GShard definition: clamping f at C/(N*k) would attenuate the
    # balancing gradient exactly when an expert overflows
    f = oh.sum((0, 1)) / max(N * k, 1)
    P = probs.mean(0)
    aux_loss = E * jnp.sum(f * P)
    return dispatch, combine, aux_loss


@register("moe_ffn")
def moe_ffn(x, gate_w, w1, b1, w2, b2, k=2, capacity_factor=1.25,
            capacity=0, activation="gelu"):
    """Mixture-of-experts positionwise FFN.

    ``x``: (..., d); ``gate_w``: (d, E); expert weights stacked on a
    leading expert axis: ``w1`` (E, d, h), ``b1`` (E, h), ``w2`` (E, h, d),
    ``b2`` (E, d). Returns ``(y, aux_loss)`` with ``y.shape == x.shape``.

    Under a mesh with an ``expert`` axis (and expert weights sharded
    ``P('expert', ...)``) the dispatched activations are constrained onto
    that axis, so XLA lowers dispatch/return to AllToAll over ICI — the
    EP communication path with zero manual collectives.
    """
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    E = w1.shape[0]

    logits = (xf @ gate_w.astype(xf.dtype)).astype(jnp.float32)
    dispatch, combine, aux_loss = moe_gate_dispatch(
        logits, k=k, capacity_factor=capacity_factor, capacity=capacity)
    dispatch = dispatch.astype(xf.dtype)
    combine = combine.astype(xf.dtype)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
    expert_in = _expert_constraint(expert_in)
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
    if activation == "gelu":
        h = jax.nn.gelu(h)
    elif activation == "relu":
        h = jax.nn.relu(h)
    elif activation in (None, "identity", "none"):
        pass
    else:
        raise ValueError(f"unsupported moe activation {activation!r}")
    h = _expert_constraint(h)
    out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    out = _expert_constraint(out)
    y = jnp.einsum("nec,ecd->nd", combine, out)
    return y.reshape(orig_shape), aux_loss.astype(jnp.float32)


def moe_route(logits, k, bias=None, scale=1.0):
    """The ``k`` experts each token chooses and the weights of the choice.

    ``logits`` (N, E) float32 over ALL the layer's experts. Scores are
    ``sigmoid(logits)``; the choice is the ``k`` largest of ``scores +
    bias`` (``bias`` (E,): used for the choice only); the weights are the
    chosen scores over their sum, times ``scale``. Returns ``(idx (N, k)
    int32, weights (N, k) f32)``.
    """
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(pick, int(k))
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * float(scale)


def moe_held_ffn(x, idx, weights, w_gate, w_up, w_down, first_expert=0,
                 live=None):
    """The held experts' part of a sparse SiLU-gated FFN, dropless.

    ``x`` (N, C); ``idx``/``weights`` (N, k) from :func:`moe_route` (ids
    over all the layer's experts); ``w_gate``/``w_up`` (E, C, F) and
    ``w_down`` (E, F, C) the ``E`` experts held here, which are experts
    ``first_expert .. first_expert + E - 1`` of the layer. The N x k
    token-choices are sorted by held expert (those that fell on experts
    held elsewhere go last and belong to no group) and each projection is
    one grouped product over the sorted rows; every token is served by
    every held expert it chose. Returns ``(y (N, C) float32, counts)``;
    ``counts`` are int32 scalars of the routing: ``routed_here``
    (token-choices on held experts), ``experts_hit`` (held experts with
    at least one token), ``load_max`` (most tokens on one held expert),
    over the tokens where ``live`` (N,) is true (all, by default).
    """
    n, k = idx.shape
    e = w_gate.shape[0]
    local = idx - int(first_expert)
    held = (local >= 0) & (local < e)
    key = jnp.where(held, local, e).reshape(-1)            # (N*k,)
    order = jnp.argsort(key, stable=True)
    hits = jax.nn.one_hot(key, e + 1, dtype=jnp.int32)      # (N*k, E+1)
    sizes = hits.sum(0)[:e]
    rows = jnp.take(x, order // k, axis=0)                  # (N*k, C)
    # 16-bit operands hold nothing a higher contract precision could
    # recover, and the TPU's ragged-dot kernel refuses the pair ("Bad lhs
    # type" under an ambient ``highest``): ask for what they can give
    low = jnp.dtype(x.dtype).itemsize < 4
    grouped = functools.partial(
        jax.lax.ragged_dot, group_sizes=sizes,
        precision=jax.lax.Precision.DEFAULT if low else None)
    h = jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up)
    out = grouped(h, w_down, preferred_element_type=jnp.float32)
    # back to (token, choice) order. Rows of no group are NOT computed:
    # the TPU's kernel leaves whatever lay there (NaN included), so they
    # are selected out, never multiplied by a zero weight
    back = jnp.take(out, jnp.argsort(order), axis=0).reshape(n, k, -1)
    back = jnp.where(held[..., None], back, 0.0)
    y = jnp.einsum("nkc,nk->nc", back, weights)
    load = sizes if live is None else \
        (hits * live.astype(jnp.int32).repeat(k)[:, None]).sum(0)[:e]
    counts = {"routed_here": load.sum(), "experts_hit": (load > 0).sum(),
              "load_max": load.max()}
    return y, {name: v.astype(jnp.int32) for name, v in counts.items()}
