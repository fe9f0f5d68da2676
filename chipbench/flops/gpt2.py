"""Operations and bytes the GPT-2 algorithm needs, from shapes alone.

Counted for the work the mathematics asks for, whatever implements it:
matrix products as 2 x rows x inner x columns, causal attention over the
keys a query may see and no others, the output head only where a logit is
used. Nothing is counted twice for recomputation, and nothing for
elementwise work (LayerNorm, GELU, softmax), so a share of a peak worked
out from these cannot be inflated by the count. ``model`` is the
configuration file's ``model`` block (Hugging Face key names).
"""

from __future__ import annotations


def _sz(model):
    c = int(model["n_embd"])
    return (c, int(model["n_layer"]), int(model["vocab_size"]),
            int(model.get("n_inner") or 4 * c))


def layer_matmul_params(model) -> int:
    """Weights of one block's four matrix products: QKV, proj, MLP."""
    c, _, _, f = _sz(model)
    return 3 * c * c + c * c + 2 * c * f


def matmul_params(model) -> int:
    """Every weight that a token is multiplied by: blocks and head."""
    c, n_layer, v, _ = _sz(model)
    return n_layer * layer_matmul_params(model) + v * c


def param_count(model) -> int:
    c, n_layer, v, f = _sz(model)
    per_layer = layer_matmul_params(model) + (3 * c + c + f + c) + 4 * c
    return (v * c + int(model["n_positions"]) * c + n_layer * per_layer
            + 2 * c + v * c)


def attn_flops(model, queries_ctx_sum: int) -> int:
    """QK^T and AV over ``queries_ctx_sum`` (query, visible key) pairs,
    all layers: 2 products x 2 x C each pair."""
    c, n_layer, _, _ = _sz(model)
    return 4 * c * n_layer * int(queries_ctx_sum)


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def forward_flops_sequence(model, t: int, logits_at: int) -> int:
    """Forward pass over one sequence of ``t`` tokens with the head
    applied at ``logits_at`` positions (``t`` in training, 1 in prefill)."""
    c, n_layer, v, _ = _sz(model)
    return (2 * t * n_layer * layer_matmul_params(model)
            + 2 * logits_at * v * c + attn_flops(model, causal_pairs(t)))


def train_step_flops(model, batch: int, seq: int) -> int:
    """Forward + backward (2x forward): 3 x forward, no recomputation."""
    return 3 * batch * forward_flops_sequence(model, seq, seq)


def prefill_flops(model, prompt_len: int) -> int:
    return forward_flops_sequence(model, prompt_len, 1)


def decode_token_flops(model, ctx: int) -> int:
    """One generated token that sees ``ctx`` keys, itself included."""
    return 2 * matmul_params(model) + attn_flops(model, ctx)


def weight_bytes(model, itemsize: int) -> int:
    """Bytes every step must read: block and head weights (the embedding
    tables are read a row at a time and are left out)."""
    return matmul_params(model) * itemsize


def kv_bytes_per_token(model, itemsize: int) -> int:
    """K and V of one position, all layers."""
    c, n_layer, _, _ = _sz(model)
    return 2 * n_layer * c * itemsize


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> int:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights once
    a step, the live cache once, one new row written per token."""
    kv = kv_bytes_per_token(model, itemsize)
    return steps * weight_bytes(model, itemsize) + (ctx_sum + tokens) * kv


def prefill_bytes(model, prompt_len: int, itemsize: int) -> int:
    return (weight_bytes(model, itemsize)
            + prompt_len * kv_bytes_per_token(model, itemsize))


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The roofline: ``(seconds, "compute" | "bandwidth")``."""
    tc = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
