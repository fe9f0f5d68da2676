"""Operations and bytes the Ouro LoopLM algorithm needs, from shapes alone:
the configuration's ``model`` block (the source's key names), with
``total_ut_steps`` (R) the passes a token makes through the stack.

Counted for the work the mathematics asks, whatever implements it: matrix
products as 2 x rows x inner x columns, every layer's once a PASS (the same
weights multiply a different input in each pass); attention as a score and
a weighted sum per (query head, visible key) over R x L (pass, layer)
instances, each with keys of its own; nothing for the norms, RoPE and the
gates; the head only where a logit is used.

Bytes of a step: the stack's weights R times, the head once, the live K/V
rows of all R x L instances once and one row written a token and instance.
The passes are not "LOW where in doubt" here: no implementation can read
the stack once for all R passes, since pass r + 1's input is pass r's
output, and 4.93 GB of stack does not stay on the chip between passes
(128 MiB of VMEM). Staggering slots across passes (each slot at another
pass of the same step) reads each layer once a step for R slot-passes, but
a token still needs R passes: the bytes per token are the same.
"""

from __future__ import annotations

from .exaone_moe import causal_pairs, least_seconds  # noqa: F401


def _sz(model) -> dict:
    return {"C": int(model["hidden_size"]), "V": int(model["vocab_size"]),
            "L": int(model["num_hidden_layers"]),
            "R": int(model["total_ut_steps"]),
            "q": int(model["num_attention_heads"]) * int(model["head_dim"]),
            "kv": int(model["num_key_value_heads"]) * int(model["head_dim"]),
            "F": int(model["intermediate_size"])}


def attention_params(model) -> int:
    """One layer's q, k, v and o projections."""
    s = _sz(model)
    return 2 * s["C"] * s["q"] + 2 * s["C"] * s["kv"]


def ffn_params(model) -> int:
    s = _sz(model)
    return 3 * s["C"] * s["F"]


def layer_params(model) -> int:
    """One layer's products and its four norm gains (the sandwich's)."""
    return attention_params(model) + ffn_params(model) \
        + 4 * _sz(model)["C"]


def matmul_params(model) -> int:
    """Every weight a token is multiplied by: the stack's once a pass, the
    head once."""
    s = _sz(model)
    return s["R"] * s["L"] * (attention_params(model) + ffn_params(model)) \
        + s["V"] * s["C"]


def param_count(model) -> int:
    """Every parameter the model has, once: 2,667,972,608 at the
    published keys (48 layers, the table, the untied head, the final
    norm)."""
    s = _sz(model)
    return s["L"] * layer_params(model) + 2 * s["V"] * s["C"] + s["C"]


def attn_flops(model, queries_ctx_sum: int) -> int:
    """The pairs of tokens that saw ``queries_ctx_sum`` keys between them,
    in every (pass, layer): a score and a weighted sum a query head."""
    s = _sz(model)
    return s["R"] * s["L"] * 4 * s["q"] * int(queries_ctx_sum)


def decode_token_flops(model, ctx: int) -> float:
    """One generated token that sees ``ctx`` keys, itself included."""
    return 2.0 * matmul_params(model) + attn_flops(model, ctx)


def prefill_flops(model, prompt_len: int) -> float:
    """Forward pass over one prompt, R passes, the head applied once."""
    s, t = _sz(model), int(prompt_len)
    body = matmul_params(model) - s["V"] * s["C"]
    return 2.0 * t * body + 2 * s["V"] * s["C"] \
        + attn_flops(model, causal_pairs(t))


def weight_bytes(model, itemsize: int) -> int:
    """Bytes of weights a step reads: the stack (products, gains) and the
    final norm once a pass, the head once; 19.93 GB in bfloat16 at the
    published keys (the table is read a row a token: left out)."""
    s = _sz(model)
    return itemsize * (s["R"] * (s["L"] * layer_params(model) + s["C"])
                       + s["V"] * s["C"])


def kv_bytes_per_token(model, itemsize: int) -> int:
    """K and V of one position, every (pass, layer): 1,572,864 B in
    bfloat16 at the published keys, four times one pass's."""
    s = _sz(model)
    return s["R"] * s["L"] * 2 * s["kv"] * itemsize


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> float:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights as
    :func:`weight_bytes` once a step, the live rows of every instance once
    and one new row a token and instance."""
    return steps * weight_bytes(model, itemsize) \
        + (ctx_sum + tokens) * kv_bytes_per_token(model, itemsize)


def prefill_bytes(model, prompt_len: int, itemsize: int) -> float:
    """The weights as a step reads them (R passes over the stack) and the
    K/V rows the cache keeps of the prompt, every instance."""
    return weight_bytes(model, itemsize) \
        + int(prompt_len) * kv_bytes_per_token(model, itemsize)
