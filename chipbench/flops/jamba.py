"""Operations and bytes the Jamba algorithm needs, from shapes alone: the
configuration's ``model`` block (the source's key names; ``state_dtype`` is
the file's own key: the type the scan's state is cached in).

Counted for the work the mathematics asks, whatever implements it, and LOW
where in doubt: matrix products as 2 x rows x inner x columns; the
recurrence as ``E x N x 6`` a token and state-space layer (discretise,
decay, add, read out), which ``decode_token_flops`` and ``prefill_flops``
count and the readers that go through ``matmul_params`` + ``attn_flops``
(``chipbench/reduce.py``) leave out: 13 MFLOP beside 6 GFLOP a token;
attention as a score and a weighted sum per (query head, visible key);
nothing for the convolution, the norms, softplus and the gates; the head
only where a logit is used. Bytes of a step: every weight once (the tied
table once, as the head: its rows as an embedding are a few KB), the live
K/V rows of the attention layers once and one row written a token, and
the recurrent state of each LIVE slot read once and written once AS THE
CONFIGURATION STORES IT (the scan's state in ``state_dtype``, the
convolution's taps in the served type).
"""

from __future__ import annotations

from .exaone_moe import causal_pairs, least_seconds  # noqa: F401

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
#: operations an element of the state costs a token: exp(dt A), dt x B,
#: decay times state, the add, and the read-out's multiply and add
_SCAN_OPS = 6


def _sz(model) -> dict:
    n, c = int(model["num_hidden_layers"]), int(model["hidden_size"])
    period, offset = (int(model["attn_layer_period"]),
                      int(model["attn_layer_offset"]))
    attn = sum(1 for i in range(n) if i % period == offset)
    h = int(model["num_attention_heads"])
    return {"C": c, "V": int(model["vocab_size"]), "L": n, "attn": attn,
            "mamba": n - attn, "E": int(model["mamba_expand"]) * c,
            "N": int(model["mamba_d_state"]),
            "R": int(model["mamba_dt_rank"]),
            "K": int(model["mamba_d_conv"]), "H": h,
            "Hkv": int(model["num_key_value_heads"]), "D": c // h,
            "F": int(model["intermediate_size"])}


def mixer_matmul_params(model) -> int:
    """One state-space mixer's dense products: ``W_in``, ``W_x``,
    ``W_dt``, ``W_out``."""
    s = _sz(model)
    return (2 * s["E"] * s["C"] + (s["R"] + 2 * s["N"]) * s["E"]
            + s["E"] * s["R"] + s["C"] * s["E"])


def mixer_params(model) -> int:
    """All of a mixer's parameters: the products', the convolution's
    weight and bias, ``b_dt``, ``A_log``, ``D`` and the three small
    norms' gains."""
    s = _sz(model)
    return (mixer_matmul_params(model) + s["K"] * s["E"] + s["E"]
            + s["E"] + s["N"] * s["E"] + s["E"] + s["R"] + 2 * s["N"])


def attention_params(model) -> int:
    """One attention layer's ``W_q``, ``W_k``, ``W_v``, ``W_o``."""
    s = _sz(model)
    return 2 * s["C"] * s["H"] * s["D"] + 2 * s["C"] * s["Hkv"] * s["D"]


def ffn_params(model) -> int:
    s = _sz(model)
    return 3 * s["C"] * s["F"]


def matmul_params(model) -> int:
    """Every weight a token is multiplied by (the tied table once, as the
    head)."""
    s = _sz(model)
    return (s["mamba"] * mixer_matmul_params(model)
            + s["attn"] * attention_params(model)
            + s["L"] * ffn_params(model) + s["V"] * s["C"])


def param_count(model) -> int:
    """Every parameter the model has: 3.03B at the published keys."""
    s = _sz(model)
    return (s["mamba"] * mixer_params(model)
            + s["attn"] * attention_params(model)
            + s["L"] * (ffn_params(model) + 2 * s["C"])
            + s["V"] * s["C"] + s["C"])


def scan_flops_per_token(model) -> int:
    """The recurrence of every state-space layer for one token."""
    s = _sz(model)
    return s["mamba"] * s["E"] * s["N"] * _SCAN_OPS


def attn_flops(model, queries_ctx_sum: int) -> int:
    """The pairs of tokens that saw ``queries_ctx_sum`` keys between them,
    the attention layers: a score and a weighted sum a query head."""
    s = _sz(model)
    return s["attn"] * 4 * s["H"] * s["D"] * int(queries_ctx_sum)


def decode_token_flops(model, ctx: int) -> float:
    """One generated token that sees ``ctx`` keys, itself included."""
    return 2.0 * matmul_params(model) + scan_flops_per_token(model) \
        + attn_flops(model, ctx)


def prefill_flops(model, prompt_len: int) -> float:
    """Forward pass over one prompt, the head applied once."""
    s, t = _sz(model), int(prompt_len)
    body = matmul_params(model) - s["V"] * s["C"]
    return 2.0 * t * body + 2 * s["V"] * s["C"] \
        + t * scan_flops_per_token(model) + attn_flops(model, causal_pairs(t))


def weight_bytes(model, itemsize: int) -> int:
    """Bytes of weights every step reads: all of them."""
    return itemsize * param_count(model)


def kv_bytes_per_token(model, itemsize: int) -> int:
    """K and V of one position, the attention layers."""
    s = _sz(model)
    return s["attn"] * 2 * s["Hkv"] * s["D"] * itemsize


def state_bytes_per_slot(model, itemsize: int) -> int:
    """What a sequence carries from step to step in the state-space
    layers, as the configuration stores it: the scan's ``N x E`` state in
    ``state_dtype`` and the convolution's ``K - 1`` last inputs in the
    served type, every layer."""
    s = _sz(model)
    return s["mamba"] * s["E"] * (
        s["N"] * _ITEMSIZE[model["state_dtype"]] + (s["K"] - 1) * itemsize)


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> float:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights once
    a step, the live K/V rows once and one new row a token, each advanced
    sequence's state once in and once out."""
    return (steps * weight_bytes(model, itemsize)
            + (ctx_sum + tokens) * kv_bytes_per_token(model, itemsize)
            + 2 * tokens * state_bytes_per_slot(model, itemsize))


def prefill_bytes(model, prompt_len: int, itemsize: int) -> float:
    """The weights once, the K/V rows the cache keeps of the prompt and
    the state it leaves."""
    return weight_bytes(model, itemsize) \
        + int(prompt_len) * kv_bytes_per_token(model, itemsize) \
        + state_bytes_per_slot(model, itemsize)
