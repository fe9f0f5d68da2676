"""Operations and bytes the Nemotron-H algorithm needs ON THIS CHIP, from
shapes alone: one chip's share of an expert-parallel deployment, as the
configuration's ``model`` block states it (the source's key names;
``n_routed_experts`` is the experts HELD, ``expert_share["of"]`` the chips
that share a layer, so the router is ``n_routed_experts * of`` wide;
``state_dtype`` is the file's own key: the type the scan's state is cached
in). A layer is one of a Mamba-2 mixer (``M``), a latent expert layer
(``E``) or attention (``*``), as ``hybrid_override_pattern`` says.

Counted for the work the mathematics asks of this chip, whatever
implements it, and LOW where in doubt: matrix products as 2 x rows x inner
x columns; of the routed experts, those a token uses HERE (``k * held /
width`` on average: 5.5 of its 22); the recurrence as ``E x N x 5`` a token
and mixer (decay times state, input times ``B``, the add, the read-out's
multiply and add), which ``decode_token_flops`` and ``prefill_flops``
count and the readers that go through ``matmul_params`` + ``attn_flops``
(``chipbench/reduce.py``) leave out; the prefill's chunked form does more
than the recurrence and is counted as the recurrence; attention as a score
and a weighted sum per (query head, visible key); nothing for the
convolution, the norms, softplus and the gates; the head only where a
logit is used. Bytes of a step: every weight but the routed experts once
(the embedding table is read a row at a time and is left out), the held
experts that at least one of the step's tokens chose (under the router's
even share: a fact of the traffic, not of the implementation), the live
K/V rows once and one row written a token, and the recurrent state of each
LIVE slot read once and written once AS THE CONFIGURATION STORES IT (the
scan's state in ``state_dtype``, the convolution's taps in the served
type).
"""

from __future__ import annotations

from .exaone_moe import causal_pairs, least_seconds  # noqa: F401

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
#: operations an element of the state costs a token: decay times state,
#: the input term times B, the add, and the read-out's multiply and add
_SCAN_OPS = 5


def _sz(model) -> dict:
    n = int(model["num_hidden_layers"])
    kinds = model["hybrid_override_pattern"][:n]
    h, p = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    g, ns = int(model["n_groups"]), int(model["ssm_state_size"])
    held = int(model["n_routed_experts"])
    return {"C": int(model["hidden_size"]), "V": int(model["vocab_size"]),
            "M": kinds.count("M"), "E_layers": kinds.count("E"),
            "A": kinds.count("*"), "H": h, "E": h * p, "N": ns,
            "K": int(model["conv_kernel"]), "conv": h * p + 2 * g * ns,
            "q": int(model["num_attention_heads"]) * int(model["head_dim"]),
            "kv": int(model["num_key_value_heads"]) * int(model["head_dim"]),
            "held": held, "width": held * int(model["expert_share"]["of"]),
            "k": int(model["num_experts_per_tok"]),
            "Fe": int(model["moe_intermediate_size"]),
            "Lt": int(model["moe_latent_size"]),
            "Fs": int(model["moe_shared_expert_intermediate_size"])}


def mixer_matmul_params(model) -> int:
    """One Mamba-2 mixer's dense products: ``W_in`` and ``W_out``."""
    s = _sz(model)
    return (s["E"] + s["conv"] + s["H"]) * s["C"] + s["C"] * s["E"]


def mixer_params(model) -> int:
    """All of a mixer layer's parameters: the products', the convolution's
    weight and bias, ``b_dt``, ``A_log``, ``D``, the gated norm's and the
    layer norm's gains."""
    s = _sz(model)
    return (mixer_matmul_params(model) + (s["K"] + 1) * s["conv"]
            + 3 * s["H"] + s["E"] + s["C"])


def attention_params(model) -> int:
    """One attention layer's ``W_q``, ``W_k``, ``W_v``, ``W_o``."""
    s = _sz(model)
    return 2 * s["C"] * s["q"] + 2 * s["C"] * s["kv"]


def expert_params(model) -> int:
    """One routed expert, in the latent: up and down."""
    s = _sz(model)
    return 2 * s["Lt"] * s["Fe"]


def expert_layer_dense_params(model) -> int:
    """What an expert layer multiplies every token by, whatever the router
    says: the router, the two latent projections, the shared expert."""
    s = _sz(model)
    return s["width"] * s["C"] + 2 * s["C"] * s["Lt"] + 2 * s["C"] * s["Fs"]


def always_read_params(model) -> int:
    """Weights every step reads whole: every layer's but the routed
    experts, gains and biases included, the final norm and the head."""
    s = _sz(model)
    return (s["M"] * mixer_params(model)
            + s["A"] * (attention_params(model) + s["C"])
            + s["E_layers"] * (expert_layer_dense_params(model) + s["width"]
                               + s["C"])
            + s["C"] + s["V"] * s["C"])


def routed_here_per_token(model) -> float:
    """Held experts a token uses, on average, under the router's even
    share: ``k * held / width`` (5.5 for 22 of 512 with 128 held)."""
    s = _sz(model)
    return s["k"] * s["held"] / s["width"]


def matmul_params(model) -> float:
    """Every weight a token is multiplied by on this chip (the head
    included)."""
    s = _sz(model)
    return (s["M"] * mixer_matmul_params(model)
            + s["A"] * attention_params(model)
            + s["E_layers"] * (expert_layer_dense_params(model)
                               + routed_here_per_token(model)
                               * expert_params(model))
            + s["V"] * s["C"])


def param_count(model) -> int:
    """Parameters resident on the chip: 4.65B at the configuration's
    keys."""
    s = _sz(model)
    return (always_read_params(model) + s["V"] * s["C"]
            + s["E_layers"] * s["held"] * expert_params(model))


def scan_flops_per_token(model) -> int:
    """The recurrence of every mixer for one token."""
    s = _sz(model)
    return s["M"] * s["E"] * s["N"] * _SCAN_OPS


def attn_flops(model, queries_ctx_sum: int) -> int:
    """QK^T and AV of tokens that saw ``queries_ctx_sum`` keys between
    them, the attention layers."""
    s = _sz(model)
    return s["A"] * 4 * s["q"] * int(queries_ctx_sum)


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


def experts_hit_share(model, tokens_per_step: float) -> float:
    """Share of the held experts that at least one of a step's tokens
    chose, under the router's even share: ``1 - (1 - k / width) ** n``
    (0.94 for 64 tokens, 22 of 512)."""
    s = _sz(model)
    return 1.0 - (1.0 - s["k"] / s["width"]) ** float(tokens_per_step)


def weight_bytes(model, itemsize: int, tokens_per_step: float = 1.0):
    """Bytes of weights a step of ``tokens_per_step`` tokens must read:
    what every step reads, and the held experts that were hit."""
    s = _sz(model)
    hit = experts_hit_share(model, tokens_per_step)
    return itemsize * (always_read_params(model) + hit * s["E_layers"]
                       * s["held"] * expert_params(model))


def kv_bytes_per_token(model, itemsize: int) -> int:
    """K and V of one position, the attention layers."""
    s = _sz(model)
    return s["A"] * 2 * s["kv"] * itemsize


def state_bytes_per_slot(model, itemsize: int) -> int:
    """What a sequence carries from step to step in the mixers, as the
    configuration stores it: the scan's ``N x E`` state in
    ``state_dtype`` and the convolution's ``K - 1`` last inputs of ``x``,
    ``B`` and ``C`` in the served type, every mixer."""
    s = _sz(model)
    return s["M"] * (s["N"] * s["E"] * _ITEMSIZE[model["state_dtype"]]
                     + (s["K"] - 1) * s["conv"] * itemsize)


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> float:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights of a
    step of the mean size once a step, the live K/V rows once and one new
    row a token, each advanced sequence's state once in and once out."""
    return (steps * weight_bytes(model, itemsize, tokens / max(1, steps))
            + (ctx_sum + tokens) * kv_bytes_per_token(model, itemsize)
            + 2 * tokens * state_bytes_per_slot(model, itemsize))


def prefill_bytes(model, prompt_len: int, itemsize: int) -> float:
    """The weights once (the experts the prompt's tokens hit), the K/V
    rows the cache keeps of the prompt and the state it leaves."""
    t = int(prompt_len)
    return weight_bytes(model, itemsize, t) \
        + t * kv_bytes_per_token(model, itemsize) \
        + state_bytes_per_slot(model, itemsize)
