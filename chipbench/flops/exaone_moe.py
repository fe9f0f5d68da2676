"""Operations and bytes the EXAONE-MoE algorithm needs ON THIS CHIP, from
shapes alone: one chip's share of an expert-parallel deployment, as the
configuration's ``model`` block states it (Hugging Face key names;
``num_experts`` is the experts HELD, ``expert_share["of"]`` the chips
that share a layer, so the router is ``num_experts * of`` wide).

Counted for the work the mathematics asks of this chip, whatever
implements it: matrix products as 2 x rows x inner x columns; of the
routed experts, those a token uses HERE (``num_experts_per_tok *
num_experts / router width`` of them on average: one of its eight);
causal attention over the keys a query may see, on window layers at most
``sliding_window`` of them; the head only where a logit is used. Bytes of
a step: what every step reads, the held experts that at least one of the
step's tokens chose (under the router's even share: a fact of the
traffic, not of the implementation), the live K/V rows. Nothing for
elementwise work, and where a sum cannot be had from what the readers
pass it is counted LOW (each place says so), so a share of a peak worked
out from these cannot be inflated by the count.
"""

from __future__ import annotations


def _sz(model) -> dict:
    n = int(model["num_hidden_layers"])
    attn = model["layer_types"][:n]
    held = int(model["num_experts"])
    width = held * int(model["expert_share"]["of"])
    return {
        "C": int(model["hidden_size"]), "V": int(model["vocab_size"]),
        "q": int(model["num_attention_heads"]) * int(model["head_dim"]),
        "kv": int(model["num_key_value_heads"]) * int(model["head_dim"]),
        "F": int(model["intermediate_size"]),
        "Fe": int(model["moe_intermediate_size"]),
        "held": held, "width": width,
        "k": int(model["num_experts_per_tok"]),
        "shared": int(model["num_shared_experts"]),
        "W": int(model["sliding_window"]),
        "full": sum(1 for a in attn if a == "full_attention"),
        "window": sum(1 for a in attn if a == "sliding_attention"),
        "dense": sum(1 for m in model["mlp_layer_types"][:n]
                     if m == "dense"),
        "sparse": sum(1 for m in model["mlp_layer_types"][:n]
                      if m == "sparse")}


def attention_params(model) -> int:
    """One layer's q, k, v and o projections."""
    s = _sz(model)
    return 2 * s["C"] * s["q"] + 2 * s["C"] * s["kv"]


def expert_params(model) -> int:
    """One routed (or shared) expert: gate, up, down."""
    s = _sz(model)
    return 3 * s["C"] * s["Fe"]


def always_read_params(model) -> int:
    """Weights every token is multiplied by, whatever the router says:
    attention, the dense FFN, router, shared expert, head."""
    s = _sz(model)
    layers = s["full"] + s["window"]
    return (layers * attention_params(model)
            + s["dense"] * 3 * s["C"] * s["F"]
            + s["sparse"] * (s["C"] * s["width"]
                             + s["shared"] * expert_params(model))
            + s["V"] * s["C"])


def routed_here_per_token(model) -> float:
    """Held experts a token uses, on average, under the router's even
    share: ``k * held / width`` (1 for 8 of 128 with 16 held)."""
    s = _sz(model)
    return s["k"] * s["held"] / s["width"]


def matmul_params(model) -> float:
    """Every weight a token is multiplied by on this chip."""
    s = _sz(model)
    return always_read_params(model) + s["sparse"] * \
        routed_here_per_token(model) * expert_params(model)


def param_count(model) -> int:
    """Parameters resident on the chip (norm gains and the router's bias
    left out: thousands beside billions)."""
    s = _sz(model)
    return (always_read_params(model) + s["V"] * s["C"]
            + s["sparse"] * s["held"] * expert_params(model))


def attn_flops(model, queries_ctx_sum: int) -> int:
    """QK^T and AV of generated tokens that saw ``queries_ctx_sum`` keys
    between them, on the FULL-attention layers: 2 products x 2 x (heads x
    head_dim) each pair. The window layers' pairs are the sum of
    min(context, window) over the tokens, which the sum of contexts does
    not give: they are left out here (counted low; 0.7% of a token's
    FLOPs at the published sizes) and counted in
    :func:`decode_token_flops` and :func:`prefill_flops`."""
    s = _sz(model)
    return 4 * s["q"] * s["full"] * int(queries_ctx_sum)


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def window_pairs(t: int, window: int) -> int:
    """(query, visible key) pairs of ``t`` causal positions that see at
    most ``window`` keys each, themselves included."""
    head = min(t, window)
    return causal_pairs(head) + (t - head) * window


def prefill_flops(model, prompt_len: int) -> float:
    """Forward pass over one prompt, the head applied once."""
    s, t = _sz(model), int(prompt_len)
    body = matmul_params(model) - s["V"] * s["C"]
    pairs = s["full"] * causal_pairs(t) \
        + s["window"] * window_pairs(t, s["W"])
    return 2.0 * t * body + 2 * s["V"] * s["C"] + 4 * s["q"] * pairs


def decode_token_flops(model, ctx: int) -> float:
    """One generated token that sees ``ctx`` keys, itself included."""
    s = _sz(model)
    return 2.0 * matmul_params(model) + 4 * s["q"] * (
        s["full"] * int(ctx) + s["window"] * min(int(ctx), s["W"]))


def experts_hit_share(model, tokens_per_step: float) -> float:
    """Share of the held experts that at least one of a step's tokens
    chose, under the router's even share: ``1 - (1 - k / width) ** n``
    (0.87 for 32 tokens, 8 of 128)."""
    s = _sz(model)
    return 1.0 - (1.0 - s["k"] / s["width"]) ** float(tokens_per_step)


def weight_bytes(model, itemsize: int, tokens_per_step: float = 1.0):
    """Bytes of weights a step of ``tokens_per_step`` tokens must read:
    what every step reads, and the held experts that were hit (the
    embedding table is read a row at a time and is left out)."""
    s = _sz(model)
    hit = experts_hit_share(model, tokens_per_step)
    return itemsize * (always_read_params(model) + hit * s["sparse"]
                       * s["held"] * expert_params(model))


def kv_bytes_per_token(model, itemsize: int) -> int:
    """K and V of one position, all layers."""
    s = _sz(model)
    return 2 * (s["full"] + s["window"]) * s["kv"] * itemsize


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> float:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights of a
    step of the mean size once a step, the live cache once (every
    position on the full layers; on the window layers at most ``window``
    a token, reckoned as min(ctx_sum, window x tokens): exact where every
    context is on one side of the window, as in the benchmark's mixes,
    whose prompts are no shorter than it), one new row written per
    token."""
    s = _sz(model)
    row = 2 * s["kv"] * itemsize                 # K and V, one layer
    live = s["full"] * ctx_sum + s["window"] * min(ctx_sum,
                                                   s["W"] * tokens)
    return (steps * weight_bytes(model, itemsize, tokens / max(1, steps))
            + live * row + tokens * kv_bytes_per_token(model, itemsize))


def prefill_bytes(model, prompt_len: int, itemsize: int) -> float:
    """The weights once (the experts the prompt's tokens hit) and the
    rows the cache keeps of the prompt: all on the full layers, the last
    ``window`` on the window layers."""
    s, t = _sz(model), int(prompt_len)
    row = 2 * s["kv"] * itemsize
    kept = s["full"] * t + s["window"] * min(t, s["W"])
    return weight_bytes(model, itemsize, t) + kept * row


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The roofline: ``(seconds, "compute" | "bandwidth")``."""
    tc = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
