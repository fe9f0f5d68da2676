"""Operations and bytes the Xing4.0 algorithm needs ON THIS CHIP, from
shapes alone: one chip's share of an expert-parallel deployment, as the
configuration's ``model`` block states it (the source's key names;
``n_routed_experts`` is the experts HELD, ``expert_share["of"]`` the chips
that share a layer, so the router is ``n_routed_experts * of`` wide).

Counted for the work the mathematics asks of this chip, whatever
implements it, and LOW where in doubt, so that a share of a peak worked
out from these cannot be inflated by the count: matrix products as 2 x
rows x inner x columns; latent attention by the CHEAPER of its two forms
in each phase (a decode token absorbed: ``H`` queries over the cached rows
at their true 576 and 512 values; a prompt expanded: the latent to ``H``
keys and values once a position, then pairs over 192 + 128); of the routed
experts, those a token uses HERE under the router's even share; the head
only where a logit is used; NOTHING for the hyper-connections' coefficient
arithmetic (norm, sigmoid, Sinkhorn, the read, the write and the mix of
the streams) but their projection, which is a weight every token is
multiplied by. Bytes of a step: what every step reads, the held experts
that at least one of the step's tokens chose (even share), the live cached
rows at the 576 values the mathematics caches of a position (the 64 zero
lanes the program stores beside them are the kernel's fetch,
``latent_attend_bytes``, not the algorithm's), one row written a token and
layer.
"""

from __future__ import annotations

from .exaone_moe import causal_pairs, least_seconds  # noqa: F401


def _sz(model) -> dict:
    n = int(model["num_hidden_layers"])
    held = int(model["n_routed_experts"])
    dense = min(n, int(model["first_k_dense_replace"]))
    h, streams = int(model["num_attention_heads"]), int(model["hc_mult"])
    return {
        "C": int(model["hidden_size"]), "V": int(model["vocab_size"]),
        "L": n, "H": h, "rq": int(model["q_lora_rank"]),
        "rank": int(model["kv_lora_rank"]),
        "dn": int(model["qk_nope_head_dim"]),
        "dr": int(model["qk_rope_head_dim"]), "dv": int(model["v_head_dim"]),
        "F": int(model["intermediate_size"]),
        "Fe": int(model["moe_intermediate_size"]),
        "held": held, "width": held * int(model["expert_share"]["of"]),
        "k": int(model["num_experts_per_tok"]),
        "shared": int(model["n_shared_experts"]),
        "coef": 2 * streams + streams * streams, "streams": streams,
        "dense": dense, "sparse": n - dense}


def attention_params(model) -> int:
    """One layer's latent attention: ``W_qa``, ``W_qb``, ``W_kva``,
    ``W_kvb``, ``W_o``. A token is multiplied by all of it in either form
    (absorbed, ``W_kvb``'s halves go into the query and the output)."""
    s = _sz(model)
    return (s["C"] * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"])
            + s["C"] * (s["rank"] + s["dr"])
            + s["rank"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["C"])


def hyper_params(model) -> int:
    """One layer's two hyper-connection projections."""
    s = _sz(model)
    return 2 * s["streams"] * s["C"] * s["coef"]


def expert_params(model) -> int:
    """One routed (or shared) expert: gate, up, down."""
    s = _sz(model)
    return 3 * s["C"] * s["Fe"]


def always_read_params(model) -> int:
    """Weights every token is multiplied by, whatever the router says:
    attention, the hyper-connections' projections, the dense FFNs, router,
    shared expert, head."""
    s = _sz(model)
    return (s["L"] * (attention_params(model) + hyper_params(model))
            + s["dense"] * 3 * s["C"] * s["F"]
            + s["sparse"] * (s["C"] * s["width"]
                             + s["shared"] * expert_params(model))
            + s["V"] * s["C"])


def routed_here_per_token(model) -> float:
    """Held experts a token uses, on average, under the router's even
    share: ``k * held / width`` (a half for 4 of 64 with 8 held)."""
    s = _sz(model)
    return s["k"] * s["held"] / s["width"]


def matmul_params(model) -> float:
    """Every weight a token is multiplied by on this chip."""
    s = _sz(model)
    return always_read_params(model) + s["sparse"] * \
        routed_here_per_token(model) * expert_params(model)


def param_count(model) -> int:
    """Parameters resident on the chip (norm gains, biases and the
    hyper-connections' scalars left out: thousands beside billions)."""
    s = _sz(model)
    return (always_read_params(model) + s["V"] * s["C"]
            + s["sparse"] * s["held"] * expert_params(model))


def absorbed_pair_flops(model) -> int:
    """One (query token, cached position) pair of one layer, absorbed:
    ``H`` scores over the latent and the shared key, ``H`` weighted sums
    of the latent."""
    s = _sz(model)
    return 2 * s["H"] * (s["rank"] + s["dr"]) + 2 * s["H"] * s["rank"]


def expanded_pair_flops(model) -> int:
    """The same pair expanded: scores over ``nope + rope``, sums over
    ``v``, per head."""
    s = _sz(model)
    return 2 * s["H"] * (s["dn"] + s["dr"]) + 2 * s["H"] * s["dv"]


def attn_flops(model, queries_ctx_sum: int) -> int:
    """The pairs of generated tokens that saw ``queries_ctx_sum`` keys
    between them, all layers, absorbed (the cheaper form of a decode
    step: expanding would multiply every cached position by ``W_kvb``
    again each step)."""
    return _sz(model)["L"] * absorbed_pair_flops(model) \
        * int(queries_ctx_sum)


def prefill_attn_flops(model, t: int) -> float:
    """The pairs of ``t`` causal positions, one layer, by the cheaper
    form. ``W_kvb`` over every position is in ``matmul_params`` either
    way; absorbed pays it twice (into the query, out of the output) and
    that second time is counted with its pairs."""
    s = _sz(model)
    again = 2 * t * s["rank"] * s["H"] * (s["dn"] + s["dv"])
    return min(causal_pairs(t) * expanded_pair_flops(model),
               causal_pairs(t) * absorbed_pair_flops(model) + again)


def prefill_flops(model, prompt_len: int) -> float:
    """Forward pass over one prompt, the head applied once."""
    s, t = _sz(model), int(prompt_len)
    body = matmul_params(model) - s["V"] * s["C"]
    return 2.0 * t * body + 2 * s["V"] * s["C"] \
        + s["L"] * prefill_attn_flops(model, t)


def decode_token_flops(model, ctx: int) -> float:
    """One generated token that sees ``ctx`` keys, itself included."""
    return 2.0 * matmul_params(model) + attn_flops(model, ctx)


def experts_hit_share(model, tokens_per_step: float) -> float:
    """Share of the held experts that at least one of a step's tokens
    chose, under the router's even share: ``1 - (1 - k / width) ** n``
    (0.87 for 32 tokens, 4 of 64)."""
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
    """What the mathematics caches of one position, all layers: the
    latent and the shared rotated key."""
    s = _sz(model)
    return s["L"] * (s["rank"] + s["dr"]) * itemsize


def decode_steps_bytes(model, steps: int, ctx_sum: int, tokens: int,
                       itemsize: int) -> float:
    """``steps`` decode steps that advance ``tokens`` sequences in all,
    which see ``ctx_sum`` cached positions between them: the weights of a
    step of the mean size once a step, the live rows once, one new row
    written per token."""
    return (steps * weight_bytes(model, itemsize, tokens / max(1, steps))
            + (ctx_sum + tokens) * kv_bytes_per_token(model, itemsize))


def prefill_bytes(model, prompt_len: int, itemsize: int) -> float:
    """The weights once (the experts the prompt's tokens hit) and the
    rows the cache keeps of the prompt."""
    t = int(prompt_len)
    return weight_bytes(model, itemsize, t) \
        + t * kv_bytes_per_token(model, itemsize)


def latent_attend_bytes(model, ctx_sum: int, itemsize: int) -> int:
    """What the decode step's attention kernels must fetch for slots that
    see ``ctx_sum`` cached positions between them: each position's row as
    the program STORES it, whole 128-lane tiles (640 for 512 + 64), once
    a layer (read as key and value both)."""
    s = _sz(model)
    stored = -(-(s["rank"] + s["dr"]) // 128) * 128
    return int(ctx_sum) * s["L"] * stored * itemsize
