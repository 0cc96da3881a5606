"""Operations and bytes that one Granite 4.0-H request requires, from the
configuration's shapes (``bench/configs/granite-*.json``, published
names): the parameters held, the least bytes one decode token must move,
the operations of a prefill and of a decode token, and the least time of
one ``ssd_scan`` call.

Matrix products count 2 operations a multiply-add.  Routed experts count
at their expected share: each token's top k of ``router_experts``, so
k × held / router_experts of the held experts a token (2.5 here).  bf16
weights (2 bytes), float32 SSM state (4 bytes).
"""

from __future__ import annotations

__all__ = ["params_held", "decode_least_bytes", "prefill_flops",
           "decode_flops", "request_flops", "ssd_least_s"]

BF16, F32 = 2, 4


def _d(cfg):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    return d, di, cfg["mamba_d_state"], di // cfg["mamba_d_head"]


def _mamba_params(cfg) -> int:
    d, di, N, nh = _d(cfg)
    ch = di + 2 * N
    return (d * (2 * di + 2 * N + nh) + ch * cfg["mamba_d_conv"] + ch
            + 3 * nh + di + di * d)


def _attn_params(cfg) -> int:
    d, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // H
    return 2 * d * H * dh + 2 * d * KV * dh


def _expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _ffn_fixed_params(cfg) -> int:
    """Router and shared expert: what every token reads in a layer."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] + 3 * d * cfg["shared_intermediate_size"]


def _mixer_params(cfg, kind) -> int:
    return _mamba_params(cfg) if kind == "mamba" else _attn_params(cfg)


def params_held(cfg) -> int:
    """Parameters on the chip: every layer's mixer, router, shared expert,
    held experts and two norms, the tied embedding and the final norm."""
    d = cfg["hidden_size"]
    per_layer = sum(_mixer_params(cfg, t) + _ffn_fixed_params(cfg)
                    + cfg["num_local_experts"] * _expert_params(cfg) + 2 * d
                    for t in cfg["layer_types"])
    return per_layer + cfg["vocab_size"] * d + d


def _experts_a_token(cfg) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_local_experts"] / cfg["router_experts"]


def decode_least_bytes(cfg) -> float:
    """The least one decode token must move: the bf16 weights it reads
    (mixers, routers, shared experts, the tied head, norms, and its
    expected share of the held experts) and the SSM state read and
    written.  The KV cache is left out, so this is a lower bound."""
    d, di, N, nh = _d(cfg)
    layers = cfg["layer_types"]
    weights = sum(_mixer_params(cfg, t) + _ffn_fixed_params(cfg) + 2 * d
                  + _experts_a_token(cfg) * _expert_params(cfg) for t in layers)
    weights += cfg["vocab_size"] * d + d
    state = sum(2 * F32 * di * N for t in layers if t == "mamba")
    return BF16 * weights + state


def _token_flops(cfg, attended: int) -> float:
    """One token through every layer; ``attended`` keys in attention."""
    d, di, N, nh = _d(cfg)
    H = cfg["num_attention_heads"]
    dh = d // H
    total = 0.0
    for t in cfg["layer_types"]:
        if t == "mamba":
            total += 2 * _mamba_params(cfg) + 4 * di * N   # projections, SSD
        else:
            total += 2 * _attn_params(cfg) + 4 * attended * H * dh
        total += 2 * _ffn_fixed_params(cfg) \
            + 2 * _experts_a_token(cfg) * _expert_params(cfg)
    return total


def prefill_flops(cfg, S: int) -> float:
    """A prompt of S tokens (causal attention: token t attends to t + 1
    keys), logits at its last position only."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n_attn = sum(t == "attention" for t in cfg["layer_types"])
    no_attn = S * _token_flops(cfg, 0)
    causal = 4 * (d // H) * H * S * (S + 1) / 2 * n_attn
    return no_attn + causal + 2 * d * cfg["vocab_size"]


def decode_flops(cfg, pos: int) -> float:
    """One decode token at position ``pos`` (pos + 1 keys), its logits."""
    return _token_flops(cfg, pos + 1) + 2 * cfg["hidden_size"] * cfg["vocab_size"]


def request_flops(cfg) -> float:
    """One request: the prompt's prefill and ``new_tokens`` decode tokens."""
    S, n = cfg["prompt_tokens"], cfg["new_tokens"]
    return prefill_flops(cfg, S) + sum(decode_flops(cfg, S + i) for i in range(n))


def ssd_least_s(cfg, S: int, peaks: dict) -> tuple[float, str]:
    """(least seconds of one ``ssd_scan`` call over S tokens, its bound).
    Operations: 4·H·P·N a token (the state's update and read-out), which
    no chunking beats.  Bytes: the kernel's float32 operands, x and y
    (S·H·P each), dt (S·H), B and C (S·N each) and the final state
    (H·P·N)."""
    d, di, N, nh = _d(cfg)
    P = cfg["mamba_d_head"]
    flops = 4.0 * nh * P * N * S
    nbytes = F32 * (2 * S * nh * P + S * nh + 2 * S * N + nh * P * N)
    t_f, t_b = flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
