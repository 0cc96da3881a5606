"""Granite-4.0-H-Small (granitemoehybrid).  [hf:ibm-granite/granite-4.0-h-small]

40L d_model=4096, a period of 10 layers (five Mamba-2, one attention, four
Mamba-2), each mixer followed by a MoE FFN.  Mamba-2: 128 heads x 64,
d_state 128, one group, conv 4 with bias, chunk 256.  Attention: GQA 32/8
heads of 128, no positional encoding (NoPE), scores scaled by
``attention_multiplier`` 1/128.  MoE: 72 SwiGLU experts of width 768, top
10 with a softmax over the chosen logits, plus one shared SwiGLU expert of
width 1,536 (``intermediate_size`` read as the expert width).  Embeddings
x12, each residual branch x0.22, logits /16; RMSNorm eps 1e-5; tied
embeddings, vocabulary 100,352.
"""
from repro.configs.base import ModelConfig, register

PERIOD = ("ssm_moe",) * 5 + ("attn_moe",) + ("ssm_moe",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=768,
    vocab_size=100352, d_head=128, tie_embeddings=True, pos_emb="none",
    norm_eps=1e-5, block_pattern=PERIOD,
    n_experts=72, experts_per_token=10, shared_expert_ff=1536,
    ssm_state=128, ssm_conv=4, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    source="hf:ibm-granite/granite-4.0-h-small (config.json)",
)
REDUCED = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_ff=32,
    vocab_size=128, d_head=16, tie_embeddings=True, pos_emb="none",
    norm_eps=1e-5, block_pattern=("ssm_moe", "attn_moe"),
    n_experts=8, experts_per_token=3, shared_expert_ff=48,
    ssm_state=16, ssm_conv=4, ssm_expand=2, ssm_head_dim=16, ssm_chunk=16,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=4.0, attn_chunk=32,
)
register(CONFIG, REDUCED)


def from_hf(hf: dict, *, experts_held: int = 0, expert_offset: int = 0,
            dtype: str = "bfloat16", name: str = CONFIG.name) -> ModelConfig:
    """The ``ModelConfig`` of a granitemoehybrid ``config.json`` (as a
    dict): its ``layer_types`` are one period, scanned once.
    ``num_local_experts`` counts the router's outputs; ``experts_held`` of
    them, from ``expert_offset``, are held here (0: all)."""
    implemented = {"mamba_n_groups": 1, "mamba_conv_bias": True,
                   "mamba_proj_bias": False, "attention_bias": False,
                   "hidden_act": "silu", "normalization_function": "rmsnorm"}
    for key, want in implemented.items():
        if hf.get(key, want) != want:
            raise ValueError(f"{key}={hf[key]!r}: only {want!r} is implemented")
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    kinds = {"mamba": "ssm_moe", "attention": "attn_moe"}
    cfg = ModelConfig(
        name=name, family="hybrid", n_layers=hf["num_hidden_layers"],
        d_model=d, n_heads=heads, n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        d_head=d // heads, tie_embeddings=hf["tie_word_embeddings"],
        pos_emb="none" if hf["position_embedding_type"] == "nope" else "rope",
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=hf["rms_norm_eps"],
        block_pattern=tuple(kinds[t] for t in hf["layer_types"]),
        n_experts=hf["num_local_experts"],
        experts_per_token=hf["num_experts_per_tok"],
        shared_expert_ff=hf["shared_intermediate_size"],
        experts_held=experts_held, expert_offset=expert_offset,
        ssm_state=hf["mamba_d_state"], ssm_conv=hf["mamba_d_conv"],
        ssm_expand=hf["mamba_expand"], ssm_head_dim=hf["mamba_d_head"],
        ssm_chunk=hf["mamba_chunk_size"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]), dtype=dtype,
        source=CONFIG.source)
    if cfg.ssm_expand * d != hf["mamba_n_heads"] * cfg.ssm_head_dim:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x hidden")
    return cfg


def layer_params(cfg: ModelConfig, kind: str, w: dict) -> dict:
    """One layer's program parameters (``models/transformer`` block of
    ``kind``) from its weights in the published layout (Hugging Face
    names, ``nn.Linear`` weights as (out, in))."""
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)
    d, f, fs = cfg.d_model, cfg.d_ff, cfg.shared_expert_ff
    t = lambda a: a.T.astype(dt)                              # noqa: E731
    p = {"norm1": {"scale": w["input_layernorm"].astype(dt)},
         "norm2": {"scale": w["post_attention_layernorm"].astype(dt)},
         "ffn": {"router": t(w["router"]),
                 "w_gate": jnp.swapaxes(w["input_linear"][:, :f], 1, 2).astype(dt),
                 "w_up": jnp.swapaxes(w["input_linear"][:, f:], 1, 2).astype(dt),
                 "w_down": jnp.swapaxes(w["output_linear"], 1, 2).astype(dt),
                 "shared": {"w_gate": t(w["shared_input_linear"][:fs]),
                            "w_up": t(w["shared_input_linear"][fs:]),
                            "w_down": t(w["shared_output_linear"])}}}
    if kind == "ssm_moe":
        di, N = cfg.ssm_expand * d, cfg.ssm_state
        z, x, B, C, dtp = jnp.split(w["in_proj"],
                                    [di, 2 * di, 2 * di + N, 2 * di + 2 * N])
        f32 = lambda a: a.astype(jnp.float32)                 # noqa: E731
        p["ssm"] = {"in_z": t(z), "in_x": t(x), "in_B": t(B), "in_C": t(C),
                    "in_dt": t(dtp), "conv_w": t(w["conv1d_weight"][:, 0, :]),
                    "conv_b": w["conv1d_bias"].astype(dt),
                    "A_log": f32(w["A_log"]), "D": f32(w["D"]),
                    "dt_bias": f32(w["dt_bias"]),
                    "norm_scale": w["norm"].astype(dt), "out": t(w["out_proj"])}
    else:
        H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        p["attn"] = {"wq": t(w["q_proj"]).reshape(d, H, dh),
                     "wk": t(w["k_proj"]).reshape(d, KV, dh),
                     "wv": t(w["v_proj"]).reshape(d, KV, dh),
                     "wo": t(w["o_proj"]).reshape(H, dh, d)}
    return p

