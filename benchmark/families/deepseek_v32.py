"""Family adapter: DeepSeek-V3.2's published ``config.json`` -> the
program.

Builds the program's ``GPTConfig`` (with its ``LatentConfig``) from the
published keys as the configuration file holds them — the chip's share
of the deployment: the experts held, the vocabulary rows held, the
layers kept — gives the operation counter the shape, and maps the
program's parameter tree onto the names
``benchmark/reference/deepseek_v32.py`` is written against.

``--tiny-cpu`` (``harness/tiny.py``) knows GPT-2's keys only: it stamps
``n_embd`` onto whatever configuration it is given. A configuration that
carries that key is therefore a rehearsal, and this family cuts it to
:data:`TINY`, which keeps every ratio (two groups or more, the experts
held a strict subset, a top-k below the context, both layer kinds, a
sliced vocabulary).
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "deepseek_v32"

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "experts_held": [0, 4], "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "vocab_size": 96,
    "max_position_embeddings": 64,
    "published": {"n_routed_experts": 16},
}


def effective(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as run: itself, or :data:`TINY` over it when
    ``harness/tiny.py`` has stamped GPT-2's keys on it."""
    if "n_embd" not in config:
        return config
    out = dict(config, **TINY)
    out["rope_scaling"] = dict(config["rope_scaling"],
                               original_max_position_embeddings=16)
    return out


def is_rehearsal(config: Dict[str, Any]) -> bool:
    return "n_embd" in config


def shape(config: Dict[str, Any]) -> Dict[str, int]:
    c = effective(config)
    return {
        "layers": c["num_hidden_layers"],
        "dense_layers": c["first_k_dense_replace"],
        "hidden": c["hidden_size"], "heads": c["num_attention_heads"],
        "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "index_heads": c["index_n_heads"],
        "index_dim": c["index_head_dim"], "topk": c["index_topk"],
        "dense_ffn": c["intermediate_size"],
        "expert_ffn": c["moe_intermediate_size"],
        "experts_all": c["published"]["n_routed_experts"],
        "experts_held": c["experts_held"][1],
        "experts_per_token": c["num_experts_per_tok"],
        "shared_experts": c["n_shared_experts"],
        "vocab": c["vocab_size"],
        "positions": c["max_position_embeddings"]}


def program_config(config: Dict[str, Any], pins: Dict[str, Any]):
    """The program's model config at the published widths, everything
    bfloat16 (a rehearsal: float32, so that its comparison with the
    reference checks the control flow and not a tiny model's rounding),
    with those of the recipe's ``pins`` that ``GPTConfig`` still
    takes."""
    import jax.numpy as jnp

    from apex_tpu.models import gpt, latent
    from apex_tpu.transformer.moe import RoutedConfig
    from benchmark.harness import recipe

    c = effective(config)
    dtype = jnp.float32 if is_rehearsal(config) else jnp.bfloat16
    for key, want in (("hidden_act", "silu"), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1), ("moe_layer_freq", 1)):
        if c[key] != want:
            raise ValueError(f"the program's layer has {key}={want!r} "
                             f"only, the configuration says {c[key]!r}")
    rs, assumed = c["rope_scaling"], c["assumed"]
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("the program has YaRN with mscale == "
                         "mscale_all_dim only")
    lc = latent.LatentConfig(
        routed=RoutedConfig(
            num_experts=c["published"]["n_routed_experts"],
            experts_held=tuple(c["experts_held"]),
            top_k=c["num_experts_per_tok"], n_group=c["n_group"],
            topk_group=c["topk_group"],
            routed_scale=c["routed_scaling_factor"]),
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        index_n_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        rms_eps=c["rms_norm_eps"],
        index_ln_eps=assumed["index_layernorm_eps"],
        dense_layers=c["first_k_dense_replace"],
        dense_ffn=c["intermediate_size"],
        expert_ffn=c["moe_intermediate_size"],
        attn_init_gain=assumed["attn_init_gain"],
        router_bias_std=assumed["router_bias_std"])
    return gpt.GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        seq_len=c["max_position_embeddings"],
        init_std=assumed["initializer_range"],
        compute_dtype=dtype, param_dtype=dtype, latent=lc,
        **recipe.accepted(gpt.GPTConfig, pins, "the model config"))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    c = effective(config)
    rs = c["rope_scaling"]
    kw = {
        "hidden": c["hidden_size"], "heads": c["num_attention_heads"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "kv_rank": c["kv_lora_rank"],
        "index_heads": c["index_n_heads"],
        "index_dim": c["index_head_dim"], "topk": c["index_topk"],
        "theta": float(c["rope_theta"]), "factor": float(rs["factor"]),
        "original": rs["original_max_position_embeddings"],
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]),
        "mscale_all_dim": float(rs["mscale_all_dim"]),
        "eps": c["rms_norm_eps"],
        "index_eps": c["assumed"]["index_layernorm_eps"],
        "top_k": c["num_experts_per_tok"], "n_group": c["n_group"],
        "topk_group": c["topk_group"],
        "routed_scale": c["routed_scaling_factor"]}
    return {"kw": kw, "held": tuple(c["experts_held"])}


def reference_layer(params, i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's tree (``models/latent.init``: a
    stack of dense layers, then a stack of routed ones) under the
    reference's names."""
    import jax

    n_dense = jax.tree.leaves(params["dense_layers"])[0].shape[0]
    stack, j, ffn = ((params["dense_layers"], i, "ffn") if i < n_dense
                     else (params["moe_layers"], i - n_dense, "moe"))
    p = jax.tree.map(lambda x: x[j], stack)
    return {"ln1": p["ln1"]["scale"], "ln2": p["ln2"]["scale"],
            "attn": p["attn"], "index": p["index"], ffn: p[ffn]}


def reference_params(params) -> Dict[str, Any]:
    """The whole tree under the reference's names: the two stacks
    become one list of per-layer trees, in layer order."""
    import jax

    n = sum(jax.tree.leaves(params[k])[0].shape[0]
            for k in ("dense_layers", "moe_layers"))
    return {"embed": params["embedding"]["word"]["table"],
            "head": params["head"]["kernel"],
            "norm": params["final_ln"]["scale"],
            "layers": [reference_layer(params, i) for i in range(n)]}
