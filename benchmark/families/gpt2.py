"""Family adapter: GPT-2's published ``config.json`` -> the program.

Builds the program's ``GPTConfig`` from the published keys, gives the
FLOP counter the published shape, and maps the program's parameter tree
onto the names ``benchmark/reference/gpt2.py`` is written against.
"""

from __future__ import annotations

from typing import Any, Dict

REFERENCE = "gpt2"


def shape(config: Dict[str, Any]) -> Dict[str, int]:
    h = config["n_embd"]
    return {"layers": config["n_layer"], "hidden": h,
            "heads": config["n_head"], "head_dim": h // config["n_head"],
            "ffn": config["n_inner"] or 4 * h,
            "vocab": config["vocab_size"],
            "positions": config["n_positions"]}


def program_config(config: Dict[str, Any], pins: Dict[str, Any]):
    """The program's model config at the published sizes, bf16 compute
    over fp32 parameters, with those of the recipe's ``pins`` that
    ``GPTConfig`` still takes."""
    import jax.numpy as jnp

    from apex_tpu.models import gpt
    from benchmark.harness import recipe

    if config["activation_function"] != "gelu_new":
        raise ValueError("the program's block has tanh-GELU only")
    return gpt.GPTConfig(
        vocab_size=config["assumed"]["padded_vocab_size"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"], seq_len=config["n_positions"],
        ffn_hidden_size=config["n_inner"],
        layernorm_epsilon=config["layer_norm_epsilon"],
        init_std=config["initializer_range"],
        compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
        **recipe.accepted(gpt.GPTConfig, pins, "the model config"))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"n_head": config["n_head"],
            "eps": config["layer_norm_epsilon"]}


def reference_params(params) -> Dict[str, Any]:
    """The program's tree (``models/gpt.init``) under GPT-2's names.
    The fused QKV kernel ``[L, h, 3, h]`` holds the q | k | v slabs
    contiguously with whole heads inside each, which is ``c_attn``'s
    ``[h, 3h]`` column order; everything else is a rename."""
    lay = params["layers"]
    qkv = lay["attn"]["qkv"]
    n_layer, h = qkv["kernel"].shape[:2]
    ln = lambda p: {"g": p["scale"], "b": p["bias"]}
    lin = lambda p: {"w": p["kernel"], "b": p["bias"]}
    return {
        "wte": params["embedding"]["word"]["table"],
        "wpe": params["embedding"]["position"],
        "h": {
            "ln_1": ln(lay["ln1"]),
            "attn": {
                "c_attn": {"w": qkv["kernel"].reshape(n_layer, h, 3 * h),
                           "b": qkv["bias"].reshape(n_layer, 3 * h)},
                "c_proj": lin(lay["attn"]["proj"]),
            },
            "ln_2": ln(lay["ln2"]),
            "mlp": {"c_fc": lin(lay["mlp"]["fc1"]),
                    "c_proj": lin(lay["mlp"]["fc2"])},
        },
        "ln_f": ln(params["final_ln"]),
    }
