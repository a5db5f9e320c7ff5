"""How the program serves a Granite 4.0-H configuration file: its
``ModelConfig`` (the layer-pattern hybrid) and its parameter tree, made
from the reference's weights by renaming (the arrays are shared, not
copied). A program without the layer-pattern hybrid stops here, at once."""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig, SSMConfig

from reference import granite as ref


def model_config(name: str, conf: dict) -> ModelConfig:
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    if "layer_types" not in fields:
        raise SystemExit("chipbench: this program has no layer-pattern "
                         "hybrid (ModelConfig.layer_types); it cannot serve "
                         f"{name}")
    m = ref.dims(conf)
    # what the program's layers and the reference both assume of the file
    want = {"mamba_n_groups": 1, "mamba_proj_bias": False,
            "mamba_conv_bias": True, "attention_bias": False,
            "hidden_act": "silu", "num_local_experts": 0,
            "rms_norm_eps": 1e-5, "mamba_n_heads": m["di"] // m["P"],
            "tie_word_embeddings": True}
    off = {k: conf[k] for k, v in want.items() if conf[k] != v}
    if off:
        raise ValueError(f"{name}: the program serves none of {off}")
    return ModelConfig(
        name=name, arch_type="hybrid", n_layers=m["L"], d_model=m["d"],
        n_heads=m["Hq"], n_kv_heads=m["Hkv"], head_dim=m["hd"], d_ff=m["F"],
        vocab_size=m["V"],
        ssm=SSMConfig(state_dim=m["N"], head_dim=m["P"],
                      expand=conf["mamba_expand"], conv_width=m["W"],
                      chunk_size=m["chunk"]),
        layer_types=tuple(m["types"]),
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=float(conf["residual_multiplier"]),
        attention_multiplier=float(conf["attention_multiplier"]),
        logits_scaling=float(conf["logits_scaling"]),
        rope_theta=0.0 if conf["position_embedding_type"] == "nope"
        else float(conf["rope_theta"]),
        norm="rmsnorm", ffn="swiglu",
        tie_embeddings=True,
        dtype=conf["serve"]["dtype"], source=conf["source"])


def program_params(w: dict) -> dict:
    mw, aw, fw = w["mamba"], w["attention"], w["mlp"]
    return {
        "emb": {"tok": w["embedding"]},
        "mamba": {"rms_w": mw["norm"], "w_in": mw["in_proj"],
                  "conv_w": mw["conv_w"], "conv_b": mw["conv_b"],
                  "dt_bias": mw["dt_bias"], "A_log": mw["A_log"],
                  "D": mw["D"], "norm_w": mw["gate_norm"],
                  "w_out": mw["out_proj"]},
        "attn": {"wq": aw["q"], "wk": aw["k"], "wv": aw["v"], "wo": aw["o"]},
        "attn_norm": {"w": aw["norm"]},
        "mlp": {"w_gate": fw["gate"], "w_up": fw["up"], "w_down": fw["down"]},
        "mlp_norm": {"w": fw["norm"]},
        "final_norm": {"w": w["norm_f"]},
    }
