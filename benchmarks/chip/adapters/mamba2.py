"""How the program serves a Mamba2 configuration file: its ``ModelConfig``
and its parameter tree, made from the reference's weights by renaming
(the arrays are shared, not copied)."""
from __future__ import annotations

from repro.configs.base import ModelConfig, SSMConfig

from reference import mamba2 as ref


def model_config(name: str, conf: dict) -> ModelConfig:
    m = ref.dims(conf)
    s = conf["mamba2_defaults"]
    return ModelConfig(
        name=name, arch_type="ssm", n_layers=m["L"], d_model=m["d"],
        n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=m["V"],
        ssm=SSMConfig(state_dim=m["N"], head_dim=m["P"],
                      expand=s["expand"], conv_width=m["W"],
                      chunk_size=s["chunk_size"]),
        norm="rmsnorm", tie_embeddings=conf["tie_embeddings"],
        dtype=conf["serve"]["dtype"], source=conf["source"])


def program_params(w: dict) -> dict:
    lw = w["layers"]
    return {
        "emb": {"tok": w["embedding"]},
        "layers": {"rms_w": lw["norm"], "w_in": lw["in_proj"],
                   "conv_w": lw["conv_w"], "conv_b": lw["conv_b"],
                   "dt_bias": lw["dt_bias"], "A_log": lw["A_log"],
                   "D": lw["D"], "norm_w": lw["gate_norm"],
                   "w_out": lw["out_proj"]},
        "final_norm": {"w": w["norm_f"]},
    }
