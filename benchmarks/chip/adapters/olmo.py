"""How the program serves an OLMo configuration file: its ``ModelConfig``
and its parameter tree, made from the reference's weights by renaming
(the arrays are shared, not copied)."""
from __future__ import annotations

from repro.configs.base import ModelConfig

from reference import olmo as ref


def model_config(name: str, conf: dict) -> ModelConfig:
    m = ref.dims(conf)
    return ModelConfig(
        name=name, arch_type="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=conf["num_key_value_heads"],
        d_ff=m["F"], vocab_size=m["V"], norm="nonparametric", ffn="swiglu",
        rope_theta=m["theta"], tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["serve"]["dtype"], source=conf["source"])


def program_params(w: dict) -> dict:
    lw = w["layers"]
    return {
        "emb": {"tok": w["embedding"]},
        "layers": {"attn": {"wq": lw["q"], "wk": lw["k"], "wv": lw["v"],
                            "wo": lw["o"]},
                   "norm1": {}, "norm2": {},
                   "ffn": {"w_gate": lw["gate"], "w_up": lw["up"],
                           "w_down": lw["down"]}},
        "final_norm": {},
    }
