"""What the kinds take from the program under test: its model configuration,
built from a bench configuration file's public ``config.json`` keys."""
from __future__ import annotations

from repro.models.config import ModelConfig


def model_config(name: str, model: dict) -> ModelConfig:
    """The program's ModelConfig for a dense Qwen3-style decoder, as the
    configuration file states it (published rope_theta and dtype)."""
    if model.get("rms_norm_eps") != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-6")
    if not model.get("tie_word_embeddings", False):
        raise ValueError("the program's dense decoder ties its output head")
    return ModelConfig(
        name=name,
        family="dense",
        num_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        vocab_size=model["vocab_size"],
        head_dim=model["head_dim"],
        qk_norm=True,
        rope_theta=float(model["rope_theta"]),
        layer_pattern=("attn",),
        dtype=model["torch_dtype"],
    )
