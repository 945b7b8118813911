"""A configuration file of the benchmark, read into the shapes the yardstick
needs (operation counts, the plain reference). Keys follow the model's
published ``config.json``; ``configs/<name>.json`` holds them as run."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str            # "relu2" | "gelu_tanh"
    tied: bool          # head shares the embedding table
    rope_theta: float
    norm_eps: float

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


_ACTS = {"relu2": "relu2", "gelu_pytorch_tanh": "gelu_tanh"}


def dims_of(model: dict) -> Dims:
    """``Dims`` from the ``model`` block of a configuration file."""
    return Dims(layers=int(model["num_hidden_layers"]),
                d_model=int(model["hidden_size"]),
                heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                head_dim=int(model["head_dim"]),
                d_ff=int(model["intermediate_size"]),
                vocab=int(model["vocab_size"]),
                act=_ACTS[model["hidden_act"]],
                tied=bool(model["tie_word_embeddings"]),
                rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]))


def load_config(path) -> dict:
    """A configuration file, with its ``Dims`` under ``"dims"``."""
    cfg = json.loads(Path(path).read_text())
    cfg["dims"] = dims_of(cfg["model"])
    return cfg
