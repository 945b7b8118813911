"""Test fixture: the shape record of a block that the GQA reference does
not compute (a gated SiLU FFN, attention over a sliding window). It shows
that such a configuration loads through its own module; it computes
nothing."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    vocab: int
    d_model: int
    window: int
    act: str


def dims_of(model: dict) -> Dims:
    return Dims(layers=int(model["num_hidden_layers"]),
                vocab=int(model["vocab_size"]),
                d_model=int(model["hidden_size"]),
                window=int(model["sliding_window"]),
                act=model["hidden_act"])
