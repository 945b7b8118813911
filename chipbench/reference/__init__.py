"""Plain references, one module per kind of block, for the check that
decides ``correct``.

A configuration file names its module under ``"reference"`` (a path
relative to ``chipbench/``); the harness loads it by that path. A reference
imports nothing of the program and takes nothing the program made: it draws
the weights from the seed as the served model does. It exports:

``dims_of(model: dict)``
    A frozen, hashable shape record of the configuration's ``model`` block,
    with at least ``vocab`` and ``layers``, which the harness and the
    traffic read; the module's own functions and the metric readers of its
    cells read the rest.
``served_gaps(dims, seed, seqs, length, *, tenants, rank, control, n_out)``
    For each :class:`Seq`, the gap at every served token by which its
    logit lies below the reference's best, as ``{"gap": [array per seq]}``;
    with ``control``, also ``"control_gap"``: at the same positions, the
    gap of the token that the reference one precision step below the
    configuration's puts first. Sequences are padded to ``length`` tokens
    and ``n_out`` scored positions, so one compiled program serves a run.
``logits(dims, seed, seqs, length, *, tenants, rank, low)``
    The reference's logits at the positions that predict each served
    token, one ``(n_served, vocab)`` array per sequence, with ``low`` one
    precision step below; for the tests.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class Seq(NamedTuple):
    prompt: np.ndarray          # int32 prompt ids
    served: np.ndarray          # int32 tokens the program served
    tenant: Optional[int]       # adapter index, None for the base model
