"""Test fixture: the GQA reference, recording each call of ``served_gaps``
(the model seed and the number of sequences compared)."""
from chipbench.reference import model as gqa

dims_of, logits = gqa.dims_of, gqa.logits
CALLS = []


def served_gaps(dims, seed, seqs, length, **kw):
    CALLS.append((seed, len(seqs)))
    return gqa.served_gaps(dims, seed, seqs, length, **kw)
