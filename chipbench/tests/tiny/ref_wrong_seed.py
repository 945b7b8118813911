"""Test fixture: the GQA reference with its weights drawn from the seed
after the program's, so that no sound run agrees with it."""
from chipbench.reference import model as gqa

dims_of = gqa.dims_of


def served_gaps(dims, seed, seqs, length, **kw):
    return gqa.served_gaps(dims, seed + 1, seqs, length, **kw)


def logits(dims, seed, seqs, length, **kw):
    return gqa.logits(dims, seed + 1, seqs, length, **kw)
