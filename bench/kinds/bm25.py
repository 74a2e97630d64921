"""BM25 retrieval on the host: the program's ``InvertedIndex`` over the
generated corpus; its reference ranks the same corpus from word ids
(``bench/reference/bm25.py``), and its control sums in bfloat16."""
from bench.reference.bm25 import BM25

ROLE = "retriever"
CORPUS = True
CONTROL = "bfloat16"


def build(world, name, spec):
    from repro.ir import InvertedIndex
    index = InvertedIndex.build(
        {"docno": d, "text": t}
        for d, t in zip(world.corpus.docnos, world.texts))
    return index.bm25(k1=spec["k1"], b=spec["b"])


def warm(world, stage, spec, queries):
    """Nothing compiles: BM25 runs on the host."""


class Reference(BM25):
    def __init__(self, cfg, spec, inputs):
        c = inputs.corpus
        super().__init__(c.doc_ptr, c.doc_words, len(c.words), spec["k1"],
                         spec["b"])
