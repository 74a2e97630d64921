"""A small retrieval-depth grid, ``retr % k >> loader >> scorer``, whose
scorer counts the pairs it computes.  Shared by the planner's
shared-store tests (``tests/test_plan.py``) and the fresh interpreters
they start, so it imports nothing from pytest."""
import numpy as np

from repro.core import ColFrame, Transformer, add_ranks
from repro.ir import TextLoader

DEPTHS = (2, 5, 10)
QUERIES = ColFrame({"qid": ["q1", "q2", "q3"],
                    "query": ["alpha", "beta gamma", "delta"]})


class Retriever(Transformer):
    """One-to-many: ``num_results`` passages per query, with distinct
    docnos across queries; absorbs ``% k`` like ``BM25Retriever``."""

    input_columns = frozenset({"qid", "query"})
    output_columns = frozenset({"qid", "query", "docno", "score", "rank"})
    key_columns = ("qid", "query")
    one_to_many = True

    def __init__(self, num_results=max(DEPTHS)):
        self.num_results = int(num_results)

    def transform(self, inp):
        rows = [{"qid": q, "query": t, "docno": f"{q}.d{i}",
                 "score": 10.0 - i}
                for q, t in zip(inp["qid"].tolist(), inp["query"].tolist())
                for i in range(self.num_results)]
        return add_ranks(ColFrame.from_dicts(rows))

    def with_cutoff(self, k):
        return self if int(k) >= self.num_results else Retriever(k)

    def signature(self):
        return ("Retriever", self.num_results)


class PairScorer(Transformer):
    """Pointwise: a pair's score depends on its query and text alone.
    ``pairs`` lists every (query, docno) it computed, in order, and
    ``calls`` the number of rows of each call."""

    input_columns = frozenset({"qid", "query", "docno", "text"})
    key_columns = ("query", "docno")
    value_columns = ("score",)

    def __init__(self):
        self.pairs, self.calls = [], []

    def transform(self, inp):
        queries, texts = inp["query"].tolist(), inp["text"].tolist()
        self.calls.append(len(inp))
        self.pairs.extend(zip(queries, inp["docno"].tolist()))
        score = np.asarray([sum(map(ord, q + t)) % 97 / 7.0
                            for q, t in zip(queries, texts)])
        return add_ranks(inp.assign(score=score))

    def signature(self):
        return ("PairScorer",)


def loader(tag="v1"):
    """A text loader whose passage texts carry ``tag``: two tags give
    other text for the same docno."""
    texts = {f"{q}.d{i}": f"passage {i} of {q} {tag}"
             for q in QUERIES["qid"].tolist() for i in range(max(DEPTHS))}
    return TextLoader(texts, name=f"texts-{tag}")


def grid(scorer, text_loader=None):
    retr, text_loader = Retriever(), text_loader or loader()
    return [retr % k >> text_loader >> scorer for k in DEPTHS]
