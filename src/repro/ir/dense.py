"""Dense (neural) first-stage retrieval as a compiler-native stage (Q → R).

The paper's RetrieverCache wraps *any* retriever; this is the neural
one: encode the corpus once (offline, cacheable via IndexerCache),
encode queries online, top-k over the embedding matrix — the
`retrieval_cand` pattern of the two-tower arch, surfaced as a
first-class plan-compiler node:

* the hot path is the fused ``kernels/dense_topk`` blocked matmul +
  streaming top-k (``backend="pallas"``: compiled Mosaic on TPU,
  interpret-mode fallback on CPU) or the same math through XLA
  (``backend="xla"``, the default off-TPU — ``lax.top_k`` over one
  jitted contraction per corpus shard);
* the corpus embedding matrix is row-sharded across local devices via
  the ``table_rows`` rule of ``distrib/shardings.py``; each device
  computes a partial top-k over its rows and the partials are merged
  on host under the global tie-break (descending score, then ascending
  doc index);
* that deterministic total order is what makes ``with_cutoff`` sound,
  so the optimizer's pushdown pass (``core/rewrite.py``) fuses
  ``RankCutoff`` into the kernel's per-block k exactly as it does for
  ``BM25Retriever.num_results``;
* ``signature()`` / ``fingerprint_extras()`` carry the corpus content
  digest, so planner-inserted caches (``auto_cache`` →
  ``RetrieverCache``; ``one_to_many=True``) invalidate when the
  embedding matrix changes.

Embeddings come from the shared cross-encoder tower in single-text mode
(mean-pooled); query embeddings are memoized per encoder (bounded LRU),
so hybrid plans whose branches survive CSE as distinct nodes — e.g.
``dense % 5`` next to ``dense % 50`` after pushdown — still encode each
unique query once per process.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..caching.bucketing import DEVICE_BATCH_FLOOR, bucket_size, pad_batch
from ..caching.compile_cache import default_compile_cache
from ..core.frame import ColFrame
from ..core.pipeline import Transformer
from ..kernels.dense_topk import dense_topk_op
from ..models.common import init_params, rms_norm

# NOTE: cross_encoder is imported lazily inside DenseEncoder.__init__ —
# cross_encoder itself imports repro.ir.tokenizer, so a module-level
# import here would close an import cycle through repro.ir.__init__.

__all__ = ["DenseEncoder", "DenseIndex", "DenseRetriever"]

EncoderConfig = Any   # type alias; see lazy-import note above


class DenseEncoder:
    """Text -> embedding via the shared encoder backbone (mean pool)."""

    #: bound on the query-embedding memo (LRU, see ``encode_queries``)
    QUERY_MEMO_MAX = 4096

    def __init__(self, cfg, seed: int = 7):
        from ..models.cross_encoder import encoder_param_specs
        from .tokenizer import HashTokenizer
        self.cfg = cfg
        self.seed = seed
        self.params = init_params(encoder_param_specs(cfg),
                                  jax.random.key(seed))
        self.tokenizer = HashTokenizer(cfg.vocab_size)
        self._query_memo: "OrderedDict[str, np.ndarray]" = OrderedDict()
        #: texts actually pushed through the backbone (memo hits do not
        #: count) — tests assert CSE'd branches encode each query once
        self.encoded_texts = 0

    def _embed_fn(self, tokens: jnp.ndarray) -> jnp.ndarray:
        p, cfg = self.params, self.cfg
        mask = (tokens != 0)
        x = jnp.take(p["embed"], tokens, axis=0, mode="clip")
        x = x + p["pos"][None, :tokens.shape[1]]

        def layer_body(x, layer):
            h = rms_norm(x, layer["ln1"])
            q = jnp.einsum("bsd,dnh->bsnh", h, layer["wq"])
            k = jnp.einsum("bsd,dnh->bsnh", h, layer["wk"])
            v = jnp.einsum("bsd,dnh->bsnh", h, layer["wv"])
            s = jnp.einsum("bqnh,bsnh->bnqs", q, k).astype(jnp.float32)
            bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
            pr = jax.nn.softmax(s / np.sqrt(cfg.head_dim) + bias,
                                axis=-1).astype(x.dtype)
            a = jnp.einsum("bnqs,bsnh->bqnh", pr, v)
            x = x + jnp.einsum("bqnh,nhd->bqd", a, layer["wo"])
            h2 = rms_norm(x, layer["ln2"])
            ff = jnp.einsum("bsf,fd->bsd",
                            jax.nn.gelu(jnp.einsum("bsd,df->bsf", h2,
                                                   layer["w1"])),
                            layer["w2"])
            return x + ff, None

        x, _ = jax.lax.scan(layer_body, x, p["layers"])
        x = rms_norm(x, p["ln_f"])
        m = mask[..., None].astype(x.dtype)
        pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
        return pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-6)

    def encode(self, texts: Sequence[str], batch: int = 256) -> np.ndarray:
        outs = []
        for lo in range(0, len(texts), batch):
            chunk = texts[lo:lo + batch]
            toks = pad_batch(
                self.tokenizer.encode_batch(chunk, self.cfg.max_len),
                bucket_size(len(chunk), floor=DEVICE_BATCH_FLOOR))
            emb = default_compile_cache.call(
                f"dense_encode:{self.cfg.name}", self._embed_fn,
                jnp.asarray(toks))
            outs.append(np.asarray(emb)[:len(chunk)])
            self.encoded_texts += len(chunk)
        return np.concatenate(outs) if outs else \
            np.zeros((0, self.cfg.d_model), np.float32)

    def encode_queries(self, texts: Sequence[str]) -> np.ndarray:
        """``encode`` behind a bounded per-encoder LRU memo.

        Encoder params are a pure function of ``(cfg, seed)``, so the
        text → embedding map is immutable for this instance; distinct
        plan nodes sharing the encoder (CSE'd hybrid branches, repeated
        serve traffic) therefore encode each unique text once.  Corpus
        indexing bypasses the memo (``encode``) — only the online query
        stream is worth pinning.
        """
        out = np.empty((len(texts), self.cfg.d_model), np.float32)
        fresh: List[str] = []
        for t in texts:
            hit = self._query_memo.get(t)
            if hit is None:
                if t not in fresh:
                    fresh.append(t)
            else:
                self._query_memo.move_to_end(t)
        if fresh:
            emb = self.encode(fresh)
            for t, e in zip(fresh, emb):
                self._query_memo[t] = e
            while len(self._query_memo) > self.QUERY_MEMO_MAX:
                self._query_memo.popitem(last=False)
        for i, t in enumerate(texts):
            out[i] = self._query_memo[t]
        return out


@partial(jax.jit, static_argnames=("k",))
def _xla_chunk_topk(q_emb: jnp.ndarray, chunk: jnp.ndarray, k: int):
    """Per-shard fused scoring on the XLA path (same math as
    ``kernels/dense_topk/ref.py``, kept inline so each corpus shard
    jits against its resident device buffer)."""
    s = jax.lax.dot_general(q_emb, chunk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    vals, idxs = jax.lax.top_k(s, k)
    return vals, idxs.astype(jnp.int32)


class DenseIndex:
    """Corpus embedding matrix + docno map, row-sharded across devices."""

    def __init__(self, encoder: DenseEncoder):
        self.encoder = encoder
        self.docnos: list = []
        self.matrix: Optional[np.ndarray] = None
        self._digest: Optional[str] = None
        self._chunks: Optional[List[Tuple[int, jnp.ndarray]]] = None
        self.sharding_spec = None        # recorded table_rows decision

    def index(self, corpus_iter) -> "DenseIndex":
        rows = list(corpus_iter)
        self.docnos = [str(r["docno"]) for r in rows]
        self.matrix = self.encoder.encode([r["text"] for r in rows])
        self._digest = None
        self._chunks = None
        return self

    def content_digest(self) -> str:
        """Stable digest of the docno map + embedding matrix bytes —
        the provenance token ``DenseRetriever.fingerprint_extras``
        folds in, so caches invalidate when the corpus is re-encoded."""
        if self._digest is None:
            h = hashlib.sha256()
            h.update(repr(self.docnos).encode())
            if self.matrix is not None:
                h.update(np.ascontiguousarray(self.matrix).tobytes())
            self._digest = h.hexdigest()[:16]
        return self._digest

    def device_chunks(self) -> List[Tuple[int, jnp.ndarray]]:
        """Row-shard the corpus matrix across local devices: the
        ``table_rows`` logical-axis rule of ``distrib/shardings.py``
        (rows over the data axis, feature dim replicated), realized as
        one contiguous ``(row_offset, resident chunk)`` per device.
        Chunks are independent — each device computes a partial top-k,
        merged on host — so ragged splits are fine even where the SPMD
        rule engine would prune for indivisibility.
        """
        if self._chunks is None:
            # deferred: distrib pulls in the model zoo, whose
            # cross-encoder imports back through repro.ir — importing
            # at module scope would close that cycle
            from ..distrib.shardings import ShardingRules
            assert self.matrix is not None, "index() before device_chunks()"
            devs = jax.devices()
            mesh = jax.sharding.Mesh(np.asarray(devs), ("data",))
            self.sharding_spec = ShardingRules().spec_for(
                self.matrix.shape, ("table_rows", "table_dim"), mesh)
            n_rows = self.matrix.shape[0]
            n = len(devs) if (len(self.sharding_spec) and
                              self.sharding_spec[0] is not None) else 1
            n = max(1, min(n, n_rows))
            bounds = [(n_rows * i) // n for i in range(n + 1)]
            self._chunks = [
                (lo, jax.device_put(jnp.asarray(self.matrix[lo:hi]),
                                    devs[i]))
                for i, (lo, hi) in enumerate(zip(bounds, bounds[1:]))
                if hi > lo]
        return self._chunks

    def topk(self, q_emb: np.ndarray, k: int, *,
             backend: str = "xla") -> Tuple[np.ndarray, np.ndarray]:
        """Global top-k over the sharded corpus: per-device partial
        top-k (fused kernel or XLA), then a host merge under the total
        order (score desc, doc index asc) — deterministic ties, so
        top-k is a prefix of top-n and cutoff fusion is sound."""
        k = int(min(k, len(self.docnos)))
        n_q = len(q_emb)
        parts_v, parts_i = [], []
        qj = np.zeros((bucket_size(n_q, floor=DEVICE_BATCH_FLOOR),
                       q_emb.shape[1]), np.float32)
        qj[:n_q] = q_emb
        qj = jnp.asarray(qj)
        for lo, chunk in self.device_chunks():
            kk = min(k, int(chunk.shape[0]))
            if backend == "pallas":
                v, i = dense_topk_op(qj, chunk, k=kk)
            else:
                v, i = _xla_chunk_topk(qj, chunk, kk)
            parts_v.append(np.asarray(v)[:n_q])
            parts_i.append(np.asarray(i)[:n_q] + lo)
        vals = np.concatenate(parts_v, axis=1)
        idxs = np.concatenate(parts_i, axis=1)
        out_v = np.empty((len(q_emb), k), np.float32)
        out_i = np.empty((len(q_emb), k), np.int64)
        for r in range(len(q_emb)):
            order = np.lexsort((idxs[r], -vals[r]))[:k]
            out_v[r] = vals[r][order]
            out_i[r] = idxs[r][order]
        return out_v, out_i

    def retriever(self, num_results: int = 100, *,
                  backend: str = "xla") -> "DenseRetriever":
        return DenseRetriever(self, num_results=num_results,
                              backend=backend)


class DenseRetriever(Transformer):
    """Q → R over a DenseIndex via the fused blocked-matmul top-k."""

    input_columns = frozenset({"qid", "query"})
    output_columns = frozenset({"qid", "query", "docno", "score", "rank"})
    key_columns = ("qid", "query")
    one_to_many = True
    shardable = True                     # row-local per qid

    def __init__(self, index: DenseIndex, num_results: int = 100, *,
                 backend: str = "xla"):
        assert backend in ("xla", "pallas"), backend
        self.index = index
        self.num_results = int(num_results)
        self.backend = backend

    def signature(self):
        return ("DenseRetriever", self.index.encoder.cfg.name,
                self.index.encoder.seed, len(self.index.docnos),
                self.num_results)

    def fingerprint_extras(self) -> Tuple:
        """Corpus content + scoring backend: re-encoding the corpus or
        switching the kernel path (whose reductions may round
        differently) must invalidate planner-inserted caches even
        though the structural ``signature()`` is unchanged."""
        return ("corpus", self.index.content_digest(),
                "backend", self.backend)

    def with_cutoff(self, k: int) -> "DenseRetriever":
        """Absorb a downstream ``RankCutoff(k)`` into the kernel's
        per-block k (the optimizer's pushdown pass, ``core/rewrite.py``).
        Sound because ``DenseIndex.topk`` resolves score ties by
        ascending doc index — a total order, so the top-k of the
        top-``num_results`` equals the global top-k for ``k <=
        num_results``."""
        if int(k) >= self.num_results:
            return self                  # already at most k results
        return DenseRetriever(self.index, num_results=int(k),
                              backend=self.backend)

    def transform(self, inp: ColFrame) -> ColFrame:
        if len(inp) == 0 or self.index.matrix is None:
            return ColFrame()
        q_emb = self.index.encoder.encode_queries(
            [str(q) for q in inp["query"].tolist()])
        k = min(self.num_results, len(self.index.docnos))
        vals, idxs = self.index.topk(q_emb, k, backend=self.backend)
        rows = []
        for i, (qid, query) in enumerate(zip(inp["qid"].tolist(),
                                             inp["query"].tolist())):
            for r in range(k):
                rows.append({"qid": qid, "query": query,
                             "docno": self.index.docnos[int(idxs[i, r])],
                             "score": float(vals[i, r]), "rank": r})
        return ColFrame.from_dicts(rows)
