"""RAG serving: retrieval as a host-side flexible op over the paged pool.

The subsystem invariant mirrors the paged scheduler's: RAG is prompt
ASSEMBLY plus scheduling, never numerics. A drain through
``submit_query`` — retrieval between segment dispatches, chunk-level KV
splicing, overlap on or off — must produce exactly the tokens that
plain ``submit()`` of the same assembled prompts produces (greedy and
sampled, GQA and MLA+MoE), which the paged suite in turn pins to solo
decode. On top of that, the payoff must be real: distinct queries whose
retrieved sets overlap share chunk-addressed KV blocks
(``retrieval_chunk_hits > 0``), and an LRU-evicted leading block no
longer voids the surviving interior blocks (interior-hole splicing).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfglib
from repro.launch.sampling import SamplingParams
from repro.launch.scheduler import PagedContinuousBatchingServer
from repro.models.registry import get_model
from repro.retrieval import (
    ChunkedCorpus,
    EmbeddingIndex,
    RagPipeline,
    make_toy_corpus,
)

ARCHS = ["nemotron-4-15b", "deepseek-v3-671b"]
BS = 8


def _cfg(arch: str):
    cfg = cfglib.get_smoke_config(arch)
    if cfg.num_experts:
        # no-drop capacity: co-scheduled rows must not change expert
        # routing — the same bit-parity caveat as prompt bucketing
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    return cfg


@pytest.fixture(scope="module")
def served():
    out = {}
    for arch in ARCHS:
        cfg = _cfg(arch)
        api = get_model(cfg)
        out[arch] = (cfg, api.init(jax.random.PRNGKey(0), cfg))
    return out


def _rag(cfg, *, block_size=BS, top_k=2, chunk_tokens=BS, n_docs=4,
         doc_len=32, seed=0, **kw):
    docs = make_toy_corpus(cfg.vocab_size, n_docs=n_docs, doc_len=doc_len,
                           seed=seed)
    corpus = ChunkedCorpus(docs, chunk_tokens=chunk_tokens)
    index = EmbeddingIndex(corpus, vocab_size=cfg.vocab_size, seed=seed)
    return docs, RagPipeline(index, system_prefix=[5, 6, 7],
                             block_size=block_size, top_k=top_k, **kw)


def _server(cfg, params, *, rag=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefill_chunk", BS)
    kw.setdefault("segment", 4)
    return PagedContinuousBatchingServer(cfg, params, rag=rag, **kw)


# ---------------------------------------------------------------------------
# Pipeline: determinism, layout, validation (no model needed).
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_block_aligned():
    cfg = _cfg("nemotron-4-15b")
    _, pipe1 = _rag(cfg)
    _, pipe2 = _rag(cfg)
    q = np.asarray([11, 12, 13], np.int32)
    a, b = pipe1.assemble(q), pipe2.assemble(q)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert [c.chunk_id for c in a.chunks] == [c.chunk_id for c in b.chunks]
    # layout contract: system prefix padded to a block multiple, every
    # chunk starts on a block boundary and covers whole blocks
    assert pipe1.system_prefix.size % BS == 0
    for c in a.chunks:
        assert c.offset % BS == 0
        assert c.tokens.size % BS == 0
        np.testing.assert_array_equal(
            a.tokens[c.offset:c.offset + c.tokens.size], c.tokens)
    np.testing.assert_array_equal(a.tokens[-q.size:], q)
    assert a.tokens.size == pipe1.prompt_len_for + q.size
    # canonical order: chunk ids ascend, independent of score order
    ids = [c.chunk_id for c in a.chunks]
    assert ids == sorted(ids)
    # chunk_blocks names exactly the retrieved-chunk block indices
    blocks = a.chunk_blocks(BS)
    assert len(blocks) == sum(c.tokens.size // BS for c in a.chunks)
    assert min(blocks) == pipe1.system_prefix.size // BS


def test_index_retrieves_own_document_first():
    cfg = _cfg("nemotron-4-15b")
    docs, pipe = _rag(cfg, n_docs=4, doc_len=32)
    for d in range(4):
        ranked = pipe.index.search(docs[d][:8], 2)
        top = pipe.index.corpus.chunks[ranked[0][0]]
        assert top.doc == d, f"query from doc {d} ranked doc {top.doc} first"


def test_alignment_validation():
    cfg = _cfg("nemotron-4-15b")
    docs = make_toy_corpus(cfg.vocab_size, n_docs=2, doc_len=32)
    corpus = ChunkedCorpus(docs, chunk_tokens=6)     # not a multiple of 8
    index = EmbeddingIndex(corpus, vocab_size=cfg.vocab_size)
    with pytest.raises(ValueError, match="multiple of block_size"):
        RagPipeline(index, system_prefix=[1], block_size=8)
    with pytest.raises(ValueError, match="full chunk"):
        ChunkedCorpus([np.asarray([1, 2], np.int32)], chunk_tokens=8)


# ---------------------------------------------------------------------------
# Scheduler: bit-exactness, chunk reuse, overlap, validation.
# ---------------------------------------------------------------------------

def _queries(docs, rng, n):
    """Queries drawn from document content so retrieval sets overlap
    across distinct queries (same docs -> same chunks)."""
    out = []
    for i in range(n):
        d = docs[rng.randint(len(docs) // 2)]       # concentrate on 2 docs
        lo = rng.randint(0, d.size - 6)
        out.append(d[lo:lo + rng.randint(3, 7)].copy())
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_rag_drain_bit_exact_vs_plain_submit(arch, served):
    """Greedy and sampled queries through submit_query produce EXACTLY
    the tokens plain submit() of the same assembled prompts produces —
    retrieval, chunk splicing and overlap never touch numerics."""
    cfg, params = served[arch]
    docs, pipe = _rag(cfg)
    rag_srv = _server(cfg, params, rag=pipe)
    rng = np.random.RandomState(7)
    qs = _queries(docs, rng, 5)
    samples = [None, SamplingParams(temperature=0.8, seed=11), None,
               SamplingParams(temperature=1.1, top_k=20, seed=3), None]
    rids = [rag_srv.submit_query(q, 5, s) for q, s in zip(qs, samples)]
    done = {r.rid: r for r in rag_srv.run()}
    assert sorted(done) == sorted(rids)
    assert rag_srv.stats.retrievals == len(qs)
    # the same assembled prompts through the plain path, fresh pool
    plain = _server(cfg, params)
    plain_rids = [plain.submit(rag_srv.rag_results[rid].tokens, 5, s)
                  for rid, s in zip(rids, samples)]
    plain_done = {r.rid: r for r in plain.run()}
    for rid, prid in zip(rids, plain_rids):
        np.testing.assert_array_equal(
            done[rid].tokens, plain_done[prid].tokens,
            err_msg=f"{arch} rid {rid}: RAG drain != plain submit")


def test_chunk_reuse_across_distinct_queries(served):
    """DISTINCT queries whose retrieved sets overlap splice each other's
    chunk blocks: nonzero retrieval_chunk_hits, and the hit rate the
    stats report is the block-level fraction."""
    cfg, params = served["nemotron-4-15b"]
    docs, pipe = _rag(cfg)
    srv = _server(cfg, params, rag=pipe)
    # three different queries into the same document -> same chunks
    for q in (docs[0][:5], docs[0][10:16], docs[0][3:9]):
        srv.submit_query(q, 4)
    srv.run()
    st = srv.stats
    assert st.retrieval_chunk_blocks > 0
    assert st.retrieval_chunk_hits > 0, "no chunk-level reuse"
    assert 0 < st.retrieval_chunk_hit_rate <= 1
    # block-granular prefix accounting: the denominator is prompt
    # blocks walked, so the rate is hits over walked blocks exactly
    assert st.prefix_prompt_blocks >= st.prefix_block_hits > 0
    assert 0 < st.prefix_hit_rate <= 1
    assert st.prefix_hit_rate == (st.prefix_block_hits
                                  / st.prefix_prompt_blocks)
    assert "retrieval" in st.summary()


def test_overlap_on_off_token_equality(served):
    """rag_overlap=True (retrieval hidden behind the in-flight segment)
    and rag_overlap=False (serial retrieval) produce identical tokens;
    the overlap arm actually overlaps when queries arrive mid-decode."""
    cfg, params = served["nemotron-4-15b"]
    tokens = {}
    for overlap in (True, False):
        docs, pipe = _rag(cfg)
        srv = _server(cfg, params, rag=pipe, rag_overlap=overlap)
        r0 = srv.submit_query(docs[0][:5], 24)      # long: keeps decoding
        done = srv.step()
        late = [srv.submit_query(docs[1][:6], 6),
                srv.submit_query(docs[0][3:9], 6)]
        while srv._has_work():
            done += srv.step(draining=True)
        tokens[overlap] = {r.rid: r.tokens for r in done}
        assert sorted(tokens[overlap]) == sorted([r0] + late)
        if overlap:
            assert srv.stats.retrieval_overlapped == 2
            assert srv.stats.retrieval_overlap_frac > 0
        else:
            assert srv.stats.retrieval_overlapped == 0
    for rid in tokens[True]:
        np.testing.assert_array_equal(tokens[True][rid], tokens[False][rid])


def test_interior_hole_splice_end_to_end(served):
    """LRU eviction of a LEADING prompt block no longer voids the
    surviving later blocks: the re-walk splices them at their interior
    chunk boundaries, staging prefills only the hole, and the drain
    stays bit-exact with the cold run."""
    cfg, params = served["nemotron-4-15b"]
    srv = _server(cfg, params, num_slots=1, block_size=4, prefill_chunk=4,
                  max_len=64)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, size=17).astype(np.int32)
    srv.submit(prompt, 4)
    (r0,) = srv.run()
    assert srv.mgr.alloc.evict_cached(1) == 1       # LRU = leading block
    srv.submit(prompt, 4)
    (r1,) = srv.run()
    np.testing.assert_array_equal(r0.tokens, r1.tokens)
    assert srv.stats.chunk_interior_hits >= 3, "interior blocks recomputed"


def test_submit_query_validation(served):
    cfg, params = served["nemotron-4-15b"]
    plain = _server(cfg, params)
    with pytest.raises(ValueError, match="needs a RagPipeline"):
        plain.submit_query([1, 2], 4)
    docs, pipe = _rag(cfg)
    srv = _server(cfg, params, rag=pipe)
    with pytest.raises(ValueError, match="empty query"):
        srv.submit_query([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.submit_query([1], 0)
    # eager length check: assembled size is deterministic pre-retrieval
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.submit_query(np.arange(60, dtype=np.int32), 30)
    # pipeline/pool block-size mismatch refused at construction
    with pytest.raises(ValueError, match="block_size"):
        _server(cfg, params, rag=pipe, block_size=4)


def test_cancel_parked_query(served):
    """A query cancelled before its retrieval turn vanishes: never
    retrieved, never decoded, load drops immediately."""
    cfg, params = served["nemotron-4-15b"]
    docs, pipe = _rag(cfg)
    srv = _server(cfg, params, rag=pipe)
    keep = srv.submit_query(docs[0][:5], 3)
    drop = srv.submit_query(docs[1][:5], 3)
    assert srv.load == 2
    assert srv.cancel(drop)
    assert srv.load == 1
    assert not srv.cancel(drop)
    done = srv.run()
    assert [r.rid for r in done] == [keep]
    assert srv.stats.retrievals == 1
    assert srv.stats.cancelled == 1
