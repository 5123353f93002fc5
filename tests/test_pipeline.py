"""PIR-RAG end-to-end: private retrieval returns the right documents."""
import json

import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.data import corpus as corpus_lib
from _mesh_harness import run_sub


@pytest.fixture(scope="module")
def system_and_corpus():
    corp = corpus_lib.make_corpus(0, 300, emb_dim=32, n_topics=8)
    sys = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                      n_clusters=8, kmeans_iters=15,
                                      impl="xla", seed=1)
    return sys, corp


def test_query_returns_cluster_topk_exactly(system_and_corpus):
    """Private result == plaintext within-cluster brute force (no crypto loss)."""
    sys, corp = system_and_corpus
    q = corp.embeddings[17] + 0.01
    top, stats = sys.query(q, top_k=5, key=jax.random.PRNGKey(7))
    assert len(top) == 5
    # plaintext oracle: best cosine within the (client-chosen) cluster
    from repro.core import clustering
    import jax.numpy as jnp
    cl = int(clustering.assign_to_centroids(
        jnp.asarray(q, jnp.float32)[None], jnp.asarray(sys.centroids))[0])
    assert stats.cluster_index == cl
    member_ids = [i for j in range(sys.db.n)
                  for (i, _, _) in _cluster_docs(sys, j) if j == cl]
    got_ids = [t[0] for t in top]
    qn = q / np.linalg.norm(q)
    emb = corp.embeddings[member_ids]
    oracle = np.asarray(member_ids)[np.argsort(
        -(emb / np.linalg.norm(emb, axis=1, keepdims=True)) @ qn)][:5]
    # quantized embeddings may swap near-ties; demand ≥4/5 overlap and same top-1
    assert got_ids[0] == int(oracle[0])
    assert len(set(got_ids) & set(int(x) for x in oracle)) >= 4


def _cluster_docs(sys, j):
    from repro.core import chunking
    return chunking.deserialize_docs(sys.db.matrix[:, j], sys.db.emb_dim)


def test_retrieved_text_is_original(system_and_corpus):
    sys, corp = system_and_corpus
    top, _ = sys.query(corp.embeddings[5], top_k=3,
                       key=jax.random.PRNGKey(8))
    for doc_id, _, text in top:
        assert text == corp.texts[doc_id]


def test_comm_accounting(system_and_corpus):
    sys, _ = system_and_corpus
    _, stats = sys.query(np.ones(32, np.float32), top_k=2,
                         key=jax.random.PRNGKey(9))
    assert stats.uplink_bytes == sys.db.n * 4          # one u32 per cluster
    assert stats.downlink_bytes == sys.db.m * 2        # mod-switched u16 rows
    assert stats.downlink_bytes > stats.uplink_bytes   # paper's core trade-off


def test_batched_matches_sequential(system_and_corpus):
    sys, corp = system_and_corpus
    qs = corp.embeddings[[3, 50, 120]]
    batched = sys.query_batch(qs, top_k=4, seed=3)
    for q, res in zip(qs, batched):
        solo, _ = sys.query(q, top_k=4, key=jax.random.PRNGKey(11))
        assert [d for d, _, _ in res] == [d for d, _, _ in solo]


def _per_query_loop(sys, embs, p, key, top_k):
    """The legacy plan as one `PIRClient.query` per (request, probe):
    its decoded (m, B·P) columns and its top-k lists."""
    from repro.core import chunking, clustering, pir, rerank
    import jax.numpy as jnp
    d2 = np.asarray(clustering.pairwise_sqdist(
        jnp.asarray(embs, jnp.float32), jnp.asarray(sys.centroids)))
    orders = np.argsort(d2, axis=1)[:, :p]
    client = pir.PIRClient(sys.cfg, sys.hint)
    qs, states = zip(*[client.query(jax.random.fold_in(key, b * p + j), int(c))
                       for b in range(len(embs))
                       for j, c in enumerate(orders[b])])
    cols = np.asarray(client.recover_batch(
        sys.server.answer(jnp.stack(qs, axis=1)),
        jnp.stack([st.secret for st in states], axis=1)))
    tops = []
    for b in range(len(embs)):
        docs = [d for j in range(p) for d in chunking.deserialize_docs(
            cols[:, b * p + j], sys.db.emb_dim)]
        tops.append(rerank.rerank(np.asarray(embs[b], np.float32), docs,
                                  top_k))
    return cols, tops


@pytest.mark.parametrize("multi_probe", [1, 3])
def test_batched_encrypt_serves_as_per_query_loop(system_and_corpus,
                                                  multi_probe):
    """One encrypt program per batch: same decoded columns, same top-k."""
    sys, corp = system_and_corpus
    embs = corp.embeddings[[3, 50, 120, 201]]
    key = jax.random.PRNGKey(31)
    infl = sys.query_batch_async(embs, top_k=4, multi_probe=multi_probe,
                                 key=key)
    cols, tops = _per_query_loop(sys, embs, multi_probe, key, 4)
    assert infl.pending[0].shape == (sys.db.m, len(embs) * multi_probe)
    np.testing.assert_array_equal(np.asarray(infl.pending[0]), cols)
    assert infl.complete() == tops


def test_batched_encrypt_counts_and_reuses_a(system_and_corpus, monkeypatch):
    """Obs counters read one program and B·P queries a batch; every batch
    encrypts under the server's one A, which is never regenerated."""
    from repro.core import lwe, pir
    from repro.obs import Obs
    sys, corp = system_and_corpus
    a_seen = []
    query_batch = pir.PIRClient.query_batch

    def spy(self, key, indices):
        a_seen.append(self._a_mat)
        return query_batch(self, key, indices)

    def no_regen(*args):
        raise AssertionError("A regenerated for a serving batch")

    monkeypatch.setattr(pir.PIRClient, "query_batch", spy)
    monkeypatch.setattr(lwe, "gen_public_matrix", no_regen)
    obs = Obs()
    for b, p in [(3, 1), (2, 3)]:
        sys.query_batch_async(corp.embeddings[:b], top_k=2, multi_probe=p,
                              key=jax.random.PRNGKey(b), obs=obs).complete()
    m = obs.metrics_dict()
    assert m["serve.encrypt.programs"] == 2
    assert m["serve.encrypt.batched_queries"] == 3 * 1 + 2 * 3
    assert len(a_seen) == 2
    assert a_seen[0] is a_seen[1] is sys.server.a_matrix


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_answer_counts_the_kernels_padded_columns(system_and_corpus, impl):
    """Per batch of C query columns `serve.answer.kernel_cols` adds the
    columns the answer computes: C through XLA; through the Pallas kernel
    (interpreted here) C up to 32, else whole tiles of 128.  Either way the
    decoded columns are the XLA system's."""
    from repro.kernels import ops
    from repro.obs import Obs
    ref_sys, corp = system_and_corpus
    sys = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                      n_clusters=8, kmeans_iters=15,
                                      impl=impl, seed=1)
    obs = Obs()
    for b in (3, 40):
        embs, key = corp.embeddings[:b], jax.random.PRNGKey(b)
        infl = sys.query_batch_async(embs, top_k=2, key=key, obs=obs)
        want = ref_sys.query_batch_async(embs, top_k=2, key=key)
        np.testing.assert_array_equal(np.asarray(infl.pending[0]),
                                      np.asarray(want.pending[0]))
    padded = {"xla": 3 + 40, "pallas": 3 + 128}[impl]
    assert ops.kernel_cols(3, impl) + ops.kernel_cols(40, impl) == padded
    assert obs.metrics_dict()["serve.answer.kernel_cols"] == padded


def test_build_seed_streams_are_independent():
    """One build seed, TWO independent fold_in streams.

    kmeans++ seeding and LWE setup (the public matrix A's seed) must not
    share a PRNG stream: a shared key would let a clustering-knob change
    silently re-derive A — and with it every hint, query and cached client
    state.  Asserts the two streams differ from each other and across build
    seeds, and that clustering knobs cannot move `a_seed`.
    """
    k_km, a_seed = pipeline._derive_build_streams(0)
    k_km1, a_seed1 = pipeline._derive_build_streams(1)
    assert a_seed != a_seed1
    assert not np.array_equal(np.asarray(k_km), np.asarray(k_km1))
    # the LWE seed is not drawn from the kmeans key (no shared stream)
    int_max = np.iinfo(np.int32).max
    assert a_seed != int(jax.random.randint(k_km, (), 0, int_max))
    lwe_key = jax.random.fold_in(jax.random.PRNGKey(0), pipeline._STREAM_LWE)
    assert not np.array_equal(np.asarray(lwe_key), np.asarray(k_km))
    # deterministic: the same seed re-derives the same pair
    again = pipeline._derive_build_streams(0)
    assert np.array_equal(np.asarray(again[0]), np.asarray(k_km))
    assert again[1] == a_seed

    corp = corpus_lib.make_corpus(5, 150, emb_dim=16, n_topics=4)
    base = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                       n_clusters=4, impl="xla", seed=0)
    assert base.cfg.a_seed == a_seed
    # changing cluster seeding inputs must leave key material untouched
    for kw in (dict(n_clusters=6), dict(kmeans_iters=3),
               dict(n_clusters=4, balance_factor=1.5)):
        other = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                            impl="xla", seed=0,
                                            **{"n_clusters": 4, **kw})
        assert other.cfg.a_seed == base.cfg.a_seed
        if other.cfg.n == base.cfg.n:      # A's shape is (n_clusters, k)
            assert np.array_equal(np.asarray(other.server.a_matrix),
                                  np.asarray(base.server.a_matrix))
    # ... and a different build seed moves BOTH streams
    moved = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                        n_clusters=4, impl="xla", seed=1)
    assert moved.cfg.a_seed == a_seed1
    assert not np.array_equal(moved.centroids, base.centroids)
    # the kmeans stream is exactly the derived fold_in stream
    from repro.core import clustering
    km = clustering.kmeans_fit(k_km, corp.embeddings.astype(np.float32),
                               k=4, iters=25,
                               n_blocks=clustering.BUILD_BLOCKS)
    assert np.array_equal(np.asarray(km.centroids), base.centroids)


def test_balanced_build_reduces_downlink():
    corp = corpus_lib.make_corpus(3, 200, emb_dim=16, n_topics=4)
    plain = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                        n_clusters=8, impl="xla", seed=0)
    balanced = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                           n_clusters=8, impl="xla", seed=0,
                                           balance_factor=1.3)
    assert balanced.db.m <= plain.db.m                 # beyond-paper win
    q = corp.embeddings[0]
    top, _ = balanced.query(q, top_k=3, key=jax.random.PRNGKey(1))
    assert top and top[0][1] > 0.5


SERVE_SPANS_BODY = """
import json
from repro.core import pipeline
from repro.data import corpus as corpus_lib
from repro.launch.mesh import make_chunk_mesh
from repro.obs import Obs
from repro.serve.engine import PipelinedServeLoop

corp = corpus_lib.make_corpus(0, 300, emb_dim=32, n_topics=8)
mesh = make_chunk_mesh({n}) if {n} > 1 else None
system = pipeline.PirRagSystem.build(corp.texts, corp.embeddings,
                                     n_clusters=8, kmeans_iters=15,
                                     impl="xla", seed=1, mesh=mesh)
loop = PipelinedServeLoop(system, max_batch=3, deadline_ms=0.0, depth=2,
                          seed=0, obs=Obs(trace=True))
for rid in range(7):
    loop.submit(rid, corp.embeddings[rid * 40], top_k=4,
                multi_probe=1 + rid % 2)
loop.drain()
spans = loop.obs.tracer.spans
by_sid = {{s.sid: s for s in spans}}
same = []
for p in (1, 2):
    embs, key = corp.embeddings[[5, 77, 150]], jax.random.PRNGKey(p)
    with_obs = system.query_batch_async(embs, top_k=4, multi_probe=p,
                                        key=key, obs=Obs()).complete()
    same.append(with_obs == system.query_batch_async(
        embs, top_k=4, multi_probe=p, key=key).complete())
print(json.dumps({{
    "n_shards": system.server.n_shards, "m": system.db.m, "n": system.db.n,
    "columns": [s.attrs["batch"] * s.attrs["multi_probe"] for s in spans
                if s.name == "serve.plan"],
    "parents": [[s.name, by_sid[s.parent].name] for s in spans
                if s.name.count(".") == 3
                or s.name.startswith("serve.complete.")],
    "counters": loop.obs.metrics_dict(), "same_topk": same}}))
"""


@pytest.mark.parametrize("n_devices", [1, 4])
def test_dispatch_and_complete_stages_are_spans_and_counted(n_devices):
    """On one device and on a four-device row-sharded mesh: the answer and
    decode are spans inside the dispatch, the parse and rerank inside the
    complete; per batch of C query columns the counters add the shard
    count, 4·n·C bytes of queries a shard and m·C fetched bytes; an `Obs`
    changes no top-k list."""
    out = json.loads(run_sub(SERVE_SPANS_BODY.format(n=n_devices),
                             n_devices=n_devices).strip().splitlines()[-1])
    shards, cols = out["n_shards"], out["columns"]
    assert shards == n_devices and len(cols) >= 3
    assert sorted(map(tuple, out["parents"])) == sorted(
        [("serve.plan.dispatch.answer", "serve.plan.dispatch"),
         ("serve.plan.dispatch.decode", "serve.plan.dispatch"),
         ("serve.complete.fetch", "serve.complete"),
         ("serve.complete.parse", "serve.complete"),
         ("serve.complete.rerank", "serve.complete")] * len(cols))
    c = out["counters"]
    assert c["serve.answer.chips"] == shards * len(cols)
    assert c["serve.answer.kernel_cols"] == sum(cols)
    assert c["serve.plan.query_bytes"] == 4 * out["n"] * sum(cols) * shards
    assert c["serve.complete.fetch_bytes"] == out["m"] * sum(cols)
    assert c["serve.encrypt.batched_queries"] == sum(cols)
    assert out["same_topk"] == [True, True]
