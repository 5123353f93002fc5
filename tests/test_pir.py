"""End-to-end SimplePIR protocol tests: exact private column retrieval."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _mesh_harness import run_sub

from repro.core import lwe, pir


def _setup(m=192, n=512, q_switch=1 << 16, seed=0, impl="xla"):
    rng = np.random.default_rng(seed)
    db = jnp.asarray(rng.integers(0, 256, (m, n), dtype=np.uint8))
    cfg = pir.make_config(m, n, impl=impl, q_switch=q_switch)
    server = pir.PIRServer(cfg, db)
    hint = server.setup()
    client = pir.PIRClient(cfg, hint)
    return db, cfg, server, client


@pytest.mark.parametrize("q_switch", [None, 1 << 16])
def test_e2e_exact_retrieval(q_switch):
    db, cfg, server, client = _setup(q_switch=q_switch)
    for i, idx in enumerate([0, 7, 511]):
        qu, state = client.query(jax.random.PRNGKey(100 + i), idx)
        ans = server.answer(qu)
        got = client.recover(ans, state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(db[:, idx]))


def test_e2e_with_pallas_server():
    db, cfg, server, client = _setup(m=64, n=128, impl="pallas")
    qu, state = client.query(jax.random.PRNGKey(0), 42)
    ans = server.answer(qu)
    np.testing.assert_array_equal(np.asarray(client.recover(ans, state)),
                                  np.asarray(db[:, 42]))


def test_batched_answers_match_individual():
    """Server GEMM over stacked queries == per-query GEMVs (multi-client)."""
    db, cfg, server, client = _setup()
    keys = [jax.random.PRNGKey(i) for i in range(4)]
    idxs = [3, 99, 200, 511]
    qus, states = zip(*[client.query(k, i) for k, i in zip(keys, idxs)])
    batch = jnp.stack(qus, axis=1)                      # (n, B)
    ans_b = server.answer(batch)                        # (m, B)
    for j, (state, idx) in enumerate(zip(states, idxs)):
        got = client.recover(ans_b[:, j], state)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(db[:, idx]))


@pytest.mark.parametrize("c", [1, 5, 16])
def test_query_batch_bit_identical_to_per_query_loop(c):
    """One-program encrypt == stacked query(fold_in(key, i), idx[i])."""
    db, cfg, server, client = _setup()
    key = jax.random.PRNGKey(2024)
    idx = np.random.default_rng(c).integers(0, cfg.n, c)
    qs, secrets = client.query_batch(key, idx)
    want = [client.query(jax.random.fold_in(key, i), int(j))
            for i, j in enumerate(idx)]
    assert qs.shape == (cfg.n, c) and qs.dtype == jnp.uint32
    assert secrets.shape == (cfg.params.k, c) and secrets.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(qs), np.stack([np.asarray(q) for q, _ in want], axis=1))
    np.testing.assert_array_equal(
        np.asarray(secrets),
        np.stack([np.asarray(st.secret) for _, st in want], axis=1))
    got = client.recover_batch(server.answer(qs), secrets)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(db)[:, idx])


def test_client_takes_the_servers_public_matrix():
    db, cfg, server, _ = _setup()
    client = pir.PIRClient(cfg, server.setup(), a_matrix=server.a_matrix)
    assert client._a_mat is server.a_matrix
    np.testing.assert_array_equal(
        np.asarray(server.a_matrix),
        np.asarray(lwe.gen_public_matrix(cfg.a_seed, cfg.n, cfg.params.k)))


def test_uplink_downlink_accounting():
    _, cfg, _, _ = _setup(m=1000, n=256)
    assert cfg.uplink_bytes == 256 * 4
    assert cfg.downlink_bytes == 1000 * 2      # modulus-switched u16
    cfg_raw = pir.make_config(1000, 256, q_switch=None)
    assert cfg_raw.downlink_bytes == 1000 * 4  # raw u32
    assert cfg.hint_bytes == 1000 * cfg.params.k * 4


def test_config_rejects_unsafe_noise():
    params = lwe.LWEParams(p=256, sigma=1e7)
    with pytest.raises(ValueError):
        pir.PIRConfig(m=8, n=1 << 14, params=params)


def test_two_queries_same_column_different_ciphertexts():
    """Fresh randomness per query: same index ⇒ different uplink bytes."""
    _, _, server, client = _setup()
    qu1, _ = client.query(jax.random.PRNGKey(1), 5)
    qu2, _ = client.query(jax.random.PRNGKey(2), 5)
    assert not np.array_equal(np.asarray(qu1), np.asarray(qu2))


@pytest.mark.parametrize("m", [1000, 1002])     # 4 | m, and 4 ∤ m
def test_sharded_results_keep_their_rows_on_their_device(m):
    """A 4-device row-sharded server: the hint, answers, the client decode
    and a commit's ΔH hold m/4 rows per device, come from programs with no
    collective, and equal the 1-device values.  An m the shards do not
    divide needs pad rows; its results are cut back to m rows."""
    run_sub(f"""
from repro.core import pir
from repro.launch.mesh import make_chunk_mesh

rng = np.random.default_rng(0)
m, n = {m}, 96
db = rng.integers(0, 256, (m, n), dtype=np.uint8)
cfg = pir.make_config(m, n, impl="xla")
s1 = pir.PIRServer(cfg, jnp.asarray(db))
s4 = pir.PIRServer(cfg, db, mesh=make_chunk_mesh(4))
assert bool(s4._row_pad) == (m % 4 != 0)

def rows_ok(x):   # m rows in all, and m/4 on each device where 4 | m
    per = sorted({{sh.data.shape[0] for sh in x.addressable_shards}})
    return x.shape[0] == m and (m % 4 != 0 or per == [m // 4])

h1, h4 = s1.setup(), s4.setup()
assert rows_ok(h4)
np.testing.assert_array_equal(np.asarray(h1), np.asarray(h4))

client = pir.PIRClient(cfg, h4)
qu, st = client.query(jax.random.PRNGKey(0), 7)
ans = s4.answer(jnp.stack([qu, qu], 1))
assert rows_ok(ans)
got = client.recover_batch(ans, jnp.stack([st.secret, st.secret], 1))
assert rows_ok(got)
np.testing.assert_array_equal(np.asarray(got)[:, 0], db[:, 7])

cols = jnp.asarray([3, 40])
new = jnp.asarray(rng.integers(0, 256, (m, 2), dtype=np.uint8))
d1, d4 = s1.update_columns(cols, new), s4.update_columns(cols, new)
assert rows_ok(d4)
np.testing.assert_array_equal(np.asarray(d1), np.asarray(d4))

# the programs the server dispatches, on operands placed as it places them
cols2 = jax.device_put(s4.db[:, :2], s4._db_sharding)
rep = lambda x: jax.device_put(x, s4._replicated)
progs = {{"hint": (s4._hint_fn, s4.db, rep(jnp.zeros((n, 8), jnp.uint32))),
          "answer": (s4._answer_fn, s4.db,
                     rep(jnp.zeros((n, 2), jnp.uint32))),
          "delta": (s4._delta_fn, cols2, cols2,
                    rep(jnp.zeros((2, 8), jnp.uint32)))}}
for name, (fn, *args) in progs.items():
    text = fn.lower(*args).compile().as_text()
    moved = [c for c in ("all-reduce", "all-gather", "all-to-all",
                         "collective-permute", "reduce-scatter") if c in text]
    assert not moved or m % 4, (name, moved)
print("OK")
""", n_devices=4)
