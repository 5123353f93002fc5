"""SimplePIR-style single-server PIR over a chunk-transposed database.

Protocol roles (honest-but-curious server):

  offline   server:  hint H = D·A  (one-time; A from a public seed)
            client:  downloads H (m×k u32) once
  online    client:  qu = A·s + e + Δ·onehot(i)          — uplink n·4 bytes
            server:  ans = D·qu (mod 2^32)               — ONE modular GEMV
            client:  decode(ans − H·s) → column i of D   — the whole cluster

The answer step is the system hot loop; it dispatches to the Pallas MXU
kernel on TPU (`kernels/ops.modmatmul`).  Batched serving stacks queries from
many clients into the column dimension, turning the GEMV into a GEMM.

Beyond-paper: modulus-switched responses (q → 2^16) halve the downlink at a
rounding-noise cost accounted in `lwe.noise_budget_ok`.

Sharded serving (beyond-paper, `distributed.sharding.pir_rules`): pass
``mesh=`` to row-shard the packed DB over the device mesh.  Queries
replicate; every shard computes its own hint rows H_s = D_s·A and answer
slice ans_s = D_s·qu with ZERO collectives (the contraction dim — the
cluster axis — is never split), and the client decodes the concatenation.
All sharded arithmetic is the same exact mod-2^32 kernel path, so results
are bit-identical to the single-device layout (property-tested under the
8-fake-device harness in tests/test_sharded_pir.py).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import lwe
from repro.kernels import ops

U32 = jnp.uint32


@dataclasses.dataclass(frozen=True)
class PIRConfig:
    m: int                       # DB rows (cluster content bytes / entry)
    n: int                       # DB cols (number of clusters)
    params: lwe.LWEParams
    a_seed: int = 7              # public seed for the LWE matrix A
    impl: str = "auto"           # kernel dispatch for the server GEMM

    def __post_init__(self):
        if not lwe.noise_budget_ok(self.params, self.n):
            raise ValueError(
                f"LWE noise budget violated for n={self.n}, p={self.params.p}")

    @property
    def uplink_bytes(self) -> int:
        """Query size: one u32 ciphertext entry per DB column (n·4)."""
        return self.n * 4

    @property
    def downlink_bytes(self) -> int:
        """Response size: m words — 2 B each when modulus-switched ≤ 2^16."""
        qs = self.params.q_switch
        per = 2 if (qs is not None and qs <= 1 << 16) else 4
        return self.m * per

    @property
    def hint_bytes(self) -> int:
        """One-time client download: the (m, k) u32 hint H = D·A."""
        return self.m * self.params.k * 4


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------

class PIRServer:
    """Holds the plaintext DB (u8, entries < p) and answers encrypted queries.

    With ``mesh=`` the DB row-shards over the mesh (the ``chunks`` logical
    axis of `sharding.pir_rules`); rows are zero-padded up to a multiple of
    the shard count so `shard_map` sees equal slices (a DB packed in the
    default 256-byte chunks needs none).  The padding rows are all-zero
    on both the DB and the hint, so answers/decodes are unaffected — every
    public method still speaks global (m, ...) shapes.  Each sharded call
    is one compiled program, the cut back to m rows included, so what it
    moves between devices shows in that program's HLO.

    ``db`` accepts three layouts (all (m, n) uint8 semantics):

      * a jax array — committed/resharded as before;
      * a host numpy array — padded host-side and transferred straight into
        the sharded layout (no device-0 commit);
      * a list/tuple of S per-shard host row slices ((m_pad/S, n) each,
        e.g. ``ChunkedDB.row_shards``) — each slice is placed directly on
        its owning device and assembled with
        `jax.make_array_from_single_device_arrays`, so the full DB is never
        materialized on (or resharded through) a single device.  This is
        the sharded offline build's in-place construction path.
    """

    def __init__(self, cfg: PIRConfig, db, *,
                 mesh=None, mesh_axes: tuple[str, ...] | None = None):
        self.cfg = cfg
        self.mesh = mesh
        self.mesh_axes: tuple[str, ...] | None = None
        self._row_pad = 0
        if mesh is not None:
            from repro.core import clustering
            axes, shards = clustering.resolve_mesh_axes(mesh, mesh_axes)
            self.mesh_axes = axes
            self.n_shards = shards
            self._row_pad = (-cfg.m) % shards
            self._db_sharding = NamedSharding(mesh,
                                              PartitionSpec(axes, None))
            self._replicated = NamedSharding(mesh, PartitionSpec())
            if isinstance(db, (list, tuple)):
                db = self._assemble_row_shards(db)
            elif isinstance(db, np.ndarray):
                assert db.shape == (cfg.m, cfg.n), (db.shape, (cfg.m, cfg.n))
                assert db.dtype == np.uint8
                if self._row_pad:
                    padded = np.zeros((cfg.m + self._row_pad, cfg.n),
                                      np.uint8)
                    padded[:cfg.m] = db
                    db = padded
                db = jax.device_put(db, self._db_sharding)
            else:
                assert db.shape == (cfg.m, cfg.n), (db.shape, (cfg.m, cfg.n))
                assert db.dtype == jnp.uint8
                if self._row_pad:
                    db = jnp.pad(jnp.asarray(db),
                                 ((0, self._row_pad), (0, 0)))
                db = jax.device_put(db, self._db_sharding)
        else:
            self.n_shards = 1
            if isinstance(db, np.ndarray):
                db = jnp.asarray(db)
            assert db.shape == (cfg.m, cfg.n), (db.shape, (cfg.m, cfg.n))
            assert db.dtype == jnp.uint8
        self.db = db
        self._a_mat: jax.Array | None = None   # lazy; immutable per config
        self._answer_fn = None                 # cached shard_map'd hot path
        self._hint_fn = None
        self._delta_fn = None

    def _assemble_row_shards(self, shards) -> jax.Array:
        """Place per-shard host row slices device-by-device and assemble.

        shards: S host arrays of shape (m_pad/S, n) u8 in row order (row
        padding, if any, lives in the last slice).  Each slice transfers to
        exactly the device that owns its rows under the P(axes, None)
        sharding — the global array exists only as the assembled sharded
        view, never on one device.
        """
        m_pad = self.cfg.m + self._row_pad
        rows_per = m_pad // self.n_shards
        assert len(shards) == self.n_shards, (len(shards), self.n_shards)
        shape = (m_pad, self.cfg.n)
        arrays = []
        dmap = self._db_sharding.addressable_devices_indices_map(shape)
        for dev, idx in dmap.items():
            lo = idx[0].start or 0
            block = np.ascontiguousarray(shards[lo // rows_per])
            assert block.shape == (rows_per, self.cfg.n), (
                block.shape, (rows_per, self.cfg.n))
            assert block.dtype == np.uint8
            arrays.append(jax.device_put(block, dev))
        return jax.make_array_from_single_device_arrays(
            shape, self._db_sharding, arrays)

    @property
    def a_matrix(self) -> jax.Array:
        """The public LWE matrix A (seed-derived, cached across commits)."""
        if self._a_mat is None:
            self._a_mat = lwe.gen_public_matrix(
                self.cfg.a_seed, self.cfg.n, self.cfg.params.k)
        return self._a_mat

    def setup(self) -> jax.Array:
        """Offline hint H = D·A ∈ Z_q^{m×k} (the heavy one-time GEMM).

        Sharded servers compute per-shard hint rows H_s = D_s·A in place
        (zero collectives) and return the global (m, k) view; the client
        downloads it once, exactly like the single-device hint.
        """
        if self.mesh is None:
            return ops.hint_gemm(self.db, self.a_matrix, impl=self.cfg.impl)
        if self._hint_fn is None:
            from repro.distributed import collectives
            self._hint_fn = self._cut_to_m(collectives.row_shard_gemm(
                self.mesh, self.mesh_axes, impl=self.cfg.impl))
        a_rep = jax.device_put(self.a_matrix, self._replicated)
        return self._hint_fn(self.db, a_rep)

    def answer(self, qu: jax.Array) -> jax.Array:
        """Online answer: D·qu mod 2^32.  qu: (n,) or (n, batch) uint32.

        Sharded servers replicate qu and run the shard_map'd row GEMM —
        each device answers its own row slice, no collectives.
        """
        if self.mesh is None:
            ans = ops.modmatmul(self.db, qu, impl=self.cfg.impl)
            if self.cfg.params.q_switch is not None:
                ans = lwe.switch_modulus(ans, self.cfg.params.q_switch)
            return ans
        if self._answer_fn is None:
            from repro.distributed import collectives
            self._answer_fn = self._cut_to_m(collectives.row_shard_gemm(
                self.mesh, self.mesh_axes, impl=self.cfg.impl,
                q_switch=self.cfg.params.q_switch))
        was_vec = qu.ndim == 1
        q2 = qu[:, None] if was_vec else qu
        ans = self._answer_fn(self.db,
                              jax.device_put(q2, self._replicated))
        return ans[:, 0] if was_vec else ans

    def _cut_to_m(self, fn):
        """``fn`` with its result cut to the m logical rows, in one program.

        Only an m that the shard count does not divide has pad rows; such a
        result cannot stay row-sharded, so the cut moves rows between
        devices.  A DB packed in the default 256-byte chunks has none on
        up to 256 shards, so its programs move nothing.
        """
        if not self._row_pad:
            return fn
        m = self.cfg.m
        return jax.jit(lambda *args: fn(*args)[:m])

    def _pad_new_cols(self, cols: jax.Array, new_cols: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
        """Validate shapes and extend new columns with the shard-pad rows."""
        cols = jnp.asarray(cols)
        new_cols = jnp.asarray(new_cols)
        assert new_cols.shape == (self.cfg.m, int(cols.shape[0]))
        assert new_cols.dtype == jnp.uint8
        if self._row_pad:
            # DB padding rows are zero and stay zero across mutations
            new_cols = jnp.pad(new_cols, ((0, self._row_pad), (0, 0)))
        return cols, new_cols

    def stage_delta(self, cols: jax.Array, new_cols: jax.Array) -> jax.Array:
        """Dispatch the hint delta ΔH = (D_new−D_old)[:,J]·A[J,:] for a
        column swap WITHOUT touching ``self.db``.

        Reads the old columns from the live DB (so it must run before any
        donating scatter of the same swap) and returns the (m, k) u32 ΔH
        as an in-flight device value.  Pow-of-two bucketed like
        `update_columns` so streamed batches reuse compiled shapes; pad
        slots carry the live DB's column 0 on BOTH sides of the
        subtraction, contributing exactly ΔH = 0.
        """
        cols, new_cols = self._pad_new_cols(cols, new_cols)
        j = int(cols.shape[0])
        old_cols = self.db[:, cols]
        bucket = 1 << max(0, (j - 1).bit_length())
        pad = min(bucket, self.cfg.n) - j
        if pad > 0:
            cols_g = jnp.concatenate([cols, jnp.zeros(pad, cols.dtype)])
            unchanged = jnp.repeat(self.db[:, :1], pad, axis=1)
            new_g = jnp.concatenate([new_cols, unchanged], axis=1)
            old_g = jnp.concatenate([old_cols, unchanged], axis=1)
        else:
            cols_g, new_g, old_g = cols, new_cols, old_cols
        a_j = self.a_matrix[cols_g]                        # (J', k)
        if self.mesh is None:
            return ops.delta_gemm(new_g, old_g, a_j, impl=self.cfg.impl)
        if self._delta_fn is None:
            from repro.distributed import collectives
            self._delta_fn = self._cut_to_m(collectives.row_shard_delta_gemm(
                self.mesh, self.mesh_axes, impl=self.cfg.impl))
        return self._delta_fn(
            jax.device_put(new_g, self._db_sharding),
            jax.device_put(old_g, self._db_sharding),
            jax.device_put(a_j, self._replicated))

    def stage_scatter(self, cols: jax.Array, new_cols: jax.Array, *,
                      donate: bool = False) -> jax.Array:
        """The patched DB array for a column swap; ``self.db`` unassigned.

        ``donate=True`` donates the live DB buffer into the scatter — the
        caller must assign the result to ``self.db`` immediately (the
        live-index publish step does) and no NEW Python-side use of the old
        array may follow; computations already enqueued keep the buffer
        alive at the runtime level.
        """
        cols, new_cols = self._pad_new_cols(cols, new_cols)
        if self.mesh is None:
            return ops.scatter_columns(self.db, cols, new_cols,
                                       donate=donate)
        if donate:
            from repro.distributed import collectives
            scatter = collectives.row_shard_scatter(
                self.mesh, self.mesh_axes, donate=True)
            return scatter(self.db, cols,
                           jax.device_put(new_cols, self._db_sharding))
        return jax.device_put(self.db.at[:, cols].set(new_cols),
                              self._db_sharding)

    def stage_update(self, cols: jax.Array, new_cols: jax.Array, *,
                     donate: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
        """Compute (new_db, ΔH) for a column swap WITHOUT publishing it.

        The shadow-epoch half of `update_columns`: `stage_delta` (which
        reads the old columns first) then `stage_scatter`.  With
        ``donate=True`` the live buffer is consumed HERE, so only callers
        that assign ``self.db`` unconditionally afterwards may pass it —
        `update_columns` does; the live-index stage path instead defers the
        donating scatter to its publish step so an aborted or dropped
        staged epoch never strands ``self.db`` on a deleted buffer.
        """
        delta_h = self.stage_delta(cols, new_cols)
        return self.stage_scatter(cols, new_cols, donate=donate), delta_h

    def update_columns(self, cols: jax.Array, new_cols: jax.Array, *,
                       donate: bool = False) -> jax.Array:
        """Replace DB columns J and return the exact hint delta.

        The hint is linear in the database, so a mutation confined to columns
        J patches it with a sparse GEMM instead of a full rebuild:

            ΔH = ΔD[:,J] · A[J,:]  =  D_new[:,J]·A[J,:] − D_old[:,J]·A[J,:]

        Both products go through the same `ops.modmatmul` kernel path as the
        offline hint, so `H + ΔH` is bit-identical to `setup()` on the
        updated DB (all arithmetic exact mod 2^32).

        cols: (J,) int column indices.  new_cols: (m, J) uint8.
        Returns ΔH: (m, k) uint32.

        The GEMM is bucketed: J is padded up to a power of two with columns
        whose "new" contents equal their current contents, so padding slots
        cancel exactly in ΔH while streamed mutation batches of varying size
        reuse a handful of compiled shapes instead of recompiling per batch.

        Sharded servers scatter the new columns into the row-sharded DB and
        run the delta GEMM shard_map'd: each shard patches only the hint
        rows it owns, so the live-index commit is collective-free like the
        answer path.

        ``donate=True`` patches the DB buffer in place (see `stage_update`);
        callers must not retain the pre-update ``self.db`` array.
        """
        new_db, delta_h = self.stage_update(cols, new_cols, donate=donate)
        self.db = new_db
        return delta_h


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClientQueryState:
    secret: jax.Array            # s ∈ Z_q^k
    index: int                   # queried column (kept client-side!)


class PIRClient:
    """Client side: query formulation and response decoding."""

    def __init__(self, cfg: PIRConfig, hint: jax.Array):
        assert hint.shape == (cfg.m, cfg.params.k)
        self.cfg = cfg
        self.hint = hint
        self._a_mat = lwe.gen_public_matrix(cfg.a_seed, cfg.n, cfg.params.k)

    def query(self, key: jax.Array, index: int) -> tuple[jax.Array,
                                                          ClientQueryState]:
        """Encrypt a one-hot selector for column `index`."""
        k_sec, k_err = jax.random.split(key)
        s = lwe.keygen(k_sec, self.cfg.params)
        onehot = jnp.zeros((self.cfg.n,), U32).at[index].set(1)
        qu = lwe.encrypt_vector(k_err, s, self._a_mat, onehot,
                                self.cfg.params.delta, self.cfg.params.sigma)
        return qu, ClientQueryState(secret=s, index=index)

    def recover(self, ans: jax.Array, state: ClientQueryState) -> jax.Array:
        """Decode the server answer into the plaintext column (m,) u8."""
        p = self.cfg.params
        if p.q_switch is not None:
            vals = lwe.decode_switched(ans, self.hint, state.secret, p)
        else:
            rec = lwe.hint_strip(ans, self.hint, state.secret)
            vals = lwe.decode(rec, p)
        return vals.astype(jnp.uint8)

    def recover_batch(self, ans: jax.Array, secrets: jax.Array) -> jax.Array:
        """Decode C answers at once: ans (m, C), secrets (k, C) → (m, C) u8.

        Every LWE decode op is exact integer arithmetic and shape
        polymorphic (the hint strip is one (m,k)·(k,C) matmul), so column
        j here is BIT-IDENTICAL to ``recover(ans[:, j], state_j)`` — the
        batched form exists so the serving pipeline can enqueue recovery
        on the device stream at dispatch time instead of paying C
        dispatch round-trips at the complete stage.
        """
        p = self.cfg.params
        if p.q_switch is not None:
            vals = lwe.decode_switched(ans, self.hint, secrets, p)
        else:
            rec = lwe.hint_strip(ans, self.hint, secrets)
            vals = lwe.decode(rec, p)
        return vals.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Convenience: parameter selection for a corpus
# ---------------------------------------------------------------------------

def make_config(m: int, n: int, *, impl: str = "auto",
                q_switch: int | None = 1 << 16,
                a_seed: int = 7) -> PIRConfig:
    """PIRConfig for an (m, n) database with auto-chosen LWE parameters.

    ``a_seed`` seeds the public LWE matrix A (shared by server and every
    client; `PirRagSystem.build` derives it from its build seed on a stream
    independent of cluster seeding).
    """
    params = lwe.choose_params(n, want_p=256, q_switch=q_switch)
    return PIRConfig(m=m, n=n, params=params, impl=impl, a_seed=a_seed)


def server_flops(cfg: PIRConfig, batch: int = 1) -> int:
    """int8-MAC count of one online answer (limb-decomposed)."""
    return 2 * cfg.m * cfg.n * batch * lwe.Q_BITS // 8


def server_bytes(cfg: PIRConfig) -> int:
    """HBM traffic floor of one answer: the DB streamed once."""
    return cfg.m * cfg.n
