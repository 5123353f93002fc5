"""LiveIndex: streaming corpus mutations over a PirRagSystem.

Mutations accumulate in the journal; `commit()` folds the pending batch into
one published epoch:

    plan      — planner resolves the batch, accounts column capacity
    repack    — `chunking.rebuild_columns` re-serializes only touched columns
    delta     — `PIRServer.update_columns` swaps the columns in-place and
                returns ΔH = ΔD[:,J]·A[J,:] via the modmatmul kernel path
    publish   — EpochLog gains a HintPatch; clients `HintCache.sync()` to
                patch their cached hint instead of re-downloading it

When the planner trips a full-rebuild trigger (insert overflowing the m-row
budget, or pad_fraction degrading past the threshold after deletes), the
epoch is published as a full-hint patch over a freshly re-clustered system.

Exactness invariant (tested): after any mutation sequence, the incrementally
patched hint — server-side AND client-side — is bit-identical to
`server.setup()` on the rebuilt database.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.core import chunking, pipeline
from repro.fleet.faults import InjectedCommitFault
from repro.kernels import ops
from repro.obs import DEFAULT_SIZE_BUCKETS, Obs
from repro.update import journal as journal_lib
from repro.update import planner, routing
from repro.update.epochs import EpochLog, HintPatch

U32 = jnp.uint32


@dataclasses.dataclass
class CommitStats:
    epoch: int
    n_mutations: int
    touched_clusters: int
    full_rebuild: bool
    reason: str | None
    seconds: float
    patch_bytes: int


@dataclasses.dataclass
class StagedEpoch:
    """One commit's shadow buffers: computed while the live epoch serves.

    `LiveIndex.stage()` plans the mutation batch, re-packs the touched
    columns and DISPATCHES every device-side patch (ΔH GEMMs, column
    scatters, bucket patches) against shadow copies — the serving path's
    pointers are untouched, so queries keep being planned, answered and
    decoded at the old epoch throughout.  `LiveIndex.publish()` then flips
    the pointers and advances the epoch log: the stale-reject window is the
    swap, not the patch computation.
    """
    patch: HintPatch
    plan: planner.UpdatePlan
    n_mutations: int
    t0: float
    _apply: Callable[[], None]


class LiveIndex:
    """A PirRagSystem that accepts insert/delete/replace without downtime."""

    def __init__(self, system: pipeline.PirRagSystem,
                 texts, embeddings, *,
                 doc_ids=None,
                 max_pad_fraction: float = 0.95,
                 compact_every: int | None = None,
                 rebuild_kwargs: dict | None = None):
        assert system.assignment is not None, "build system via PirRagSystem.build"
        assert system.db.used_bytes is not None
        self.system = system
        self.journal = journal_lib.MutationJournal()
        # compact_every=C chains patches with periodic compaction: a client
        # K epochs behind downloads O(K/C) precomputed segments, not K
        # patches (the hint-delivery layer; see update.epochs.EpochLog)
        self.epochs = EpochLog(compact_every=compact_every)
        self.max_pad_fraction = max_pad_fraction
        self._rebuild_kwargs = dict(rebuild_kwargs or {})
        # _commit_full supplies the then-current id set itself
        self._rebuild_kwargs.pop("doc_ids", None)
        if system.keyed is None:
            self._rebuild_kwargs.setdefault("n_clusters", system.db.n)
        # Full rebuilds re-run the ENTIRE offline build; a sharded system
        # must rebuild through the same sharded path (mesh-parallel K-means,
        # per-shard packing) rather than fall back to a host-side build that
        # would then reshard — at scale the rebuild epoch is exactly where
        # the single-host path stops fitting.
        self._rebuild_kwargs.setdefault("mesh", system.mesh)
        self._rebuild_kwargs.setdefault("mesh_axes", system.mesh_axes)
        self.commits: list[CommitStats] = []
        # Observability handle: a serve loop replaces this with its own via
        # set_obs() so commit spans land in the SAME trace as serve ticks.
        self.obs = Obs(trace=False)
        self.epochs.obs = self.obs
        # Fault-injection hook (repro.fleet.faults.FaultInjector): `stage`
        # guards the "update.commit.stage" site — an injected failure
        # raises BEFORE any shadow state is computed, so the pending
        # journal batch stays intact and the serving epoch never moves.
        # Recovery replay (repro.fleet.recovery) clears it around replays.
        self.faults = None
        # A client that detects a corrupt patch chain recovers by fetching
        # the CURRENT full hint (one deterministic full re-sync).
        self.epochs.full_fetch = self._full_patch

        ids = (np.arange(len(texts)) if doc_ids is None
               else np.asarray(doc_ids))
        embs = np.asarray(embeddings, np.float32)
        self._docs = {int(i): (texts[p], embs[p])
                      for p, i in enumerate(ids)}
        self._cluster_of = {int(i): int(system.assignment[p])
                            for p, i in enumerate(ids)}
        self._used = {j: int(system.db.used_bytes[j])
                      for j in range(system.db.n)}

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, texts, embeddings, *, n_clusters: int,
              max_pad_fraction: float = 0.95, doc_ids=None,
              compact_every: int | None = None,
              **build_kwargs) -> "LiveIndex":
        """Offline-build a PirRagSystem and wrap it as a live index.

        texts: N byte strings; embeddings: (N, d) f32; extra kwargs
        (incl. ``mesh=`` for a sharded build) forward to
        `PirRagSystem.build` AND are replayed on every full rebuild, so a
        sharded index rebuilds through the sharded path.
        ``compact_every=C`` enables periodic hint-patch compaction in the
        epoch log (the many-epoch hint-delivery path).
        """
        system = pipeline.PirRagSystem.build(
            texts, embeddings, n_clusters=n_clusters, doc_ids=doc_ids,
            **build_kwargs)
        return cls(system, texts, embeddings, doc_ids=doc_ids,
                   max_pad_fraction=max_pad_fraction,
                   compact_every=compact_every,
                   rebuild_kwargs=dict(n_clusters=n_clusters, **build_kwargs))

    @classmethod
    def build_keyed(cls, table, *, max_pad_fraction: float = 0.95,
                    compact_every: int | None = None,
                    **build_kwargs) -> "LiveIndex":
        """Offline-build a KEYED system (embedding table) as a live index.

        table: (V, d) f32; extra kwargs forward to
        `PirRagSystem.build_keyed` and are replayed on full rebuilds.  Row i
        is doc i with the id-derived group assignment, so `replace(i, ...)`
        streams fresh embedding rows through the standard delta-epoch path
        (replaced records keep the fixed keyed width, so a replace can
        never overflow a column).  Keyed mutations are REPLACE-only: the
        table's id space must stay dense 0..V-1 for the client's stride
        arithmetic, so inserts/deletes trip the planner's keyed guard.
        """
        table = np.ascontiguousarray(table, np.float32)
        system = pipeline.PirRagSystem.build_keyed(table, **build_kwargs)
        layout = system.keyed
        texts = [layout.row_text(table[i]) for i in range(layout.n_rows)]
        return cls(system, texts, table,
                   max_pad_fraction=max_pad_fraction,
                   compact_every=compact_every,
                   rebuild_kwargs=dict(build_kwargs))

    def set_obs(self, obs: Obs) -> None:
        """Adopt `obs` (a serve loop's handle) for commit/compaction events."""
        self.obs = obs
        self.epochs.obs = obs

    # -- introspection -------------------------------------------------------

    @property
    def epoch(self) -> int:
        """The published epoch number (0 before any commit)."""
        return self.epochs.epoch

    @property
    def n_docs(self) -> int:
        """Documents in the PUBLISHED epoch (pending mutations excluded)."""
        return len(self._docs)

    def pad_fraction(self) -> float:
        """Current zero-padding share of the (m, n) matrix (rebuild gauge)."""
        db = self.system.db
        return 1.0 - sum(self._used.values()) / float(db.m * db.n)

    def doc_ids(self) -> list[int]:
        """Sorted external doc ids of the published epoch."""
        return sorted(self._docs)

    # -- mutation intake -----------------------------------------------------

    def insert(self, doc_id: int, text: bytes, emb: np.ndarray):
        """Journal an insert (emb: (d,) f32); visible at the next commit."""
        self.journal.append(journal_lib.insert(doc_id, text, emb))

    def delete(self, doc_id: int):
        """Journal a delete; visible at the next commit."""
        self.journal.append(journal_lib.delete(doc_id))

    def replace(self, doc_id: int, text: bytes, emb: np.ndarray):
        """Journal a replace (emb: (d,) f32); visible at the next commit."""
        self.journal.append(journal_lib.replace(doc_id, text, emb))

    def replace_row(self, row_id: int, row: np.ndarray):
        """Journal a KEYED row replace; the record payload is the row itself.

        Keyed records carry the row's raw f32 bytes as their text payload
        (`KeyedLayout.row_text`), so callers hand over just the new row and
        the (fixed-width) record stays in the id-derived group — the next
        commit ships it as an ordinary delta epoch.
        """
        layout = self.system.keyed
        if layout is None:
            raise ValueError("replace_row needs a keyed (build_keyed) index")
        row = np.asarray(row, np.float32)
        self.journal.append(journal_lib.replace(
            row_id, layout.row_text(row), row))

    # -- commit --------------------------------------------------------------

    def commit(self, *, donate: bool = False) -> HintPatch | None:
        """Fold all pending mutations into one published epoch.

        Equivalent to ``publish(stage())`` — the synchronous path runs the
        two halves back-to-back.  ``donate=True`` (engine-only) patches the
        server-side DB buffers in place instead of copying them per epoch;
        see `PIRServer.stage_update` for the aliasing contract.
        """
        staged = self.stage(donate=donate)
        return self.publish(staged) if staged is not None else None

    def stage(self, *, donate: bool = False) -> StagedEpoch | None:
        """Compute one commit's shadow state without publishing it.

        Everything device-side is dispatched (JAX async) against fresh —
        or, with ``donate=True``, in-place aliased — buffers; nothing the
        serving path reads has moved when this returns.  Returns None when
        no mutations are pending.
        """
        muts = self.journal.pending()
        if not muts:
            return None
        if self.faults is not None and self.faults.fire("update.commit.stage"):
            raise InjectedCommitFault(
                f"injected stage failure at epoch {self.epochs.epoch} "
                f"({len(muts)} pending mutations, batch retryable)")
        t0 = time.perf_counter()
        db = self.system.db
        keyed = self.system.keyed
        if keyed is not None:
            # The client decodes by id arithmetic over a dense 0..V-1 space;
            # inserts/deletes would punch holes in it.  Replaced rows must
            # also STAY in their id-derived group, so the planner routes by
            # the public layout, not by embedding similarity.
            for m_ in muts:
                if m_.kind != journal_lib.REPLACE:
                    raise ValueError(
                        f"keyed index supports replace only, got {m_.kind} "
                        f"for doc {m_.doc_id}")
        assign_fn = (None if keyed is None
                     else (lambda i, e: keyed.group_of(i)))
        with self.obs.span("commit.stage", mutations=len(muts)) as sp:
            plan = planner.plan_updates(
                muts, docs=self._docs, cluster_of=self._cluster_of,
                centroids=self.system.centroids, m=db.m,
                used_bytes=self._used, n_clusters=db.n, emb_dim=db.emb_dim,
                max_pad_fraction=self.max_pad_fraction,
                assign_fn=assign_fn)
            sp.set(kind="full" if plan.full_rebuild else "delta",
                   touched=len(plan.touched))
            if plan.full_rebuild:
                with self.obs.span("commit.rebuild", docs=len(plan.new_docs)):
                    patch, apply = self._stage_full(plan)
            else:
                patch, apply = self._stage_delta(plan, donate=donate)
        return StagedEpoch(patch=patch, plan=plan, n_mutations=len(muts),
                           t0=t0, _apply=apply)

    def publish(self, staged: StagedEpoch) -> HintPatch:
        """Flip the staged pointers and advance the epoch: the swap instant.

        Queries planned before this call keep decoding against their
        snapshot of the old epoch; queries planned after it are formed —
        and admitted — at the new one.
        """
        plan, patch = staged.plan, staged.patch
        with self.obs.span("commit.publish",
                           kind="full" if plan.full_rebuild else "delta",
                           epoch=self.epochs.epoch + 1):
            staged._apply()
            self.epochs.publish(patch)
        self.journal.mark_committed(self.epochs.epoch)
        self._docs = plan.new_docs
        self._cluster_of = plan.new_cluster_of
        self.obs.counter("commit.epochs").inc()
        self.obs.counter("commit.mutations").inc(staged.n_mutations)
        if plan.full_rebuild:
            self.obs.counter("commit.full_rebuilds").inc()
        self.obs.histogram("commit.patch_bytes",
                           bounds=DEFAULT_SIZE_BUCKETS).record(
                               patch.wire_bytes)
        self.commits.append(CommitStats(
            epoch=self.epochs.epoch, n_mutations=staged.n_mutations,
            touched_clusters=len(plan.touched),
            full_rebuild=plan.full_rebuild, reason=plan.reason,
            seconds=time.perf_counter() - staged.t0,
            patch_bytes=patch.wire_bytes))
        return patch

    def _stage_delta(self, plan: planner.UpdatePlan, *, donate: bool
                     ) -> tuple[HintPatch, Callable[[], None]]:
        db, system = self.system.db, self.system
        cols, new_cols, used = chunking.rebuild_columns(
            db.m, plan.docs_by_cluster)

        # Row truncation for the patch: beyond the max used length of the
        # old and new touched columns both sides are zero padding, so ΔD
        # there is identically zero and need not travel.
        old_used = max(self._used[int(j)] for j in cols)
        r = max(old_used, max(used.values()))
        old_rows = np.asarray(system.server.db[:, jnp.asarray(cols)])[:r]
        delta = (new_cols[:r].astype(np.int16)
                 - old_rows.astype(np.int16))           # entries ∈ [−255, 255]

        cols_j, new_cols_j = jnp.asarray(cols), jnp.asarray(new_cols)
        delta_h = system.server.stage_delta(cols_j, new_cols_j)
        # The donating column scatter is DEFERRED to publish(): an exception
        # later in this stage tail, or a caller dropping the StagedEpoch
        # unpublished, must leave server.db serving the old epoch — never
        # pointing at a consumed buffer.  Without donation the scatter is a
        # fresh buffer, so it overlaps here in the (shadowable) stage phase.
        new_db_arr = (None if donate
                      else system.server.stage_scatter(cols_j, new_cols_j))
        # u32 wraparound: exact.  ΔH is transient, so the add donates ITS
        # buffer; the old hint array survives for in-flight decode snapshots.
        new_hint = ops.add_delta(system.hint, delta_h)
        # Batch-PIR replicas (if enabled) take the same exact delta, routed
        # to each touched cluster's owning buckets.
        staged_batch = routing.stage_batch_hints(system, cols, new_cols,
                                                 used, donate=donate)

        def apply():
            system.server.db = (
                system.server.stage_scatter(cols_j, new_cols_j, donate=True)
                if donate else new_db_arr)
            system.hint = new_hint
            if staged_batch is not None:
                staged_batch.publish()
            # Mirror the host-side ChunkedDB view (tests/tools read
            # db.matrix).  Patched in place: copying the full (m, n) matrix
            # per commit would make host cost O(DB) and swamp the O(m·|J|)
            # delta path at scale.
            db.matrix[:, cols] = new_cols
            for j in cols:
                db.cluster_sizes[j] = len(plan.docs_by_cluster[int(j)])
                self._used[int(j)] = used[int(j)]
                db.used_bytes[j] = used[int(j)]
            self.system.db = dataclasses.replace(
                db, n_docs=len(plan.new_docs),
                pad_fraction=1.0 - sum(self._used.values())
                / float(db.m * db.n))

        return HintPatch(from_epoch=self.epochs.epoch,
                         to_epoch=self.epochs.epoch + 1,
                         cols=np.asarray(cols), delta=delta), apply

    def _stage_full(self, plan: planner.UpdatePlan
                    ) -> tuple[HintPatch, Callable[[], None]]:
        """Overflow / pad-degradation: re-cluster, re-pack, re-hint.

        Naturally shadowed: the rebuilt system is a fresh object graph, so
        the whole build (clustering, packing, hint GEMM, re-bucketing)
        happens while the old system keeps serving; publish is one pointer
        swap.
        """
        ids = sorted(plan.new_docs)
        texts = [plan.new_docs[i][0] for i in ids]
        embs = np.stack([plan.new_docs[i][1] for i in ids])
        if self.system.keyed is not None:
            # Keyed rebuild: the id space is dense (replace-only), so the
            # doc set IS the table — rebuild through the keyed path with
            # the same layout/bucket knobs.
            assert ids == list(range(len(ids))), "keyed id space not dense"
            lay, bp = self.system.keyed, self.system.batch
            kw = {k: v for k, v in self._rebuild_kwargs.items()
                  if k not in ("group_size", "kappa", "n_buckets",
                               "batch_seed")}
            new_system = pipeline.PirRagSystem.build_keyed(
                embs, group_size=lay.group_size, kappa=bp.kappa,
                n_buckets=bp.partition.n_buckets, batch_seed=bp.seed, **kw)
            plan.new_cluster_of.clear()
            plan.new_cluster_of.update(
                {i: int(new_system.assignment[p])
                 for p, i in enumerate(ids)})

            def apply_keyed():
                self.system = new_system
                self._used = {j: int(new_system.db.used_bytes[j])
                              for j in range(new_system.db.n)}

            return HintPatch(from_epoch=self.epochs.epoch,
                             to_epoch=self.epochs.epoch + 1,
                             full_hint=np.asarray(new_system.hint),
                             cfg=new_system.cfg), apply_keyed
        new_system = pipeline.PirRagSystem.build(
            texts, embs, doc_ids=ids, **self._rebuild_kwargs)
        routing.rebuild_batch(self.system, new_system)
        # Rebuild re-clusters, so the plan's incremental cluster map is stale.
        plan.new_cluster_of.clear()
        plan.new_cluster_of.update(
            {i: int(new_system.assignment[p]) for p, i in enumerate(ids)})

        def apply():
            self.system = new_system
            self._used = {j: int(new_system.db.used_bytes[j])
                          for j in range(new_system.db.n)}

        return HintPatch(from_epoch=self.epochs.epoch,
                         to_epoch=self.epochs.epoch + 1,
                         full_hint=np.asarray(new_system.hint),
                         cfg=new_system.cfg), apply

    def _full_patch(self, from_epoch: int) -> HintPatch:
        """A sealed full-hint patch `from_epoch` → head (corrupt-chain
        fallback: costs `cfg.hint_bytes`, same as bootstrap)."""
        return HintPatch(from_epoch=from_epoch, to_epoch=self.epochs.epoch,
                         full_hint=np.asarray(self.system.hint),
                         cfg=self.system.cfg).sealed()

    # -- epoch-checked queries ----------------------------------------------

    def check_epoch(self, epoch: int):
        """Raise StaleEpochError unless `epoch` is the published head."""
        self.epochs.check_fresh(epoch)

    def query(self, query_emb: np.ndarray, *, epoch: int, **kwargs):
        """Epoch-checked private query (kwargs forwarded to the system).

        A query formed against a stale cached hint would decode garbage, so
        the server rejects it up front; the client syncs its HintCache and
        retries.
        """
        self.check_epoch(epoch)
        return self.system.query(query_emb, **kwargs)

    def query_batch(self, query_embs: np.ndarray, *, epoch: int, **kwargs):
        """Epoch-checked batched query ((B, d) f32; kwargs to the system)."""
        self.check_epoch(epoch)
        return self.system.query_batch(query_embs, **kwargs)

    def lookup(self, ids, *, epoch: int, **kwargs):
        """Epoch-checked keyed row lookup (kwargs to `PirRagSystem.lookup`)."""
        self.check_epoch(epoch)
        return self.system.lookup(ids, **kwargs)

    def lookup_batch(self, ids_batch, *, epoch: int, **kwargs):
        """Epoch-checked batched keyed lookup (kwargs to the system)."""
        self.check_epoch(epoch)
        return self.system.lookup_batch(ids_batch, **kwargs)
