"""What the benchmark observes of the timed path, and what it may plant in it.

`Tap` wraps the serving system's plan entry (``query_batch_async``) and the
server's ``answer``: it records every dispatched batch, keeps the inputs
and outputs of a sample of batches for the comparison, and names the plan
and complete stages for the profiler.  It wraps instance attributes of the
objects the benchmark built, in its own process; the program's code is not
touched.

`lowlimb_answer` is the control and `fault` the planted faults: answers put
in the program's place to show that the comparison fails them.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Batch:
    """One answer dispatched by the engine."""
    b: int                  # real query columns


class Tap:
    """Records batches and keeps a sample of them (see the module doc).

    ``replace(orig_answer, qu)``, when given, computes the answer instead
    of the program.
    """

    def __init__(self, system, rng: np.random.Generator, p_capture: float,
                 replace=None):
        self.rng, self.p = rng, p_capture
        self.batches: list[Batch] = []
        self.captured: list[dict] = []
        self._cur = None
        self._qba = system.query_batch_async
        self._answer = system.server.answer
        self._replace = replace
        system.query_batch_async = self.query_batch_async
        system.server.answer = self.answer

    def answer(self, qu):
        ans = (self._answer(qu) if self._replace is None
               else self._replace(self._answer, qu))
        if self._cur is not None:
            self._cur["qu"], self._cur["ans"] = qu, ans
        return ans

    def query_batch_async(self, embs, **kw):
        cap = ({"embs": np.array(embs), "top_k": list(kw["top_k"])}
               if self.rng.random() < self.p else None)
        self._cur = cap
        with TraceAnnotation("bench.plan"):
            infl = self._qba(embs, **kw)
        self._cur = None
        self.batches.append(Batch(len(embs)))
        inner = infl._complete
        if cap is not None:
            cap["cols"] = infl.pending[0]
            self.captured.append(cap)

        def complete():
            with TraceAnnotation("bench.complete"):
                out = inner()
            if cap is not None:
                cap["results"] = out
            return out

        infl._complete = complete
        return infl


class CompileCounter:
    """Counts programs lowered (compiled or loaded from cache) while on."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if self.on and event.endswith("jaxpr_to_mlir_module_duration"):
            self.n += 1


def _lowlimb(d, qu, shift: int):
    """``d · qu`` with each query word's lowest 8-bit limb dropped, in
    256-row blocks, modulus-switched by ``shift`` bits."""
    import jax
    import jax.numpy as jnp

    m = d.shape[0]
    rows = 256
    qm = qu & jnp.uint32(0xFFFFFF00)
    # blocks sliced in place: a reshape of d[:m - m % rows] would copy d
    out = jax.lax.map(lambda i: jnp.matmul(jax.lax.dynamic_slice_in_dim(
        d, i * rows, rows).astype(jnp.uint32), qm),
        jnp.arange(m // rows)).reshape(-1, qu.shape[1])
    if m % rows:
        out = jnp.concatenate([out, jnp.matmul(
            d[m - m % rows:].astype(jnp.uint32), qm)])
    half = jnp.uint32(1 << (shift - 1))
    return ((out + half) >> jnp.uint32(shift)).astype(jnp.uint16)


def lowlimb_answer(srv):
    """The control: ``f(db, qu)``, the answer computed plainly with each
    query word's lowest 8-bit limb dropped (three limbs of four),
    modulus-switched as the server's answer is.

    On a row-sharded server it runs shard by shard over the server's own
    mesh and row axes, each chip on the rows it holds, so the DB is never
    gathered; the result is cut to the server's ``m`` rows.
    """
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    f = functools.partial(
        _lowlimb, shift=32 - int(math.log2(srv.cfg.params.q_switch)))
    if srv.mesh is None:
        return jax.jit(f)
    axes, m = srv.mesh_axes, srv.cfg.m
    per_shard = jax.shard_map(f, mesh=srv.mesh, in_specs=(P(axes, None), P()),
                              out_specs=P(axes, None))
    return jax.jit(lambda d, qu: per_shard(d, qu)[:m])


def control():
    """``replace`` that puts the control in the answer's place."""
    import jax
    fns: dict = {}

    def replace(orig, qu):
        srv = orig.__self__
        if srv.db.shape not in fns:
            fns[srv.db.shape] = lowlimb_answer(srv)
        if srv.mesh is not None:
            qu = jax.device_put(qu, srv._replicated)
        return fns[srv.db.shape](srv.db, qu)

    return replace


def fault(kind: str):
    """A planted fault in the answer, for the harness's own tests."""
    import jax.numpy as jnp

    def altered(orig, qu):          # one word of one answer changed
        ans = orig(qu)
        return ans.at[-1, 0].add(jnp.asarray(1 << 14, ans.dtype))

    def half(orig, qu):             # half of the batch left out
        h = (qu.shape[1] + 1) // 2
        ans = orig(qu[:, :h])
        return jnp.concatenate(
            [ans, jnp.repeat(ans[:, :1], qu.shape[1] - h, axis=1)], axis=1)

    return {"altered": altered, "half": half}[kind]
