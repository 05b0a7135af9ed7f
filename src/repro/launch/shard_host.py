"""Process-parallel shard execution for the routing plane.

:class:`repro.engine.router.FleetRouter` runs its shards inline — each
shard is an independent :class:`~repro.engine.fleet.FleetEngine` with
its own scheduler and packed plane, so nothing stops them draining in
parallel.  This module supplies the parallel runner: a
:class:`ShardHost` is one shard living in its own OS process (spawned,
so no fork-after-JAX hazards), driven over a pipe by a tiny command
protocol, and a :class:`ProcessShardSet` is N hosts behind the same
consistent-hash placement the inline router uses
(:func:`repro.engine.router.shard_ids_for` + the
:class:`~repro.engine.placement.PartitionDirectory`), presenting the
same submit/drain/stats EventSink surface.  ``drain`` is split-phase —
every host is told to drain before any is waited on — so shard work
overlaps across cores.  (The accelerator-resident alternative is to lay
shards over JAX devices with :mod:`repro.launch.mesh`; processes are
the portable default.)

Tenant engines are built *inside* the worker from picklable zero-arg
factories (module-level functions / :func:`functools.partial`), so the
parent never pays for — or shares — shard state.  Live migration works
across processes too: :meth:`ProcessShardSet.migrate_tenant` pickles
the detached engine (its trace, StateMatrix plane, pending deltas and
micro-move ledger are all ordinary state on the object) through the
parent to the target host, same finish-or-transplant semantics as the
inline router.
"""
from __future__ import annotations

import multiprocessing as mp
import traceback
from typing import Callable, Dict, List, Mapping, Optional

from repro.engine.placement import HashRing, PartitionDirectory
from repro.engine.scheduler import SchedulerSpec
from repro.engine.router import shard_ids_for


def _shard_worker(conn, factories: Dict[str, Callable],
                  spec: SchedulerSpec, name: str,
                  incremental: Optional[bool]) -> None:
    """Worker main loop: build the shard fleet, serve commands until EOF."""
    from repro.engine.fleet import FleetEngine

    try:
        tenants = {tid: factory() for tid, factory in factories.items()}
        fleet = FleetEngine(tenants, spec.build(), name=name,
                            incremental=incremental)
        conn.send(("ok", None))
    except BaseException:
        conn.send(("err", traceback.format_exc()))
        return
    while True:
        try:
            cmd, payload = conn.recv()
        except EOFError:
            return
        try:
            if cmd == "submit_many":
                for ev in payload:
                    fleet.submit(ev)
                result = len(payload)
            elif cmd == "drain":
                result = fleet.drain(**payload)
            elif cmd == "result":
                result = fleet.result(payload)
            elif cmd == "stats":
                result = fleet.stats()
            elif cmd == "queue_depth":
                result = fleet.queue_depth
            elif cmd == "migrate_out":
                inbox = fleet.take_inbox(payload)
                engine = fleet.remove_tenant(payload)
                result = (engine, inbox)
            elif cmd == "migrate_in":
                tid, engine, inbox = payload
                fleet.add_tenant(tid, engine)
                for ev in inbox:
                    fleet.submit(ev)
                result = None
            elif cmd == "close":
                conn.send(("ok", None))
                return
            else:
                raise ValueError(f"unknown shard command {cmd!r}")
            conn.send(("ok", result))
        except BaseException:
            conn.send(("err", traceback.format_exc()))


class ShardHostError(RuntimeError):
    """A shard worker raised; carries the worker-side traceback."""


def _check_worker_compute(compute: str) -> None:
    """Refuse a kernel compute path that no worker could run.

    A TPU belongs to one process at a time: while this (parent) process
    holds the TPU backend, a worker asked for a ``pallas*`` path would
    fail or hang reaching for the chip.
    """
    if not compute.startswith("pallas"):
        return
    from repro.kernels import _backend
    if _backend.initialized_platform() == "tpu":
        raise RuntimeError(
            f"shard workers cannot run compute={compute!r}: this process "
            f"holds the TPU, and a chip serves one process at a time; "
            f"drain worker shards with compute='numpy', or run the shards "
            f"in this process (repro.engine.FleetRouter)")


class ShardHost:
    """One fleet shard behind a spawned worker process.

    Submits buffer in the parent and flush with the next drain (one
    pipe round trip per drain, not per event).  All calls are
    synchronous except the :meth:`start_drain` / :meth:`finish_drain`
    pair, which :class:`ProcessShardSet` uses to overlap shard drains.
    """

    def __init__(self, shard_id: str, factories: Mapping[str, Callable],
                 spec: SchedulerSpec, name: Optional[str] = None,
                 incremental: Optional[bool] = None,
                 mp_context: str = "spawn"):
        self.shard_id = shard_id
        ctx = mp.get_context(mp_context)
        self._conn, child = ctx.Pipe()
        self._proc = ctx.Process(
            target=_shard_worker,
            args=(child, dict(factories), spec, name or shard_id,
                  incremental),
            daemon=True)
        self._proc.start()
        child.close()
        self._outbox: List = []
        self._busy = False          # a start_drain awaiting finish_drain
        self._recv()                # worker construction handshake

    def _recv(self):
        status, payload = self._conn.recv()
        if status != "ok":
            raise ShardHostError(
                f"shard {self.shard_id!r} worker failed:\n{payload}")
        return payload

    def _call(self, cmd: str, payload=None):
        if self._busy:
            raise RuntimeError("finish_drain() the in-flight drain first")
        self._conn.send((cmd, payload))
        return self._recv()

    # -- EventSink-ish surface -----------------------------------------
    def submit(self, event) -> None:
        self._outbox.append(event)

    @property
    def queue_depth(self) -> int:
        return len(self._outbox) + self._call("queue_depth")

    def flush_submits(self) -> int:
        if not self._outbox:
            return 0
        out, self._outbox = self._outbox, []
        return self._call("submit_many", out)

    def start_drain(self, **kwargs) -> None:
        """Flush buffered submits and tell the worker to drain (async)."""
        _check_worker_compute(kwargs.get("compute", "numpy"))
        self.flush_submits()
        self._conn.send(("drain", kwargs))
        self._busy = True

    def finish_drain(self) -> int:
        self._busy = False
        return self._recv()

    def drain(self, **kwargs) -> int:
        self.start_drain(**kwargs)
        return self.finish_drain()

    def result(self, name: Optional[str] = None):
        return self._call("result", name)

    def stats(self) -> dict:
        return self._call("stats")

    def migrate_out(self, tenant_id: str):
        return self._call("migrate_out", tenant_id)

    def migrate_in(self, tenant_id: str, engine, inbox) -> None:
        self._call("migrate_in", (tenant_id, engine, inbox))

    def close(self) -> None:
        if self._proc.is_alive():
            try:
                self._call("close")
            except (ShardHostError, OSError, EOFError):
                pass
            self._proc.join(timeout=10)
        self._conn.close()


class ProcessShardSet:
    """N process-resident shards behind the router's placement.

    Same consistent-hash tenant→shard mapping as an inline
    :class:`repro.engine.router.FleetRouter` with the same shard count
    and ``replicas`` — the two agree on every tenant's home, so a
    deployment can switch runners without a placement migration.
    Context-manage it (or call :meth:`close`) to reap the workers.
    """

    def __init__(self, factories: Mapping[str, Callable],
                 num_shards: int = 2,
                 scheduler: Optional[SchedulerSpec] = None,
                 name: str = "procset",
                 replicas: int = 64,
                 incremental: Optional[bool] = None,
                 mp_context: str = "spawn"):
        if not factories:
            raise ValueError("a shard set needs at least one tenant factory")
        self.name = name
        spec = scheduler or SchedulerSpec.unlimited()
        self.ring = HashRing(shard_ids_for(num_shards), replicas=replicas)
        self.directory = PartitionDirectory(self.ring)
        by_shard: Dict[str, Dict[str, Callable]] = {
            sid: {} for sid in self.ring.shard_ids}
        for tid, factory in factories.items():
            by_shard[self.directory.lookup(tid)][tid] = factory
        self._hosts: Dict[str, ShardHost] = {}
        try:
            for sid in self.ring.shard_ids:
                self._hosts[sid] = ShardHost(
                    sid, by_shard[sid], spec, name=f"{name}/{sid}",
                    incremental=incremental, mp_context=mp_context)
        except BaseException:
            self.close()
            raise
        self._known = set(factories)
        self.migrations = 0

    def __enter__(self) -> "ProcessShardSet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def shard_ids(self) -> List[str]:
        return self.ring.shard_ids

    def shard_of(self, tenant_id: str) -> str:
        if tenant_id not in self._known:
            raise KeyError(f"unknown tenant {tenant_id!r}")
        return self.directory.lookup(tenant_id)

    # -- EventSink surface ---------------------------------------------
    def submit(self, event) -> None:
        from repro.core import workload as wl
        ev = wl.as_event(event)
        self._hosts[self.shard_of(ev.tenant_id)].submit(ev)

    @property
    def queue_depth(self) -> int:
        return sum(h.queue_depth for h in self._hosts.values())

    def drain(self, **kwargs) -> int:
        """Drain all shards concurrently (split-phase over the workers)."""
        kwargs.pop("collect", None)     # per-event observations stay local
        for sid in self.ring.shard_ids:
            self._hosts[sid].start_drain(**kwargs)
        return sum(self._hosts[sid].finish_drain()
                   for sid in self.ring.shard_ids)

    def stats(self) -> dict:
        return {
            "name": self.name,
            "num_shards": len(self._hosts),
            "tenants": len(self._known),
            "migrations": self.migrations,
            "shards": {sid: self._hosts[sid].stats()
                       for sid in self.ring.shard_ids},
        }

    def result(self, name: Optional[str] = None):
        from repro.engine.fleet import FleetResult
        per_tenant = {}
        ticks = deferred = deferred_ticks = 0
        shard_stats = {}
        sched_name = ""
        for sid in self.ring.shard_ids:
            r = self._hosts[sid].result()
            per_tenant.update(r.per_tenant)
            ticks += r.ticks
            deferred += r.swaps_deferred
            deferred_ticks += r.deferred_ticks
            shard_stats[sid] = r.scheduler_stats
            sched_name = r.scheduler
        return FleetResult(name=name or self.name, scheduler=sched_name,
                           per_tenant=per_tenant, ticks=ticks,
                           swaps_deferred=deferred,
                           deferred_ticks=deferred_ticks,
                           scheduler_stats={"shards": shard_stats})

    def migrate_tenant(self, tenant_id: str, target_shard: str) -> bool:
        """Engine + queued events, pickled source → parent → target."""
        if target_shard not in self._hosts:
            raise KeyError(f"unknown shard {target_shard!r}")
        source_shard = self.shard_of(tenant_id)
        if source_shard == target_shard:
            return False
        # Parent-side buffered submits must reach the worker inbox first,
        # or migrate_out would miss them.
        self._hosts[source_shard].flush_submits()
        engine, inbox = self._hosts[source_shard].migrate_out(tenant_id)
        self._hosts[target_shard].migrate_in(tenant_id, engine, inbox)
        self.directory.assign(tenant_id, target_shard)
        self.migrations += 1
        return True

    def close(self) -> None:
        for host in self._hosts.values():
            host.close()
        self._hosts = {}


__all__ = ["ProcessShardSet", "ShardHost", "ShardHostError"]
