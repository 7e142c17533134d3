"""Executor registry: pluggable strategies for running experiment units.

Mirrors ``SEARCHERS`` / ``BACKENDS`` / ``STORES``: an executor is resolved by
name and runs a list of :class:`~repro.core.workunits.ExperimentUnit`\\ s for
a session, returning :class:`~repro.core.workunits.UnitResult` fragments the
session merges deterministically by unit key.  Built-ins:

* ``"serial"``  — the in-process loop; journals each completed unit.
* ``"process"`` — ``multiprocessing`` (spawn) fan-out.  Under the default
  *work-stealing* scheduler each worker process builds ONE persistent
  session at pool start (initializer), then pulls units one at a time from
  the shared submit queue — a worker that finishes early simply takes the
  next pending unit instead of idling behind a static partition.  Each
  worker writes to its own ``store_path.<ns8>.shard<pid>`` (seeded from the warm
  parent store), journals completed units into it, and the parent glob-
  merges shard stores when the pool joins.
* ``"futures"`` — the grouped worker payload submitted to ANY
  ``concurrent.futures.Executor``.  Pass a live pool via
  ``run_matrix(futures_pool=...)`` (a ``ThreadPoolExecutor``, a cluster
  client's pool adapter, ...); without one a spawn-context
  ``ProcessPoolExecutor`` is created for the call.  This is the
  remote-executor seam: the payload is ``(spec_dict, unit dicts,
  store paths)`` and the results come back as plain JSON-able dicts, so an
  executor whose workers live on other hosts only needs to ship the payload
  and a store path visible to the worker.  Under the stealing scheduler
  every payload carries exactly one unit, so any pool balances the queue;
  the cost is one session rebuild per unit (document-level knob: use
  ``scheduler="static"`` for pools where rebuilds dominate).
* ``"device"``  — multi-chip fan-out WITHIN one process: worker threads,
  each pinned to one of ``jax.devices()`` via ``jax.default_device``, with
  one shard store per device.  Under the stealing scheduler each thread
  keeps a persistent session (compilation caches warm across units) and
  pulls units as it frees up.  An 8-chip host runs the matrix ~8x wider
  with no process spawn or re-import; merges are bit-identical to
  ``serial`` because workers rebuild sessions from the same serialized
  spec and seeds derive from the spec alone.  It is the only parallel
  executor for a compiling backend (``pallas``) on a TPU host: a chip
  belongs to one process, so ``process`` and ``futures`` refuse there.

Scheduling: ``ExecutionPlan.scheduler`` selects ``"steal"`` (default — one
unit per submission, ``as_completed`` streaming, telemetry counters for
steals and a queue-depth gauge) or ``"static"`` (the round-robin
one-payload-per-worker partition; same results, coarser balancing).  Unit
*results* merge by unit key, so both schedules — and any completion order —
are bit-identical to the serial loop.

Parallel executors collect worker results as they complete and fail fast:
the first worker exception cancels outstanding work, absorbs completed
workers' shard stores (their journaled units survive into the parent) and
trace shards, and re-raises.

Worker crash/kill recovery: because workers journal completed units into
their shard stores as they go, :func:`recover_shard_stores` can absorb
leftover ``*.<ns8>.shard<k>`` files from a killed run into the parent store
before a resumed run partitions its units — nothing a dead worker finished is
lost.  Shard filenames are namespaced by the session's journal-namespace
digest, so recovery never absorbs shards a *different* spec left behind in a
shared store directory.
"""

from __future__ import annotations

import os
import re
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable

from .stores import absorb_winners, make_store
from .workunits import ExperimentUnit, UnitResult

__all__ = [
    "EXECUTORS",
    "ExecutionPlan",
    "Executor",
    "recover_shard_stores",
    "register_executor",
    "run_units",
    "shard_namespace",
    "shard_store_path",
]


@dataclass
class ExecutionPlan:
    """Everything an executor needs for one fan-out."""

    session: Any                      # TuningSession (duck-typed; no import cycle)
    units: list[ExperimentUnit] = field(default_factory=list)
    max_workers: int = 1
    futures_pool: Any = None          # concurrent.futures.Executor, "futures" only
    scheduler: str = "steal"          # "steal" (shared unit queue) | "static"


@dataclass(frozen=True)
class Executor:
    """A named unit-execution strategy.

    ``parallel`` marks executors that ship work out of the calling process:
    they require a fully serializable spec (no in-process overrides, a
    name-resolvable backend) and degrade to ``serial`` — with a warning —
    when the plan cannot keep more than one worker busy.
    """

    name: str
    run: Callable[[ExecutionPlan], list[UnitResult]]
    parallel: bool = True


EXECUTORS: dict[str, Executor] = {}


def register_executor(executor: Executor) -> Executor:
    EXECUTORS[executor.name] = executor
    return executor


def run_units(name: str, plan: ExecutionPlan) -> list[UnitResult]:
    """Run ``plan`` through the named executor."""
    if name not in EXECUTORS:
        raise KeyError(f"unknown executor {name!r}; have {sorted(EXECUTORS)}")
    return EXECUTORS[name].run(plan)


# -------------------------------------------------------------------- serial


def _run_serial(plan: ExecutionPlan) -> list[UnitResult]:
    session = plan.session
    journal = session.unit_journal()
    out = []
    for unit in plan.units:
        result = session.run_unit(unit)
        if journal is not None:
            journal.put(result)   # flushed (throttled) — a kill loses little
        out.append(result)
    return out


register_executor(Executor(name="serial", run=_run_serial, parallel=False))


# ----------------------------------------------------- shard-store plumbing


def shard_namespace(session) -> str:
    """8-hex digest namespacing this session's shard-store filenames.

    Derived from :meth:`TuningSession.journal_namespace` — the same
    fingerprint that scopes unit-journal entries — so two different specs
    sharing one store directory (or one store *path*) can never absorb each
    other's leftover shards on recovery."""
    ns = session.journal_namespace()
    if ns is None:
        # no stable fingerprint (live callables in the spec): fall back to
        # the cache key, which still separates kernels/chips
        ns = str(session.cache_key)
    return f"{zlib.crc32(ns.encode('utf-8')) & 0xFFFFFFFF:08x}"


def shard_store_path(session, ident) -> str | None:
    """The shard-store filename for worker ``ident`` (pid, device index, or
    a fleet worker's host-pid string): ``<store>.<ns8>.shard<ident>``."""
    if session.spec.store is None or session._store_path is None:
        return None
    return f"{session._store_path}.{shard_namespace(session)}.shard{ident}"


def _shard_store_path(session, shard) -> str | None:
    return shard_store_path(session, shard)


def absorb_store(dst, kind: str, path: str) -> None:
    """Copy one store file's values AND metadata (which carries the unit
    journal) into ``dst``; serving winner records merge under the
    better-value / never-staler policy."""
    src = make_store(kind, path)
    dst.update(src.items())
    if hasattr(src, "meta_items"):
        dst.update_meta(src.meta_items())
    absorb_winners(dst, src)
    if hasattr(src, "close"):
        src.close()


def merge_shard_stores(session, paths: list[str]) -> None:
    """Fold worker shard stores into the session's main store, then delete
    the shard files."""
    if session.store is None:
        return
    for path in paths:
        if path is None or not os.path.exists(path):
            continue
        absorb_store(session.store, session.spec.store, path)
        os.remove(path)
    session.store.save()


def recover_shard_stores(session) -> int:
    """Absorb shard stores left behind by a killed parallel run.

    Workers journal completed units into their shard stores incrementally,
    so even though the dead parent never merged them, their measurements and
    journal entries are intact on disk.  Returns how many files were
    recovered.
    """
    base = session._store_path
    if session.store is None or base is None:
        return 0
    # the namespace digest scopes recovery to THIS spec's shards: a different
    # spec writing through the same store path leaves shards this glob must
    # not absorb (its journal entries would be orphaned, its values wrong)
    pattern = re.compile(
        re.escape(f"{os.path.basename(base)}.{shard_namespace(session)}")
        + r"\.shard[A-Za-z0-9_-]+$"
    )
    d = os.path.dirname(base) or "."
    if not os.path.isdir(d):
        return 0
    # sqlite keeps "-wal" / "-shm" / "-journal" files beside a store that is
    # open or was killed; they belong to their shard and are not stores
    leftovers = sorted(
        os.path.join(d, f)
        for f in os.listdir(d)
        if pattern.fullmatch(f) and not f.endswith(("-wal", "-shm", "-journal"))
    )
    merge_shard_stores(session, leftovers)
    # a killed run's workers also leave trace.shard<k>.jsonl files beside the
    # parent trace; fold them in so the resumed trace keeps their spans
    session.telemetry.recover()
    return len(leftovers)


# ----------------------------------------------------------- worker payloads


def _check_shippable(session) -> dict:
    """Validate that the session can be rebuilt in a worker; return the
    serialized spec.  Raises the same errors for every parallel executor."""
    if session._has_overrides:
        raise RuntimeError(
            "parallel matrix runs rebuild the session from the serialized "
            "spec in worker processes; in-process overrides (space/"
            "measurement_factory/dataset/store objects) cannot be shipped"
        )
    if not session._backend.serializable:
        raise RuntimeError(
            f"backend {session.spec.backend!r} holds in-process callables and "
            "cannot be rebuilt in shard workers; use a name-resolvable "
            "backend (e.g. 'costmodel') for parallel runs"
        )
    return session.spec.to_dict()  # raises early if not serializable


def _refuse_on_chip(session, executor: str) -> None:
    """A backend that measures on the accelerator, on a TPU host: worker
    processes would each try to open the chips the parent may already hold.
    The ``device`` executor is the path that drives every chip from this
    one process."""
    if not session._backend.uses_device:
        return
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"executor {executor!r} cannot run backend "
            f"{session.spec.backend!r} on a TPU host: a chip belongs to one "
            "process. Use executor='device', which pins one worker thread "
            "to each chip from this process"
        )


def _make_payloads(
    plan: ExecutionPlan, spec_dict: dict
) -> list[dict]:
    """Group units round-robin into at most ``max_workers`` payloads (the
    static schedule — one payload per worker)."""
    n = max(1, min(plan.max_workers, len(plan.units)))
    return _payloads_for_groups(plan, spec_dict, [plan.units[k::n] for k in range(n)])


def _make_unit_payloads(plan: ExecutionPlan, spec_dict: dict) -> list[dict]:
    """One payload per unit (the stealing schedule for the generic futures
    seam): any pool drains the queue in completion order, at the cost of a
    session rebuild per unit."""
    return _payloads_for_groups(plan, spec_dict, [[u] for u in plan.units])


def _payloads_for_groups(
    plan: ExecutionPlan, spec_dict: dict, groups: list[list[ExperimentUnit]]
) -> list[dict]:
    """One worker payload per unit group.

    The payload is the remote-executor seam: ``spec`` / ``units`` /
    ``store_path`` are plain JSON; ``dataset`` ships the parent's
    pre-generated sample arrays so N workers never redo the 20k-sample
    generation (remote workers that cannot receive arrays should use
    ``TuningSpec.dataset_cache`` on a shared path instead).
    """
    session = plan.session
    n = len(groups)
    dataset = session._get_dataset()
    dataset_payload = (
        None if dataset is None else (dataset.indices, dataset.values)
    )
    # a warm parent store is shipped (by path) to every worker: shard stores
    # start as copies, so previously-measured entries are served as hits — a
    # second parallel run performs zero re-measurements and the merged store
    # comes back bit-identical
    base_store_path = (
        session._store_path
        if session.spec.store is not None
        and session._store_path is not None
        and os.path.exists(session._store_path)
        else None
    )
    # telemetry fan-out: each worker appends to its own trace.shard<k>.jsonl
    # beside the parent trace (None when telemetry is off — workers then run
    # the exact disabled path)
    tel = session.telemetry
    return [
        {
            "spec": spec_dict,
            "units": [u.to_dict() for u in groups[k]],
            "store_path": _shard_store_path(session, k),
            "base_store_path": base_store_path,
            "dataset": dataset_payload,
            "trace_path": tel.shard_path(k),
            "trace_src": tel.shard_src(k),
        }
        for k in range(n)
    ]


def _unit_worker(payload: dict) -> list[dict]:
    """Runs one payload's units in a worker (any process, any host with the
    package importable and the store paths reachable).  Rebuilds the session
    from the serialized spec, journals each completed unit into the shard
    store, and returns JSON-able :class:`UnitResult` dicts."""
    from .api import TuningSession, TuningSpec  # lazy: avoid an import cycle
    from .dataset import SampleDataset

    spec = TuningSpec.from_dict(payload["spec"])
    telemetry = None
    if payload.get("trace_path") is not None:
        from ..telemetry.tracer import Telemetry

        telemetry = Telemetry(
            payload["trace_path"], src=payload.get("trace_src") or "shard"
        )
    session = TuningSession(
        spec, store_path=payload["store_path"], telemetry=telemetry
    )
    base_path = payload.get("base_store_path")
    if (
        base_path is not None
        and session.store is not None
        and os.path.exists(base_path)
    ):
        # seed the shard store from the parent's warm store: hits are served
        # without re-measuring (or recompiling, for the pallas backend)
        absorb_store(session.store, spec.store, base_path)
    if payload.get("dataset") is not None:
        indices, values = payload["dataset"]
        session._dataset = SampleDataset(
            space=session.space, indices=indices, values=values
        )
    journal = session.unit_journal()
    out = []
    try:
        for d in payload["units"]:
            result = session.run_unit(ExperimentUnit.from_dict(d))
            if journal is not None:
                journal.put(result)
            out.append(result.to_dict())
        session.save_store()
    finally:
        if telemetry is not None:
            # flush the shard trace (counters event + fh) even on a crash, so
            # the parent's fail-fast absorb keeps the spans written so far
            telemetry.close()
    return out


def _absorb_trace_shards(plan: ExecutionPlan, payloads: list[dict]) -> None:
    """Fold worker trace shards into the parent trace, deterministically
    (shard-index order; each shard's own event order preserved)."""
    paths = [p.get("trace_path") for p in payloads]
    plan.session.telemetry.absorb([p for p in paths if p is not None])


def _collect(plan: ExecutionPlan, payloads: list[dict],
             worker_results: list[list[dict]]) -> list[UnitResult]:
    merge_shard_stores(
        plan.session, [p["store_path"] for p in payloads]
    )
    _absorb_trace_shards(plan, payloads)
    return [
        UnitResult.from_dict(d) for results in worker_results for d in results
    ]


def _drain_futures(plan: ExecutionPlan, payloads: list[dict],
                   futures: list) -> list[list[dict]]:
    """Collect worker futures as they complete, failing fast.

    On the first worker exception: cancel every outstanding future, wait for
    the ones already running to retire (so no worker is still writing its
    shard store), absorb completed workers' shard stores — their journaled
    units survive into the parent store for ``resume=True`` — and re-raise.
    A slow healthy worker can no longer hide a failed one behind an
    in-submission-order ``f.result()`` wait.
    """
    import concurrent.futures

    tel = plan.session.telemetry
    results: list[list[dict] | None] = [None] * len(futures)
    index = {f: i for i, f in enumerate(futures)}
    done = 0
    try:
        for f in concurrent.futures.as_completed(futures):
            results[index[f]] = f.result()
            done += 1
            if tel.enabled:
                # payloads not yet retired (per-unit payloads under the
                # stealing scheduler, per-worker groups under static)
                tel.gauge("scheduler.queue_depth", len(futures) - done)
    except BaseException:
        for f in futures:
            f.cancel()
        concurrent.futures.wait(futures)
        merge_shard_stores(plan.session, [p["store_path"] for p in payloads])
        _absorb_trace_shards(plan, payloads)
        raise
    return results


# ------------------------------------------------- work-stealing machinery


def _steal_context(plan: ExecutionPlan, spec_dict: dict) -> dict:
    """The per-WORKER context for the stealing scheduler, shipped once per
    worker (pool initializer / thread init) instead of once per unit: the
    serialized spec, the warm parent store path, the dataset arrays, and the
    parent trace path (workers derive their own shard names from their
    identity, so the parent need not know worker pids up front)."""
    session = plan.session
    dataset = session._get_dataset()
    tel = session.telemetry
    base_store_path = (
        session._store_path
        if session.spec.store is not None
        and session._store_path is not None
        and os.path.exists(session._store_path)
        else None
    )
    return {
        "spec": spec_dict,
        "store_base": (
            session._store_path
            if session.spec.store is not None and session._store_path is not None
            else None
        ),
        # workers build `<store_base>.<shard_ns>.shard<ident>` — the parent
        # computes the namespace once so every worker agrees on it
        "shard_ns": (
            shard_namespace(session)
            if session.spec.store is not None and session._store_path is not None
            else None
        ),
        "base_store_path": base_store_path,
        "dataset": (
            None if dataset is None else (dataset.indices, dataset.values)
        ),
        "trace_path": getattr(tel, "path", None) if tel.enabled else None,
    }


def _build_worker_state(ctx: dict, ident: int) -> dict:
    """One persistent worker session keyed by ``ident`` (pid for process
    workers, device index for device threads): shard store
    ``<base>.<ns8>.shard<ident>``, trace shard ``trace.shard<ident>.jsonl``
    — both names the parent's glob-based recovery already understands."""
    from .api import TuningSession, TuningSpec  # lazy: avoid an import cycle
    from .dataset import SampleDataset

    spec = TuningSpec.from_dict(ctx["spec"])
    telemetry = None
    if ctx.get("trace_path"):
        from ..telemetry.events import shard_file
        from ..telemetry.tracer import Telemetry

        telemetry = Telemetry(
            shard_file(ctx["trace_path"], ident), src=f"shard{ident}"
        )
    store_path = (
        None
        if ctx.get("store_base") is None
        else f"{ctx['store_base']}.{ctx['shard_ns']}.shard{ident}"
    )
    session = TuningSession(spec, store_path=store_path, telemetry=telemetry)
    base = ctx.get("base_store_path")
    if base is not None and session.store is not None and os.path.exists(base):
        # seed the shard store from the parent's warm store: hits are served
        # without re-measuring (or recompiling, for the pallas backend)
        absorb_store(session.store, spec.store, base)
    if ctx.get("dataset") is not None:
        indices, values = ctx["dataset"]
        session._dataset = SampleDataset(
            space=session.space, indices=indices, values=values
        )
    return {
        "session": session,
        "journal": session.unit_journal(),
        "telemetry": telemetry,
        "ident": int(ident),
    }


def _close_worker_state(state: dict | None) -> None:
    """Flush and close a worker's shard store and its trace (counters + fh);
    a device-executor thread's store must be closed before the parent
    absorbs it."""
    if state is None:
        return
    try:
        session = state["session"]
        session.save_store()
        if hasattr(session.store, "close"):
            session.store.close()
    finally:
        if state["telemetry"] is not None:
            state["telemetry"].close()


def _run_state_unit(state: dict, unit_dict: dict) -> tuple[int, dict]:
    """Run one pulled unit against a persistent worker state, journaling it
    into the worker's shard store.  Returns ``(worker ident, result dict)``
    so the parent can attribute completions (steal accounting)."""
    session = state["session"]
    result = session.run_unit(ExperimentUnit.from_dict(unit_dict))
    if state["journal"] is not None:
        state["journal"].put(result)   # throttled flush — a kill loses little
    return state["ident"], result.to_dict()


def _drain_steal(plan: ExecutionPlan, futures: list, n_workers: int) -> list[dict]:
    """Collect per-unit futures as they complete, failing fast (the caller
    owns pool shutdown + shard merge on both paths).

    Steal accounting: worker identities are mapped to slots in first-seen
    completion order; a completed unit whose worker slot differs from its
    static round-robin owner (``unit_index % n_workers``) counts as one
    ``scheduler.steals`` — an approximate but cheap measure of how much the
    queue rebalanced versus the static partition.  ``scheduler.queue_depth``
    gauges units not yet retired after each completion."""
    import concurrent.futures

    tel = plan.session.telemetry
    n = len(futures)
    results: list[dict | None] = [None] * n
    index = {f: i for i, f in enumerate(futures)}
    slot_of: dict[int, int] = {}
    done = 0
    for f in concurrent.futures.as_completed(futures):
        ident, rd = f.result()        # re-raises the worker's exception
        i = index[f]
        results[i] = rd
        done += 1
        if tel.enabled:
            slot = slot_of.setdefault(ident, len(slot_of))
            tel.gauge("scheduler.queue_depth", n - done)
            if slot != i % n_workers:
                tel.inc("scheduler.steals")
    return results


# ------------------------------------------------------------------- process

#: per-process worker state for the stealing scheduler (set by the pool
#: initializer in each spawned worker; module-global because pool tasks
#: can only receive picklable arguments)
_STEAL_STATE: dict = {}


def _steal_init(ctx: dict) -> None:
    """Pool initializer (runs once per spawned worker process): build the
    persistent session keyed by pid and register its flush at process exit
    — ``ProcessPoolExecutor.shutdown(wait=True)`` joins workers, so the
    parent merges only after every shard store is saved."""
    import atexit

    state = _build_worker_state(ctx, ident=os.getpid())
    _STEAL_STATE["state"] = state
    atexit.register(_close_worker_state, state)


def _steal_unit_task(unit_dict: dict) -> tuple[int, dict]:
    return _run_state_unit(_STEAL_STATE["state"], unit_dict)


def _merge_steal_shards(session) -> None:
    """Fold worker shard stores and trace shards into the parent.  Worker
    identities (pids / device indices) are not known to the parent up
    front, so this is the same glob the kill-recovery path uses."""
    recover_shard_stores(session)


def _run_process_static(plan: ExecutionPlan) -> list[UnitResult]:
    """The static schedule: one round-robin payload per worker, submitted to
    a spawn pool and drained ``as_completed`` — same fail-fast semantics as
    every other parallel path (the first worker exception absorbs completed
    workers' shard stores and traces before re-raising)."""
    import concurrent.futures
    import multiprocessing

    spec_dict = _check_shippable(plan.session)
    _refuse_on_chip(plan.session, "process")
    payloads = _make_payloads(plan, spec_dict)
    pool = concurrent.futures.ProcessPoolExecutor(
        max_workers=len(payloads),
        mp_context=multiprocessing.get_context("spawn"),
    )
    try:
        futures = [pool.submit(_unit_worker, p) for p in payloads]
        worker_results = _drain_futures(plan, payloads, futures)
    finally:
        pool.shutdown()
    return _collect(plan, payloads, worker_results)


def _run_process(plan: ExecutionPlan) -> list[UnitResult]:
    """Spawn-process fan-out.  Stealing (default): persistent per-process
    sessions pull units from the shared pool queue; static: the legacy
    one-payload-per-worker partition."""
    if plan.scheduler == "static":
        return _run_process_static(plan)
    import concurrent.futures
    import multiprocessing

    spec_dict = _check_shippable(plan.session)
    _refuse_on_chip(plan.session, "process")
    ctx = _steal_context(plan, spec_dict)
    n = max(1, min(plan.max_workers, len(plan.units)))
    pool = concurrent.futures.ProcessPoolExecutor(
        max_workers=n,
        mp_context=multiprocessing.get_context("spawn"),
        initializer=_steal_init,
        initargs=(ctx,),
    )
    try:
        futures = [
            pool.submit(_steal_unit_task, u.to_dict()) for u in plan.units
        ]
        try:
            dicts = _drain_steal(plan, futures, n)
        except BaseException:
            for f in futures:
                f.cancel()
            # join workers first (their exit handlers flush shard stores),
            # THEN absorb what they completed — fail-fast parity with
            # _drain_futures: journaled units survive into the parent
            pool.shutdown(wait=True)
            _merge_steal_shards(plan.session)
            raise
    finally:
        pool.shutdown(wait=True)
    _merge_steal_shards(plan.session)
    return [UnitResult.from_dict(d) for d in dicts]


register_executor(Executor(name="process", run=_run_process, parallel=True))


# ------------------------------------------------------------------- futures


def _run_futures(plan: ExecutionPlan) -> list[UnitResult]:
    """The generic ``concurrent.futures`` seam.  Under the stealing
    scheduler each payload carries exactly one unit, so ANY pool — thread,
    process, or remote adapter — drains the queue in completion order; under
    ``static`` the legacy one-payload-per-worker grouping is submitted."""
    spec_dict = _check_shippable(plan.session)
    _refuse_on_chip(plan.session, "futures")
    if plan.scheduler == "static":
        payloads = _make_payloads(plan, spec_dict)
    else:
        payloads = _make_unit_payloads(plan, spec_dict)
    pool = plan.futures_pool
    owned = pool is None
    if owned:
        import concurrent.futures
        import multiprocessing

        pool = concurrent.futures.ProcessPoolExecutor(
            max_workers=max(1, min(plan.max_workers, len(payloads))),
            mp_context=multiprocessing.get_context("spawn"),
        )
    try:
        futures = [pool.submit(_unit_worker, p) for p in payloads]
        worker_results = _drain_futures(plan, payloads, futures)
    finally:
        if owned:
            pool.shutdown()
    return _collect(plan, payloads, worker_results)


register_executor(Executor(name="futures", run=_run_futures, parallel=True))


# -------------------------------------------------------------------- device


def _device_worker(payload: dict, device) -> list[dict]:
    """One shard's units pinned to one jax device.  ``jax.default_device``
    is thread-local, so concurrent shard threads each keep their own pin."""
    import jax

    with jax.default_device(device):
        return _unit_worker(payload)


def _run_device(plan: ExecutionPlan) -> list[UnitResult]:
    """Fan units across ``jax.devices()`` within this process.

    Same payloads and shard-store plumbing as the process executor, but the
    workers are threads pinned to devices instead of spawned interpreters —
    the right shape for a multi-chip host where process spawn (and per-worker
    jax re-initialization) costs more than the matrix.  On a host faking
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this
    exercises the exact fan-out path with CPU "chips".
    """
    import concurrent.futures
    import warnings

    import jax

    spec_dict = _check_shippable(plan.session)
    devices = jax.devices()
    if plan.max_workers > len(devices):
        warnings.warn(
            f"device executor: {plan.max_workers} workers requested but only "
            f"{len(devices)} jax device(s) present; capping"
        )
        plan = ExecutionPlan(
            session=plan.session,
            units=plan.units,
            max_workers=len(devices),
            futures_pool=plan.futures_pool,
            scheduler=plan.scheduler,
        )
    if plan.scheduler == "static":
        payloads = _make_payloads(plan, spec_dict)
        with concurrent.futures.ThreadPoolExecutor(
            max_workers=len(payloads), thread_name_prefix="device-shard"
        ) as pool:
            futures = [
                pool.submit(_device_worker, p, devices[k])
                for k, p in enumerate(payloads)
            ]
            worker_results = _drain_futures(plan, payloads, futures)
        return _collect(plan, payloads, worker_results)
    return _run_device_steal(plan, spec_dict, devices)


def _run_device_steal(
    plan: ExecutionPlan, spec_dict: dict, devices: list
) -> list[UnitResult]:
    """Stealing schedule over device-pinned worker threads.  Each thread
    builds ONE persistent session at thread start (compilation caches stay
    warm across units) and pulls units from the pool queue as it frees up;
    the worker identity is the device index, so shard stores and trace
    shards use the same ``shard<k>`` names as the static path."""
    import concurrent.futures
    import threading

    import jax

    ctx = _steal_context(plan, spec_dict)
    n = max(1, min(plan.max_workers, len(plan.units)))
    states: list[dict | None] = []
    states_lock = threading.Lock()
    tls = threading.local()

    def _thread_init() -> None:
        with states_lock:
            k = len(states)
            states.append(None)
        state = _build_worker_state(ctx, ident=k)
        state["device"] = devices[k]
        states[k] = state
        tls.state = state

    def _thread_task(unit_dict: dict) -> tuple[int, dict]:
        state = tls.state
        with jax.default_device(state["device"]):
            return _run_state_unit(state, unit_dict)

    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=n,
        thread_name_prefix="device-steal",
        initializer=_thread_init,
    )
    try:
        futures = [
            pool.submit(_thread_task, u.to_dict()) for u in plan.units
        ]
        try:
            dicts = _drain_steal(plan, futures, n)
        except BaseException:
            for f in futures:
                f.cancel()
            pool.shutdown(wait=True)
            for s in states:
                _close_worker_state(s)
            _merge_steal_shards(plan.session)
            raise
    finally:
        pool.shutdown(wait=True)
    for s in states:
        _close_worker_state(s)
    _merge_steal_shards(plan.session)
    return [UnitResult.from_dict(d) for d in dicts]


register_executor(Executor(name="device", run=_run_device, parallel=True))
