"""Tuning-as-a-service: serve tuned winners, enqueue what's missing.

The paper's result — which search algorithm wins depends on the sample
budget — only pays off in production if tuned configurations are *served*
rather than rediscovered per process tree.  This package layers three
pieces over the measurement store:

* :mod:`repro.serving.winners` — a per-``(kernel, x, y, device)`` best-config
  index living in the store itself (a ``winners`` table in the sqlite
  backend, a ``"winners"`` mapping in the JSON format), maintained
  transactionally as :class:`~repro.core.api.TuningSession` records results.
* :mod:`repro.serving.api` — the query layer: :func:`best_config` answers
  instantly on an exact-geometry hit, falls back to the nearest geometry,
  and on a miss optionally enqueues an async tuning job.  ``repro.serve``
  re-exports it as the stable entry point.
* :mod:`repro.serving.queue` / :mod:`repro.serving.fleet` — a shared-store
  work queue with the same ``O_EXCL`` claim + stale-claim-steal discipline
  as the persistent compile cache, so fleet workers on any host can claim
  :class:`~repro.core.workunits.ExperimentUnit` jobs, crash, and be resumed
  by peers.

``python -m repro.serving`` exposes the whole flow (HTTP endpoint, query,
enqueue, worker, collect) on the command line; see ``docs/serving.md``.
"""

from .api import ServeResult, best_config, default_miss_spec, open_serve_store
from .fleet import FleetWorker, collect_jobs
from .queue import JobQueue, job_id_for_spec
from .winners import (
    WinnerRecord,
    all_winners,
    index_winners,
    lookup_winner,
    nearest_winner,
    record_session_winner,
    record_winner,
    session_geometry,
)

__all__ = [
    "FleetWorker",
    "JobQueue",
    "ServeResult",
    "WinnerRecord",
    "all_winners",
    "best_config",
    "collect_jobs",
    "default_miss_spec",
    "index_winners",
    "job_id_for_spec",
    "lookup_winner",
    "nearest_winner",
    "open_serve_store",
    "record_session_winner",
    "record_winner",
    "session_geometry",
]
