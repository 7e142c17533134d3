"""Tuning-as-a-service: the winners index, the query API, the HTTP
endpoint, the job queue, and the fleet loop.

The serving layer's load-bearing promises, each pinned here:

* winners survive save/load round-trips in BOTH store backends, and the
  merge policy (lower value wins, ties keep newer, freshness never moves
  backwards) holds however records race;
* ``best_config`` resolves hit / stale / nearest / miss deterministically,
  misses enqueue idempotent jobs, and the HTTP endpoint is the same
  function over a socket;
* concurrent readers — threads in-process plus spawned subprocesses —
  never see a torn winner while a writer updates the index (WAL-mode
  sqlite + atomic payload merges), and freshness observed by any single
  reader is monotonic;
* a fleet worker drains a miss-enqueued job into a store the collector
  absorbs, after which the same query is a hit.
"""

from __future__ import annotations

import json
import os
import sqlite3
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from repro.core import ExperimentDesign, TuningSession, TuningSpec
from repro.core.stores import (
    MeasurementStore,
    SqliteMeasurementStore,
    absorb_winners,
    make_store,
    merge_winner_payloads,
)
from repro.serving import (
    FleetWorker,
    JobQueue,
    ServeResult,
    WinnerRecord,
    best_config,
    collect_jobs,
    default_miss_spec,
    index_winners,
    job_id_for_spec,
    lookup_winner,
    nearest_winner,
    record_winner,
)
from repro.serving.http import ServingState, make_server
from repro.serving.winners import (
    parse_config_from_store_key,
    parse_winner_key,
    winner_key,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rec(kernel="add", x=8192, y=8192, device="v5e", value=1.0, fresh=100.0,
        config=None, **kw) -> WinnerRecord:
    return WinnerRecord(kernel=kernel, x=x, y=y, device=device,
                        config=config or {"t_x": 4}, value=value,
                        fresh=fresh, **kw)


# ------------------------------------------------------------- key + payload


def test_winner_key_roundtrip():
    key = winner_key("harris", 4096, 2048, "v4")
    assert key == "harris|x=4096|y=2048|v4"
    assert parse_winner_key(key) == ("harris", 4096, 2048, "v4")
    assert parse_winner_key("not-a-winner-key") is None
    assert parse_winner_key("k|x=a|y=2|d") is None


def test_parse_config_from_store_key_skips_final_marker():
    cfg = parse_config_from_store_key(
        "add/v5e/seed=17|t_x=9,t_y=16,w_x=3.5,name=foo|final3"
    )
    assert cfg == {"t_x": 9, "t_y": 16, "w_x": 3.5, "name": "foo"}
    assert parse_config_from_store_key("no-config-here") is None


def test_merge_policy_lower_value_wins():
    worse = rec(value=2.0, fresh=50.0).to_payload()
    better = rec(value=1.0, fresh=10.0).to_payload()
    for old, new in ((worse, better), (better, worse)):
        merged = json.loads(merge_winner_payloads(old, new))
        assert merged["value"] == 1.0
        # freshness is monotonic even when the older record's config wins
        assert merged["fresh"] == 50.0


def test_merge_policy_tie_keeps_newer_config():
    a = rec(value=1.0, fresh=10.0, config={"t_x": 1}).to_payload()
    b = rec(value=1.0, fresh=20.0, config={"t_x": 2}).to_payload()
    assert json.loads(merge_winner_payloads(a, b))["config"] == {"t_x": 2}
    assert json.loads(merge_winner_payloads(b, a))["config"] == {"t_x": 2}


def test_merge_policy_unparseable_loses():
    good = rec(value=5.0).to_payload()
    assert merge_winner_payloads("not json{", good) == good
    assert merge_winner_payloads(None, good) == good
    merged = json.loads(merge_winner_payloads(good, "not json{"))
    assert merged["value"] == 5.0


# ------------------------------------------------------ store round-tripping


@pytest.mark.parametrize("kind", ["json", "sqlite"])
def test_winners_survive_save_load(tmp_path, kind):
    path = str(tmp_path / f"s.{'sqlite' if kind == 'sqlite' else 'json'}")
    store = make_store(kind, path)
    store.put("add/v5e/seed=1|t_x=4", 0.5)
    r = rec(value=0.5, fresh=123.0)
    store.put_winner(r.key, r.to_payload())
    store.save()
    if hasattr(store, "close"):
        store.close()

    reopened = make_store(kind, path)
    got = lookup_winner(reopened, "add", 8192, 8192, "v5e")
    assert got is not None
    assert (got.value, got.fresh, got.config) == (0.5, 123.0, {"t_x": 4})
    assert reopened.get("add/v5e/seed=1|t_x=4") == 0.5
    assert dict(reopened.winner_items()) == {r.key: r.to_payload()}
    if hasattr(reopened, "close"):
        reopened.close()


def test_json_store_without_winners_keeps_legacy_format(tmp_path):
    path = str(tmp_path / "s.json")
    store = MeasurementStore(path)
    store.put("k", 1.0)
    store.save()
    assert "winners" not in json.load(open(path))
    store.put_winner("add|x=1|y=1|d", rec().to_payload())
    store.save()
    assert json.load(open(path))["__format__"] == 3


def test_record_winner_applies_merge_policy_in_store(tmp_path):
    # put_winner is deliberately last-writer-wins (a raw channel); the merge
    # policy is record_winner's job, in both backends
    for kind in ("json", "sqlite"):
        store = make_store(kind, None)
        record_winner(store, rec(value=1.0, fresh=10.0), save=False)
        record_winner(store, rec(value=2.0, fresh=99.0), save=False)
        kept = json.loads(store.get_winner(rec().key))
        assert kept["value"] == 1.0 and kept["fresh"] == 99.0


def test_absorb_winners_merges(tmp_path):
    dst, src = make_store("json", None), make_store("sqlite", None)
    dst.put_winner("k|x=1|y=1|d", rec(value=2.0, fresh=1.0).to_payload())
    src.put_winner("k|x=1|y=1|d", rec(value=1.0, fresh=2.0).to_payload())
    src.put_winner("k|x=2|y=2|d", rec(x=2, y=2, value=3.0).to_payload())
    absorb_winners(dst, src)
    assert json.loads(dst.get_winner("k|x=1|y=1|d"))["value"] == 1.0
    assert len(dict(dst.winner_items())) == 2


def test_index_winners_counts_and_merges():
    dst, a, b = (make_store("json", None) for _ in range(3))
    a.put_winner("k|x=1|y=1|d", rec(value=2.0).to_payload())
    b.put_winner("k|x=1|y=1|d", rec(value=1.0).to_payload())
    assert index_winners(dst, a, save=False) == 1
    assert index_winners(dst, b, save=False) == 1
    assert json.loads(dst.get_winner("k|x=1|y=1|d"))["value"] == 1.0


# --------------------------------------------------- session -> winners index


SMOKE_SPEC = TuningSpec(
    kernel="add",
    backend_kwargs={"chip": "v5e"},
    algorithms=("rs",),
    design=ExperimentDesign(
        sample_sizes=(25,), n_experiments=(4,), final_repeats=3
    ),
    seed=11,
)


def test_session_records_winner_transactionally(tmp_path):
    spec = SMOKE_SPEC.replace(store="json",
                              store_path=str(tmp_path / "c.json"))
    session = TuningSession(spec)
    session.run_matrix()
    store = MeasurementStore(spec.store_path)
    got = lookup_winner(store, "add", 8192, 8192, "v5e")
    assert got is not None
    # the winner points at a measurement the same store actually holds
    assert store.get(got.store_key) == got.value
    assert got.value == min(v for k, v in store.items() if "|final" in k)
    assert got.config == parse_config_from_store_key(got.store_key)
    assert got.fingerprint == session.journal_namespace()
    assert got.fresh > 0


def test_pallas_winner_is_indexed_under_measured_device_kind(tmp_path):
    """A real-kernel winner names the device it was measured on, so an
    interpret-mode CPU winner and a chip winner never share a key."""
    import jax

    spec = TuningSpec(
        kernel="add", backend="pallas",
        backend_kwargs={"x": 16, "y": 256, "repeats": 1},
        budget=2, final_repeats=1, seed=0,
        store="json", store_path=str(tmp_path / "c.json"),
    )
    TuningSession(spec).run()
    store = MeasurementStore(spec.store_path)
    kind = jax.devices()[0].device_kind
    assert [k for k, _ in store.winner_items()] == [f"add|x=16|y=256|{kind}"]
    assert lookup_winner(store, "add", 16, 256, kind) is not None
    assert best_config(store, "add", 16, 256, "pallas").status == "miss"


def test_collector_indexes_pallas_winner_under_the_workers_device(
    tmp_path, monkeypatch
):
    """A collector whose own device differs from the workers' (a CPU
    coordinator over TPU workers) files the winner under the device the
    measurements ran on, which the worker's shard store carries."""
    import jax

    from repro.core.executors import absorb_store
    from repro.serving import record_session_winner

    spec = TuningSpec(
        kernel="add", backend="pallas",
        backend_kwargs={"x": 16, "y": 256, "repeats": 1},
        budget=2, final_repeats=1, seed=0,
        store="json", store_path=str(tmp_path / "shard.json"),
    )
    TuningSession(spec).run()
    worker_kind = jax.devices()[0].device_kind
    parent = TuningSession(spec.replace(store_path=str(tmp_path / "parent.json")))
    absorb_store(parent.store, "json", spec.store_path)

    class OtherDevice:
        platform, device_kind = "tpu", "collector-only device"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [OtherDevice()])
    rec = record_session_winner(parent)
    assert rec is not None and rec.device == worker_kind
    assert [k for k, _ in parent.store.winner_items()] == [
        f"add|x=16|y=256|{worker_kind}"
    ]


# ------------------------------------------------------------------- serving


def serve_store_with(records) -> object:
    store = make_store("json", None)
    for r in records:
        store.put_winner(r.key, r.to_payload())
    return store


def test_best_config_hit_stale_nearest_miss():
    store = serve_store_with([
        rec(x=8192, y=8192, value=1.0, fresh=1000.0),
        rec(x=1024, y=1024, value=2.0, fresh=1000.0),
    ])
    hit = best_config(store, "add", 8192, 8192, "v5e", now=1010.0)
    assert (hit.status, hit.value, hit.age_s) == ("hit", 1.0, 10.0)
    assert hit.matched_key == "add|x=8192|y=8192|v5e"

    stale = best_config(store, "add", 8192, 8192, "v5e", max_age_s=5.0,
                        now=1010.0)
    assert stale.status == "stale" and stale.config == hit.config

    near = best_config(store, "add", 2048, 2048, "v5e")
    assert near.status == "nearest"
    assert near.matched_key == "add|x=1024|y=1024|v5e"  # closer in log-space

    for kernel, device in (("harris", "v5e"), ("add", "v4")):
        assert best_config(store, kernel, 8192, 8192, device).status == "miss"


def test_nearest_is_log_space_and_deterministic():
    store = serve_store_with([
        rec(x=4096, y=4096, value=1.0),   # 2x down from 8192
        rec(x=32768, y=32768, value=2.0)  # 4x up
    ])
    near = nearest_winner(store, "add", 8192, 8192, "v5e")
    assert near.x == 4096


def test_miss_enqueues_idempotent_job(tmp_path):
    store = make_store("sqlite", str(tmp_path / "s.sqlite"))
    queue = JobQueue(store, "sqlite", str(tmp_path / "s.sqlite"),
                     str(tmp_path / "q"))
    res = best_config(store, "add", 8192, 8192, "v5e", queue=queue)
    assert res.status == "miss" and res.job_id is not None
    again = best_config(store, "add", 8192, 8192, "v5e", queue=queue)
    assert again.job_id == res.job_id
    assert queue.depth() == 1
    job = queue.job(res.job_id)
    assert job["state"] == "pending"
    assert job["spec"]["kernel"] == "add"
    store.close()


def test_default_miss_spec_backend_split():
    cm = default_miss_spec("add", 8192, 8192, "v4")
    assert cm.backend == "costmodel"
    assert cm.backend_kwargs == {"chip": "v4"}
    pl = default_miss_spec("add", 512, 256, "tpu-v5e")
    assert pl.backend == "pallas"
    assert pl.backend_kwargs == {"x": 512, "y": 256}


def test_serve_result_dict_shape():
    d = ServeResult(status="miss", kernel="k", x=1, y=2, device="d").to_dict()
    assert d["status"] == "miss" and d["config"] is None and d["job_id"] is None


# ---------------------------------------------------------------------- http


def test_http_endpoint(tmp_path):
    store = serve_store_with([rec(value=1.5, fresh=100.0)])
    queue = JobQueue(store, "json", str(tmp_path / "s.json"),
                     str(tmp_path / "q"))
    server = make_server(ServingState(store, queue=queue), port=0)
    host, port = server.server_address[:2]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        def get(path):
            with urllib.request.urlopen(f"http://{host}:{port}{path}") as r:
                return r.status, json.loads(r.read())

        assert get("/healthz") == (200, {"ok": True})

        code, body = get("/best_config?kernel=add&x=8192&y=8192&device=v5e")
        assert code == 200
        assert body["status"] == "hit" and body["value"] == 1.5

        code, body = get("/best_config?kernel=nope&x=4&y=4&device=v5e")
        assert code == 200 and body["status"] == "miss"
        assert body["job_id"]  # queue attached: the miss enqueued a job

        code, body = get("/stats")
        assert code == 200 and body["winners"] == 1
        assert body["queue_depth"] == 1

        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://{host}:{port}/best_config?kernel=add&x=nope&y=1&device=d"
            )
        assert err.value.code == 400
    finally:
        server.shutdown()
        server.server_close()


# -------------------------------------------------------- concurrent serving


HAMMER = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from repro.core.stores import make_store
from repro.serving import best_config
store = make_store("sqlite", sys.argv[2])
last_fresh = 0.0
for _ in range(120):
    res = best_config(store, "add", 8192, 8192, "v5e")
    if res.status != "hit":
        sys.exit(f"unexpected status {res.status}")
    # consistency: value and config were written as one payload; a torn
    # read would decouple them
    if res.config["i"] != int(round(1000.0 - res.value)):
        sys.exit(f"torn read: value={res.value} config={res.config}")
    if res.fresh < last_fresh:
        sys.exit(f"freshness went backwards: {res.fresh} < {last_fresh}")
    last_fresh = res.fresh
store.close()
print("ok")
"""


def test_concurrent_readers_never_see_torn_winners(tmp_path):
    """N reader threads + 2 spawned reader subprocesses hammer
    ``best_config`` while a writer thread rewrites the winner through the
    merge policy.  Every observed record must be internally consistent
    (value matches config — they're written as one payload) and each
    reader's observed freshness must be monotonic."""
    path = str(tmp_path / "serve.sqlite")
    seed_store = SqliteMeasurementStore(path, autosave_every=0)

    # sqlite serving store runs WAL with a busy timeout (the concurrency
    # contract): verify the pragmas actually took
    assert seed_store._conn.execute(
        "PRAGMA journal_mode").fetchone()[0].lower() == "wal"
    assert seed_store._conn.execute(
        "PRAGMA busy_timeout").fetchone()[0] == 5000

    def winner_at(i: int) -> WinnerRecord:
        # decreasing value => each update wins the merge; fresh stamps are
        # record_winner's wall clock, which only moves forward
        return rec(value=1000.0 - i, config={"i": i})

    record_winner(seed_store, winner_at(0))
    seed_store.close()

    stop = threading.Event()
    errors: list[str] = []

    def writer():
        # sqlite connections are thread-bound: the writer owns its handle
        store = SqliteMeasurementStore(path, autosave_every=0)
        i = 0
        try:
            while not stop.is_set():
                i += 1
                record_winner(store, winner_at(i))
        finally:
            store.close()

    def reader():
        store = SqliteMeasurementStore(path)
        last_fresh = 0.0
        try:
            for _ in range(200):
                res = best_config(store, "add", 8192, 8192, "v5e")
                if res.status != "hit":
                    errors.append(f"status {res.status}")
                    return
                if res.config["i"] != int(round(1000.0 - res.value)):
                    errors.append(f"torn: {res.value} vs {res.config}")
                    return
                if res.fresh < last_fresh:
                    errors.append(f"fresh regressed {res.fresh}<{last_fresh}")
                    return
                last_fresh = res.fresh
        finally:
            store.close()

    wt = threading.Thread(target=writer)
    readers = [threading.Thread(target=reader) for _ in range(4)]
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", HAMMER, os.path.join(REPO, "src"), path],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for _ in range(2)
    ]
    wt.start()
    for r in readers:
        r.start()
    for r in readers:
        r.join(timeout=120)
    outs = [p.communicate(timeout=120)[0] for p in procs]
    stop.set()
    wt.join(timeout=120)

    assert errors == []
    for p, out in zip(procs, outs, strict=True):
        assert p.returncode == 0, out
        assert "ok" in out


# ------------------------------------------------------------------ JobQueue


def test_job_id_ignores_storage_fields():
    a = default_miss_spec("add", 8192, 8192, "v5e").to_dict()
    b = dict(a, store="sqlite", store_path="/somewhere/else.sqlite")
    assert job_id_for_spec(a) == job_id_for_spec(b)
    c = dict(a, kernel="harris")
    assert job_id_for_spec(a) != job_id_for_spec(c)


def queue_at(tmp_path, name="q") -> JobQueue:
    return JobQueue(make_store("json", None), "json",
                    str(tmp_path / "s.json"), str(tmp_path / name),
                    claim_timeout_s=0.2)


def test_claim_is_exclusive_then_released(tmp_path):
    q = queue_at(tmp_path)
    assert q.claim_unit("j1", "rs/S25/E4/e0:4", "w1") == "fresh"
    assert q.claim_unit("j1", "rs/S25/E4/e0:4", "w2") is None
    assert q.unit_claimed("j1", "rs/S25/E4/e0:4")
    q.release_unit("j1", "rs/S25/E4/e0:4")
    assert q.claim_unit("j1", "rs/S25/E4/e0:4", "w2") == "fresh"


def test_stale_claim_is_stolen(tmp_path):
    q = queue_at(tmp_path)
    assert q.claim_unit("j1", "u", "victim") == "fresh"
    path = q._claim_path("j1", "u")
    old = time.time() - 60.0
    os.utime(path, (old, old))    # the victim "died" a minute ago
    assert q.claim_unit("j1", "u", "peer") == "stolen"
    assert open(path).read() == "peer"


def test_heartbeat_prevents_steal(tmp_path):
    q = queue_at(tmp_path)
    q.claim_unit("j1", "u", "w1")
    path = q._claim_path("j1", "u")
    old = time.time() - 60.0
    os.utime(path, (old, old))
    q.heartbeat_unit("j1", "u")   # long unit, still alive
    assert q.claim_unit("j1", "u", "peer") is None


def test_done_markers_are_atomic_json(tmp_path):
    q = queue_at(tmp_path)
    assert q.unit_done("j1", "u") is None
    q.write_unit_done("j1", "u", {"ident": "w1", "stolen": False})
    assert q.unit_done("j1", "u") == {"ident": "w1", "stolen": False}
    q.cleanup_job_files("j1")
    assert q.unit_done("j1", "u") is None
    assert not any(f.startswith("j1.") for f in os.listdir(q.qdir))


def test_mark_done_persists_through_store(tmp_path):
    path = str(tmp_path / "s.json")
    store = make_store("json", path)
    q = JobQueue(store, "json", path, str(tmp_path / "q"))
    jid = q.enqueue(SMOKE_SPEC)
    assert [j["id"] for j in q.pending_jobs()] == [jid]
    q.mark_done(jid, ident="collect")
    assert q.pending_jobs() == [] and q.job(jid)["state"] == "done"
    # a fresh handle sees it too — the record rode the store
    q2 = JobQueue(make_store("json", path), "json", path, str(tmp_path / "q"))
    assert q2.job(jid)["state"] == "done"


# ------------------------------------------------------------- fleet end2end


def test_fleet_fills_a_miss_end_to_end(tmp_path):
    """miss -> enqueue -> one fleet worker drains -> collect -> hit, with
    the collected measurements byte-identical to a serial run."""
    path = str(tmp_path / "serve.sqlite")
    store = make_store("sqlite", path)
    queue = JobQueue(store, "sqlite", path, str(tmp_path / "queue"))
    spec = SMOKE_SPEC
    res = best_config(store, "add", 8192, 8192, "v5e", queue=queue,
                      enqueue_spec=spec)
    assert res.status == "miss" and res.job_id
    store.close()

    worker = FleetWorker("sqlite", path, str(tmp_path / "queue"), ident="w1")
    assert worker.drain(max_jobs=1, timeout_s=120.0) == 1
    collected = collect_jobs("sqlite", path, str(tmp_path / "queue"))
    assert collected == [res.job_id]

    store = make_store("sqlite", path)
    hit = best_config(store, "add", 8192, 8192, "v5e")
    assert hit.status == "hit"
    assert hit.fingerprint  # provenance rode along
    q = JobQueue(store, "sqlite", path, str(tmp_path / "queue"))
    assert q.depth() == 0 and q.job(res.job_id)["state"] == "done"

    # byte-identity vs the serial reference
    serial = TuningSession(
        spec.replace(store="json", store_path=str(tmp_path / "serial.json"))
    )
    serial.run_matrix()
    fleet_values = {
        k: v for k, v in store.items() if not k.startswith("__")
    }
    serial_values = dict(MeasurementStore(str(tmp_path / "serial.json")).items())
    assert fleet_values == serial_values
    store.close()


# --------------------------------------------------------- staticcheck knobs


def test_staticcheck_sets_cover_serving_knobs():
    """The serving layer's pacing/plumbing knobs are registered with the
    static gate: PROV001 guards fleet pacing out of provenance, OBS001
    keeps serve-dir plumbing out of identity sinks."""
    from repro.staticcheck.obs import TELEMETRY_TOKENS
    from repro.staticcheck.prov import SPEED_KNOBS

    assert {"claim_timeout_s", "poll_s", "stall_s"} <= set(SPEED_KNOBS)
    assert {"serve_dir", "qdir", "queue_dir"} <= set(TELEMETRY_TOKENS)
