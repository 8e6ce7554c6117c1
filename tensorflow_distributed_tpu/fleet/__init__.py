"""Fleet serving: a health-aware router + lifecycle controller over N
engine-replica processes (README "Fleet serving").

The serve/ engine is a single process: one scheduler, one slot cache,
one journal. Every resilience mechanism the repo has built — fault
plans, the restart supervisor, journal resume, anomaly detection,
elastic restarts, hot weight swap — protects exactly that one process.
This package is the layer above: a **fleet** that stays within SLO
while individual replicas die, restart, resize, and hot-swap
checkpoints (the source paper's fault-tolerant multi-process serving
claim restated at fleet scale — PAPERS.md 1605.08695, 1811.02084).

- :mod:`fleet.replica` — the per-replica contract: an append-only
  JSONL **inbox** each replica tails for requests and control commands
  (``--serve.inbox``), the per-epoch workspace layout, and the handle
  the router/controller read snapshots and journals through.
- :mod:`fleet.router` — SLO-class-aware dispatch across replicas,
  driven by each replica's ``--observe.export-path`` snapshot
  (occupancy, queue depth, per-class TTFT p95, live anomaly state).
  A replica with an active anomaly or a stale/frozen snapshot is
  QUARANTINED from new admissions and its in-flight requests are
  re-dispatched as journal-style continuations (token-identical by
  greedy determinism — the PR-6 contract); per-dispatch timeout +
  capped-backoff retry; lowest-class load shedding when the whole
  fleet is saturated (shed, never hang).
- :mod:`fleet.controller` — replica lifecycle (spawn/restart with the
  supervisor's leg semantics and capped backoff, drain-before-stop),
  a checkpoint-directory watch, and ROLLING weight swaps: new weights
  reach the fleet one replica at a time via the live ``swap_params``
  path (sha256-verified, EMA-preferred), so serving capacity never
  drops below N-1 during an upgrade; model staleness (steps between
  trained and served weights) is tracked per replica.
- :mod:`fleet.run` — the front-end driver gluing the three together
  (and the ``python -m tensorflow_distributed_tpu.fleet.run`` CLI).

Everything here is host-side policy — stdlib + numpy, no jax — so the
router/controller suites run on fake replicas with a fake clock
(tests/test_fleet.py): failover with token identity, quarantine and
rejoin, rolling swaps one replica at a time, zero lost requests. The
real thing on accelerators, one replica a chip, has not run
(ROADMAP.md B6).
"""
