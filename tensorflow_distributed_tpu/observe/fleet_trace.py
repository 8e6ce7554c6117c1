"""Fleet-level tracing: the router's own span emitter plus the
cross-replica trace stitcher.

PR 15 made serving a *fleet* — a router process dispatching to N
serve-replica processes — but every trace we could render was still
per-process: replica r0's ``ServeTracer`` file shows its half of a
failover and nothing else. This module closes that gap in two parts.

**FleetTracer** is the router's span recorder (cat ``fleet``), built
on the same :class:`~.trace.ChromeTracer` primitives the serve tracer
uses: one async ``request`` span per client request (submit ->
done/shed), a ``client_queue`` child that reopens on every
retry/re-dispatch (time the request spent back at the router), and one
``dispatch`` span per generation keyed by the PR-15 wire id
``gen_rid = rid*1024 + dispatches`` — deliberately the SAME id the
replica-side scheduler sees, so the stitched timeline joins router and
replica spans for one leg by id alone. Quarantines, rejoins, deaths,
restarts, retries and re-dispatches land as instants; waiting/inflight
ride counter tracks.

**stitch()** merges the router trace with every replica's ServeTracer
file into ONE balanced Perfetto timeline. Processes share no
``perf_counter`` origin, so each file carries a ``clock_sync``
metadata anchor (wall time at ts=0, written by ChromeTracer) and each
replica gets a clock *offset* estimated from the snapshot liveness
triplet: the replica stamps ``wall_ts`` (its clock) into
``snapshot.json`` and the filesystem stamps mtime (the router's
frame), so ``median(mtime - wall_ts)`` is that replica's skew —
:func:`estimate_offset`. Sources whose file is torn (a SIGKILL
mid-rename) are skipped with a marker instant rather than sinking the
merge. A replica killed mid-request leaves unmatched ``b`` spans; the
stitcher closes them (``process_death=True``) at the router's
``redispatch``/``retry`` instant for that generation — that IS when
the fleet declared the leg dead — so the merged file is balanced by
construction and a SIGKILL failover renders as router-queue ->
replica-A prefill/decode -> process_death -> re-dispatch -> replica-B
continuation on a single track.

**decompose()** reads the merged timeline back into per-request
latency decompositions: router queue vs inbox-poll lag vs replica
queue vs prefill vs decode (vs residual), per generation — a
breakdown whose parts sum to the measured end-to-end latency
(tests/test_fleet_obs.py).

Pure stdlib; every FleetTracer method is a no-op when unconfigured.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tensorflow_distributed_tpu.utils.atomicio import atomic_write_json
from tensorflow_distributed_tpu.observe.trace import (
    ChromeTracer, load_trace, unbalanced_async)

_CAT = "fleet"

#: gen_rid = rid * _GEN_BASE + dispatch_ordinal (fleet/router.py).
_GEN_BASE = 1024


def gen_to_rid(gen_rid: int) -> int:
    """The client rid a wire (generation) id belongs to."""
    return int(gen_rid) // _GEN_BASE


class FleetTracer:
    """Router-side span/instant/counter recorder (cat ``fleet``)."""

    def __init__(self, path: str = "", enabled: bool = True,
                 clock=time.perf_counter, max_events: int = 200_000):
        self.tracer = ChromeTracer(path, pid=0, enabled=enabled,
                                   process_name="tfd-router",
                                   clock=clock, max_events=max_events)
        self.enabled = self.tracer.enabled
        self._queued: set = set()      # rids with an open client_queue
        self._dispatch: Dict[int, int] = {}  # rid -> open gen_rid

    # -- request lifecycle (router) ---------------------------------------

    def request_queued(self, rid: int, slo: str = "standard",
                       prompt_len: int = 0) -> None:
        if not self.enabled:
            return
        self.tracer.async_begin("request", rid, cat=_CAT, slo=slo,
                                prompt_len=prompt_len)
        self.tracer.async_begin("client_queue", rid, cat=_CAT)
        self._queued.add(int(rid))

    def dispatch(self, rid: int, gen_rid: int, replica: str,
                 retry: int = 0) -> None:
        """A generation leaves for a replica: close the client-queue
        span, open the ``dispatch`` span under the WIRE id (the same
        id the replica's own trace uses for this leg)."""
        if not self.enabled:
            return
        if int(rid) in self._queued:
            self.tracer.async_end("client_queue", rid, cat=_CAT)
            self._queued.discard(int(rid))
        self.tracer.async_begin("dispatch", gen_rid, cat=_CAT,
                                rid=int(rid), replica=replica,
                                retry=int(retry))
        self._dispatch[int(rid)] = int(gen_rid)

    def first_token(self, rid: int, gen_rid: int,
                    replica: str = "") -> None:
        if not self.enabled:
            return
        self.tracer.instant("first_token", cat=_CAT, rid=int(rid),
                            gen=int(gen_rid), replica=replica)

    def leg_failed(self, rid: int, gen_rid: int, replica: str,
                   why: str) -> None:
        """A dispatched generation died under the request (replica
        death/quarantine evacuation or a dispatch timeout): close its
        dispatch span, drop the ``redispatch`` instant the stitcher
        uses to close the dead replica's spans, and reopen the
        client-queue span — the request is back at the router."""
        if not self.enabled:
            return
        if self._dispatch.get(int(rid)) == int(gen_rid):
            del self._dispatch[int(rid)]
            self.tracer.async_end("dispatch", gen_rid, cat=_CAT,
                                  why=why, failed=True)
        self.tracer.instant("redispatch", cat=_CAT, rid=int(rid),
                            gen=int(gen_rid), replica=replica, why=why)
        if int(rid) not in self._queued:
            self.tracer.async_begin("client_queue", rid, cat=_CAT,
                                    why=why)
            self._queued.add(int(rid))

    def request_done(self, rid: int, finish: str, tokens: int = 0,
                     ttft_ms: float = 0.0, retries: int = 0) -> None:
        if not self.enabled:
            return
        gen = self._dispatch.pop(int(rid), None)
        if gen is not None:
            self.tracer.async_end("dispatch", gen, cat=_CAT,
                                  finish=finish)
        if int(rid) in self._queued:
            self.tracer.async_end("client_queue", rid, cat=_CAT)
            self._queued.discard(int(rid))
        self.tracer.async_end("request", rid, cat=_CAT, finish=finish,
                              tokens=int(tokens),
                              ttft_ms=round(float(ttft_ms), 3),
                              retries=int(retries))

    def shed(self, rid: int, reason: str) -> None:
        if not self.enabled:
            return
        self.tracer.instant("shed", cat=_CAT, rid=int(rid),
                            reason=reason)
        self.request_done(rid, finish="shed:" + reason)

    # -- fleet health instants + counters ---------------------------------

    def replica_event(self, name: str, replica: str,
                      **args: Any) -> None:
        """quarantine / rejoin / replica_death / replica_restart —
        flushed immediately: these are the rare, precious markers a
        router that dies next must still leave on disk."""
        if not self.enabled:
            return
        self.tracer.instant(name, cat=_CAT, replica=replica, **args)
        self.tracer.flush()

    def counters(self, **values: float) -> None:
        if not self.enabled:
            return
        for name, value in values.items():
            self.tracer.counter(name, **{name: value})

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        if self.enabled:
            for gen in list(self._dispatch.values()):
                self.tracer.async_end("dispatch", gen, cat=_CAT,
                                      finish="open_at_close")
            self._dispatch.clear()
            for rid in list(self._queued):
                self.tracer.async_end("client_queue", rid, cat=_CAT)
            self._queued.clear()
            for ev in unbalanced_async(self.tracer._events):
                if ev.get("ph") == "b":
                    self.tracer.async_end(ev["name"], ev.get("id"),
                                          cat=ev.get("cat", _CAT),
                                          finish="open_at_close")
        self.tracer.close()


# -- clock-offset estimation ----------------------------------------------


def estimate_offset(samples: Sequence[Tuple[float, float]]
                    ) -> float:
    """Per-replica clock skew from snapshot ``(wall_ts, mtime)``
    pairs: each pair is one observation of ``mtime - wall_ts`` (the
    replica stamped its clock into the payload; the filesystem stamped
    the router's frame onto the file). The median shrugs off the odd
    pair where the router polled a snapshot long after it was written
    — write and stamp happen in the same rename, so the per-sample
    noise is write latency, not poll latency."""
    if not samples:
        return 0.0
    deltas = sorted(float(m) - float(w) for w, m in samples)
    n = len(deltas)
    mid = n // 2
    if n % 2:
        return deltas[mid]
    return 0.5 * (deltas[mid - 1] + deltas[mid])


def _first_clock_sync(events: List[Dict[str, Any]]) -> Optional[float]:
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "clock_sync":
            try:
                return float(ev["args"]["wall_ts"])
            except (KeyError, TypeError, ValueError):
                return None
    return None


# -- the stitcher ---------------------------------------------------------


def stitch(router_path: str,
           replicas: Sequence[Tuple[str, str, float]],
           out_path: str) -> Dict[str, Any]:
    """Merge the router trace and every replica trace into one
    balanced timeline at ``out_path``.

    ``replicas`` is ``(name, trace_path, offset_s)`` per source —
    ``offset_s`` from :func:`estimate_offset` (0.0 when no snapshot
    pair was ever observed, e.g. a replica killed before its first
    export). Returns the merge stats:
    ``sources``/``skipped`` (torn or missing files), ``events``,
    ``closed_at_death`` (dead-leg spans the stitcher closed), and
    ``balanced``.
    """
    sources: List[Tuple[str, List[Dict[str, Any]], float]] = []
    skipped: List[str] = []

    def _load(name: str, path: str, offset_s: float) -> None:
        try:
            events = load_trace(path)
        except (OSError, ValueError, KeyError):
            # Torn mid-rename by a SIGKILL, or never written: the
            # merge must not sink with it.
            skipped.append(name)
            return
        if not isinstance(events, list) or not events:
            skipped.append(name)
            return
        sources.append((name, events, float(offset_s)))

    _load("router", router_path, 0.0)
    for name, path, offset_s in replicas:
        _load(name, path, offset_s)
    if not sources:
        raise ValueError(
            f"fleet stitch: no readable trace among router "
            f"{router_path!r} + {len(replicas)} replicas")

    # Absolute (router-frame wall) start per source: clock_sync anchor
    # + skew offset. A source with no anchor (pre-PR trace) pins to
    # the earliest anchored source so its events still render.
    anchored: List[Tuple[str, List[Dict[str, Any]], Optional[float]]] = []
    for name, events, offset_s in sources:
        anchor = _first_clock_sync(events)
        start = None if anchor is None else anchor + offset_s
        anchored.append((name, events, start))
    known = [s for _, _, s in anchored if s is not None]
    t0 = min(known) if known else 0.0

    merged: List[Dict[str, Any]] = []
    redispatch_ts: Dict[int, float] = {}   # gen_rid -> instant ts (merged)
    request_end: Dict[int, float] = {}     # rid -> router request "e" ts
    source_max: Dict[int, float] = {}      # pid -> max shifted ts
    for pid, (name, events, start) in enumerate(anchored):
        shift_us = 0.0 if start is None else (start - t0) * 1e6
        merged.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"fleet:{name}"}})
        for ev in events:
            ev = dict(ev)
            ev["pid"] = pid
            if ev.get("ph") == "M":
                if ev.get("name") == "process_name":
                    continue   # replaced by the fleet:name row above
                merged.append(ev)
                continue
            ts = float(ev.get("ts", 0.0)) + shift_us
            ev["ts"] = round(ts, 3)
            end = ts + float(ev.get("dur", 0.0))
            source_max[pid] = max(source_max.get(pid, 0.0), end)
            if (pid == 0 and ev.get("ph") == "i"
                    and ev.get("name") in ("redispatch", "retry")):
                gen = ev.get("args", {}).get("gen")
                if gen is not None:
                    redispatch_ts[int(gen)] = ts
            if (pid == 0 and ev.get("ph") == "e"
                    and ev.get("name") == "request"):
                try:
                    request_end[int(ev.get("id"))] = ts
                except (TypeError, ValueError):
                    pass
            merged.append(ev)
    for name in skipped:
        merged.append({
            "ph": "i", "name": f"trace_skipped:{name}", "cat": _CAT,
            "pid": 0, "tid": 0, "s": "p",
            "ts": round(max(source_max.values(), default=0.0), 3)})

    # Dead legs: a replica SIGKILLed mid-request leaves "b" spans with
    # no "e". Close each at the router's redispatch/retry instant for
    # its generation — the fleet-level moment that leg ended — falling
    # back to the router-side request end (shed with no re-dispatch),
    # then to the source's own last event.
    closed = 0
    for ev in unbalanced_async(merged):
        if ev.get("ph") != "b":
            continue
        pid = ev.get("pid", 0)
        end_ts = source_max.get(pid, float(ev.get("ts", 0.0)))
        try:
            wire = int(ev.get("id"))
        except (TypeError, ValueError):
            wire = None
        if wire is not None and pid != 0:
            if wire in redispatch_ts:
                end_ts = redispatch_ts[wire]
            elif gen_to_rid(wire) in request_end:
                end_ts = request_end[gen_to_rid(wire)]
        end_ts = max(end_ts, float(ev.get("ts", 0.0)))
        merged.append({
            "ph": "e", "name": ev["name"], "cat": ev.get("cat"),
            "pid": pid, "tid": 0, "id": ev.get("id"),
            "ts": round(end_ts, 3),
            "args": {"process_death": True}})
        closed += 1

    merged.sort(key=lambda e: (e.get("ph") != "M",
                               float(e.get("ts", 0.0))))
    atomic_write_json(out_path, {"traceEvents": merged,
                                 "displayTimeUnit": "ms"})
    return {
        "sources": len(sources),
        "skipped": len(skipped),
        "events": len(merged),
        "closed_at_death": closed,
        "balanced": not unbalanced_async(merged),
    }


# -- latency decomposition ------------------------------------------------


def _span_index(events: List[Dict[str, Any]]
                ) -> Dict[Tuple[str, str, str], List[Tuple[float, float]]]:
    """(cat, name, id) -> [(begin_ts, end_ts)] intervals, pairing
    b/e stack-wise per key (the merged file is balanced)."""
    open_b: Dict[Tuple[str, str, str], List[float]] = {}
    out: Dict[Tuple[str, str, str], List[Tuple[float, float]]] = {}
    for ev in sorted(events, key=lambda e: float(e.get("ts", 0.0))):
        ph = ev.get("ph")
        if ph not in ("b", "e"):
            continue
        key = (str(ev.get("cat")), str(ev.get("name")),
               str(ev.get("id")))
        ts = float(ev.get("ts", 0.0))
        if ph == "b":
            open_b.setdefault(key, []).append(ts)
        elif open_b.get(key):
            out.setdefault(key, []).append((open_b[key].pop(), ts))
    return out


def decompose(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-request latency decomposition from a stitched timeline.

    For every router ``request`` span: ``e2e_ms`` (submit -> done) and
    its components — ``router_queue_ms`` (client_queue spans, retries
    included), and per generation the replica-side ``inbox_lag_ms``
    (router dispatch begin -> replica request begin: dispatch-file
    write + feed poll), ``replica_queue_ms``, ``prefill_ms``,
    ``decode_ms``, and ``absorb_ms`` (replica request end -> router
    dispatch close: the journal tail-poll lag before the router SEES
    the finished tokens — the return half of the file control plane,
    mirror of ``inbox_lag_ms`` on the way in) — plus ``residual_ms``
    (e2e minus all components: clock-offset error, scheduler-loop
    gaps, shed wait).
    """
    idx = _span_index(events)
    out: List[Dict[str, Any]] = []
    dispatches: Dict[int, List[Tuple[int, float, float]]] = {}
    for (cat, name, sid), spans in idx.items():
        if cat == _CAT and name == "dispatch":
            try:
                gen = int(sid)
            except ValueError:
                continue
            for b, e in spans:
                dispatches.setdefault(gen_to_rid(gen), []).append(
                    (gen, b, e))
    for (cat, name, sid), spans in sorted(idx.items()):
        if cat != _CAT or name != "request":
            continue
        try:
            rid = int(sid)
        except ValueError:
            continue
        b, e = spans[0]
        e2e_ms = (e - b) / 1e3
        queue_ms = sum(
            (qe - qb) / 1e3
            for qb, qe in idx.get((_CAT, "client_queue", sid), []))
        inbox = rq = pf = dec = absorb = 0.0
        gens = []
        for gen, db, de in sorted(dispatches.get(rid, [])):
            gens.append(gen)
            gid = str(gen)
            rep_req = idx.get(("serve", "request", gid), [])
            if rep_req:
                inbox += max(0.0, (rep_req[0][0] - db) / 1e3)
                absorb += max(0.0, (de - rep_req[-1][1]) / 1e3)
            for comp, acc in (("queue", "rq"), ("prefill", "pf"),
                              ("decode", "dec")):
                dur = sum((ce - cb) / 1e3 for cb, ce
                          in idx.get(("serve", comp, gid), []))
                if acc == "rq":
                    rq += dur
                elif acc == "pf":
                    pf += dur
                else:
                    dec += dur
        parts = queue_ms + inbox + rq + pf + dec + absorb
        out.append({
            "rid": rid, "gens": gens,
            "e2e_ms": round(e2e_ms, 3),
            "router_queue_ms": round(queue_ms, 3),
            "inbox_lag_ms": round(inbox, 3),
            "replica_queue_ms": round(rq, 3),
            "prefill_ms": round(pf, 3),
            "decode_ms": round(dec, 3),
            "absorb_ms": round(absorb, 3),
            "residual_ms": round(e2e_ms - parts, 3),
        })
    return out
