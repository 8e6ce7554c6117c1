"""Fleet serving: router policy on fake replicas, controller
lifecycle on fake processes, the replica-side inbox feed, and one slow
supervised e2e (2-replica real fleet, SIGKILL mid-stream, zero lost).

The fast tier is jax-free by design: fleet/router.py and
fleet/controller.py are host policy driven by an explicit ``now``, so
every scenario (failover token identity, quarantine/rejoin, retry
budgets, shedding order, drain-before-stop, rolling swaps) runs on
fakes with a hand-advanced clock.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from tensorflow_distributed_tpu.fleet.controller import (
    ControllerConfig, FleetController, latest_ckpt_step)
from tensorflow_distributed_tpu.fleet.replica import (
    InboxFeed, ReplicaHandle, append_line)
from tensorflow_distributed_tpu.fleet.router import (
    Router, RouterConfig, SLO_CLASSES)


# --- the deterministic fake replica --------------------------------------

def _next_tok(context):
    """The fake "greedy decode": next token is a pure function of the
    FULL context — so a continuation (prompt + tokens so far) on a
    different replica produces exactly the tokens the dead one would
    have, like real greedy decode with shared weights."""
    return (sum(context) * 31 + 7) % 97


def _stream(prompt, n):
    ctx = list(prompt)
    out = []
    for _ in range(n):
        t = _next_tok(ctx)
        out.append(t)
        ctx.append(t)
    return out


class FakeReplica:
    """In-memory replica with the ReplicaHandle surface the router and
    controller read/write (name/epoch/send/read_snapshot/
    read_journal). ``tick()`` serves ``tok_per_tick`` tokens per live
    request and bumps the snapshot seq (unless frozen — the
    stale-snapshot drill)."""

    def __init__(self, name, tok_per_tick=2, max_len=4096):
        self.name = name
        self.epoch = 0
        self.tok_per_tick = tok_per_tick
        self.max_len = max_len
        self.live = {}        # rid -> {"ctx": [...], "left": n}
        self.journal = {}     # rid -> replay-shaped entry
        self.sent = []        # every inbox line, in order
        self.seq = 0
        self.frozen = False
        self.anomaly = {"anomalies": 0, "active": [],
                        "by_detector": {}}
        self.ckpt_step = 2
        self.queue_depth = 0  # extra load the snapshot reports
        self.ttft_p95 = {}    # class -> ms, for the score tiebreak

    # -- handle surface --------------------------------------------------

    def send(self, obj):
        self.sent.append(obj)
        if "cmd" in obj:
            if obj["cmd"] == "cancel":
                self.live.pop(obj.get("rid"), None)
            elif obj["cmd"] == "swap":
                self.ckpt_step = obj.get("_to", self.ckpt_step)
            return
        rid = obj["rid"]
        self.journal[rid] = {"req": None, "tokens": [], "done": False,
                             "reject": False, "last_s": 0.0}
        self.live[rid] = {"ctx": [int(t) for t in obj["prompt"]],
                          "left": int(obj["max_new"])}

    def read_snapshot(self):
        if self.seq == 0:
            return None
        snap = {"seq": self.seq, "wall_ts": 0.0, "pid": 1234,
                "queue_depth": self.queue_depth,
                "requests_live": len(self.live),
                "anomaly": dict(self.anomaly),
                "ckpt_step": self.ckpt_step,
                "num_slots": 2, "max_len": self.max_len}
        for cls, ms in self.ttft_p95.items():
            snap[f"ttft_ms_p95_{cls}"] = ms
        return snap

    def read_journal(self):
        return {rid: dict(e, tokens=list(e["tokens"]))
                for rid, e in self.journal.items()}

    # -- simulation ------------------------------------------------------

    def tick(self):
        for rid in list(self.live):
            st = self.live[rid]
            for _ in range(min(self.tok_per_tick, st["left"])):
                t = _next_tok(st["ctx"])
                st["ctx"].append(t)
                st["left"] -= 1
                self.journal[rid]["tokens"].append(t)
            if st["left"] == 0:
                self.journal[rid]["done"] = True
                del self.live[rid]
        if not self.frozen:
            self.seq += 1


def _gen(rid, n=1):
    """The wire/journal id of rid's n-th dispatch (router gen rids)."""
    return rid * 1024 + n


def _req(rid, arrival=0.0, slo="standard", max_new=6, plen=3):
    return {"rid": rid, "prompt": [rid + 1] * plen,
            "max_new": max_new, "eos": -1, "arrival_s": arrival,
            "slo": slo}


def _router(reps, emit=None, **cfg):
    r = Router(reps, RouterConfig(**cfg), emit=emit)
    r.begin(0.0)
    return r


def _spin(router, reps, t0, t1, dt=0.1):
    """Advance sim time: tick every replica, step the router."""
    t = t0
    while t < t1:
        for rep in reps:
            rep.tick()
        t = round(t + dt, 6)
        router.step(t)
    return t


def test_slo_class_parity_with_scheduler():
    from tensorflow_distributed_tpu.serve.scheduler import (
        SLO_CLASSES as sched_classes)
    assert tuple(SLO_CLASSES) == tuple(sched_classes)


def test_dispatch_least_loaded():
    a, b = FakeReplica("a"), FakeReplica("b")
    a.queue_depth = 3          # a is busier
    a.tick(), b.tick()         # first snapshots
    router = _router([a, b])
    router.submit([_req(0)])
    router.step(0.1)
    assert not b.live or _gen(0) in b.live
    assert [o for o in b.sent if "rid" in o]
    assert not [o for o in a.sent if "rid" in o]


def test_dispatch_class_p95_tiebreak():
    # Equal load; replica b has been slow for "high" — a wins.
    a, b = FakeReplica("a"), FakeReplica("b")
    a.ttft_p95 = {"high": 10.0}
    b.ttft_p95 = {"high": 500.0}
    a.tick(), b.tick()
    router = _router([a, b])
    router.submit([_req(0, slo="high")])
    router.step(0.1)
    assert [o for o in a.sent if "rid" in o]
    assert not [o for o in b.sent if "rid" in o]


def test_failover_redispatch_token_identity():
    a, b = FakeReplica("a", tok_per_tick=1), FakeReplica(
        "b", tok_per_tick=1)
    events = []
    router = _router([a, b], emit=lambda e, **f: events.append((e, f)))
    router.submit([_req(0, max_new=8)])
    a.tick(), b.tick()
    router.step(0.1)
    owner = a if a.live else b
    # A few tokens land, then the owner dies mid-request.
    t = _spin(router, [a, b], 0.1, 0.4)
    assert _gen(0) in owner.live
    served = len(owner.journal[_gen(0)]["tokens"])
    assert 0 < served < 8
    owner.frozen = True        # a dead process stops everything
    owner.live.clear()
    router.mark_dead(owner.name, t)
    other = b if owner is a else a
    t = _spin(router, [other], t, 2.0)
    tr = router.tracks[0]
    assert tr.state == "done"
    assert tr.retries == 1 and tr.redispatched
    # The assembled stream is exactly the uninterrupted one.
    assert tr.tokens == _stream([1, 1, 1], 8)
    # The continuation carried prompt + served tokens.
    cont = [o for o in other.sent if "rid" in o][-1]
    assert cont["prompt"] == [1, 1, 1] + tr.tokens[:served]
    assert cont["max_new"] == 8 - served
    kinds = [e for e, _ in events]
    assert "fleet_dispatch" in kinds
    assert ("fleet_replica",) and any(
        f.get("state") == "dead" for e, f in events
        if e == "fleet_replica")


def test_quarantine_on_anomaly_evacuates_and_rejoins():
    a, b = FakeReplica("a", tok_per_tick=1), FakeReplica(
        "b", tok_per_tick=1)
    events = []
    router = _router([a, b],
                     emit=lambda e, **f: events.append((e, f)),
                     anomaly_cooldown_s=60.0)
    router.submit([_req(0, max_new=12)])
    a.tick(), b.tick()
    router.step(0.1)
    owner = a if a.live else b
    other = b if owner is a else a
    t = _spin(router, [a, b], 0.1, 0.4)
    # The engine flags a slot: anomaly state rides the snapshot.
    owner.anomaly = {"anomalies": 1, "active": ["slot_nonfinite"],
                     "by_detector": {"slot_nonfinite": 1}}
    owner.tick()
    router.step(t + 0.1)
    assert router.reps[owner.name].health == "quarantined"
    # In-flight moved to the peer as a continuation; the old owner
    # got a cancel.
    assert any(o.get("cmd") == "cancel" for o in owner.sent)
    t = _spin(router, [a, b], t + 0.1, 1.2)
    assert (_gen(0, 2) in other.live
            or other.journal.get(_gen(0, 2), {}).get("done"))
    # New work never lands on the quarantined replica...
    router.submit([_req(1, arrival=0.0)])
    router.step(t + 0.1)
    assert _gen(1) not in owner.live
    # ...until the anomaly clears (hub horizon passed) — then REJOIN,
    # and the replica takes work again (no permanent capacity loss).
    owner.anomaly = {"anomalies": 1, "active": [],
                     "by_detector": {"slot_nonfinite": 1}}
    owner.tick()
    router.step(t + 0.2)
    assert router.reps[owner.name].health == "up"
    assert any(f.get("state") == "rejoined" for e, f in events
               if e == "fleet_replica")


def test_anomaly_cooldown_rejoin_does_not_oscillate():
    a, b = FakeReplica("a", tok_per_tick=1), FakeReplica("b")
    router = _router([a, b], anomaly_cooldown_s=1.0)
    router.submit([_req(0)])
    for rep in (a, b):
        rep.tick()
    router.step(0.1)
    a.anomaly = {"anomalies": 2, "active": ["slot_nonfinite"],
                 "by_detector": {"slot_nonfinite": 2}}
    t = _spin(router, [a, b], 0.1, 0.5)
    assert router.reps["a"].health == "quarantined"
    # The active entry never clears (idle replica, frozen step
    # clock) — the cooldown rejoins anyway...
    t = _spin(router, [a, b], t, t + 1.5)
    assert router.reps["a"].health == "up"
    # ...and the STALE active entry must not re-quarantine (count
    # unchanged). A NEW firing (count grows) must.
    t = _spin(router, [a, b], t, t + 0.5)
    assert router.reps["a"].health == "up"
    a.anomaly = {"anomalies": 3, "active": ["slot_nonfinite"],
                 "by_detector": {"slot_nonfinite": 3}}
    a.tick()
    router.step(t + 0.1)
    assert router.reps["a"].health == "quarantined"


def test_quarantine_on_stale_snapshot_and_rejoin():
    a, b = FakeReplica("a", tok_per_tick=1), FakeReplica(
        "b", tok_per_tick=1)
    router = _router([a, b], stale_s=0.5)
    router.submit([_req(0, max_new=20)])
    a.tick(), b.tick()
    router.step(0.1)
    owner = a if a.live else b
    other = b if owner is a else a
    t = _spin(router, [a, b], 0.1, 0.3)
    owner.frozen = True        # exports stop; the process still runs
    t = _spin(router, [a, b], t, t + 1.0)
    assert router.reps[owner.name].health == "quarantined"
    assert router.reps[owner.name].reason == "stale_snapshot"
    # In-flight re-dispatched; peer finishes the stream identically.
    t = _spin(router, [a, b], t, t + 3.0)
    assert router.tracks[0].state == "done"
    assert router.tracks[0].tokens == _stream([1, 1, 1], 20)
    assert other.journal[_gen(0, 2)]["done"]
    # Exports resume -> seq advances -> rejoin.
    owner.frozen = False
    owner.tick()
    router.step(t + 0.1)
    assert router.reps[owner.name].health == "up"


def test_retry_budget_exhaustion_sheds_loudly():
    # One replica that accepts work but never serves a token.
    a = FakeReplica("a", tok_per_tick=0)
    events = []
    router = _router([a], emit=lambda e, **f: events.append((e, f)),
                     dispatch_timeout_s=0.5, retry_budget=2,
                     backoff_base_s=0.1, backoff_max_s=0.2)
    router.submit([_req(0)])
    t = _spin(router, [a], 0.0, 5.0)
    tr = router.tracks[0]
    assert tr.state == "shed" and tr.shed_reason == "retry_budget"
    assert tr.retries == 3     # budget 2 exhausted on the 3rd
    assert not router.active()     # shed, never hang
    assert any(e == "fleet_shed" and f["reason"] == "retry_budget"
               for e, f in events)


def test_saturation_shed_order_lowest_class_first():
    a = FakeReplica("a")
    a.queue_depth = 99         # saturated forever
    a.tick()
    events = []
    router = _router([a], emit=lambda e, **f: events.append((e, f)),
                     queue_high=8, shed_wait_s=1.0)
    router.submit([_req(0, slo="high"), _req(1, slo="batch"),
                   _req(2, slo="standard")])
    t = 0.0
    while router.active() and t < 10.0:
        a.tick()
        t = round(t + 0.5, 6)
        router.step(t)
    sheds = [f for e, f in events if e == "fleet_shed"]
    assert [s["slo"] for s in sheds] == ["batch", "standard", "high"]
    assert all(s["reason"] == "saturated" for s in sheds)
    assert not router.active()


def test_dispatch_timeout_retries_with_capped_backoff():
    a = FakeReplica("a", tok_per_tick=0)   # wedged on the request
    b = FakeReplica("b", tok_per_tick=2)
    a.ttft_p95 = {}
    router = _router([a, b], dispatch_timeout_s=0.5,
                     backoff_base_s=0.4, backoff_max_s=1.0,
                     retry_budget=5)
    router.submit([_req(0, max_new=4)])
    a.tick(), b.tick()
    a.queue_depth = 0
    router.step(0.05)
    owner = a if a.live else b
    if owner is b:             # force the wedged replica as owner
        b.live.clear()
        router.reps["b"].inflight.clear()
        pytest.skip("dispatch landed on the healthy replica")
    # Past the timeout: cancelled on a, backoff scheduled.
    router.step(0.7)
    tr = router.tracks[0]
    assert tr.state == "waiting" and tr.retries == 1
    assert any(o.get("cmd") == "cancel" for o in a.sent)
    assert tr.next_t == pytest.approx(0.7 + 0.4)
    # Not re-dispatched before the backoff deadline...
    router.step(0.9)
    assert tr.state == "waiting"
    # ...after it, anywhere healthy (including b).
    _spin(router, [a, b], 1.2, 3.0)
    assert tr.state == "done"
    assert tr.tokens == _stream([1, 1, 1], 4)


def test_reject_in_journal_sheds():
    a = FakeReplica("a")
    a.tick()
    router = _router([a])
    router.submit([_req(0)])
    router.step(0.1)
    a.journal[_gen(0)]["reject"] = True
    a.live.pop(_gen(0), None)
    a.tick()
    router.step(0.2)
    assert router.tracks[0].state == "shed"
    assert router.tracks[0].shed_reason == "rejected"


def test_summary_shape_and_recovery_population():
    a, b = FakeReplica("a", tok_per_tick=1), FakeReplica(
        "b", tok_per_tick=1)
    router = _router([a, b])
    router.submit([_req(i, arrival=0.0, max_new=4)
                   for i in range(4)])
    a.tick(), b.tick()
    router.step(0.1)
    owner = a if a.live else b
    router.mark_dead(owner.name, 0.3)
    other = b if owner is a else a
    _spin(router, [other], 0.3, 3.0)
    s = router.summary()
    assert s["requests"] == 4 and s["requests_lost"] == 0
    assert s["requests_done"] == 4
    assert s["deaths"] == 1
    assert s["redispatches"] >= 1
    hist = s["dispatch_retry_hist"]
    assert sum(hist.values()) == 4 and "1" in hist
    assert s["recovery_requests"] >= 1
    assert "ttft_ms_p99_recovery" in s
    assert s["ttft_ms_p50"] >= 0


def test_session_turns_stick_to_one_replica_and_repin_on_death():
    a, b = FakeReplica("a", tok_per_tick=2), FakeReplica(
        "b", tok_per_tick=2)
    router = _router([a, b])
    router.submit([
        dict(_req(0, max_new=4), session="s1"),
        dict(_req(1, arrival=0.0, max_new=4), session="s1"),
        dict(_req(2, arrival=0.0, max_new=4)),   # fills the peer
    ])
    a.tick(), b.tick()
    _spin(router, [a, b], 0.0, 2.0)
    owner = {o.get("session"): n for n, rep in (("a", a), ("b", b))
             for o in rep.sent if "rid" in o and o.get("session")}
    # Both turns of s1 landed on the SAME replica despite
    # least-loaded balancing wanting to spread them.
    s1_owners = {n for n, rep in (("a", a), ("b", b))
                 for o in rep.sent
                 if "rid" in o and o.get("session") == "s1"}
    assert len(s1_owners) == 1
    assert owner["s1"] in s1_owners
    # A later turn re-pins when the owner dies.
    dead = a if "a" in s1_owners else b
    alive = b if dead is a else a
    router.mark_dead(dead.name, 2.0)
    router.submit([dict(_req(3, arrival=0.0, max_new=4),
                        session="s1")])
    _spin(router, [alive], 2.0, 4.0)
    assert any(o.get("session") == "s1" for o in alive.sent
               if "rid" in o)
    assert router.tracks[3].state == "done"


# --- replica-side: inbox feed + handle -----------------------------------

def test_inbox_feed_requests_commands_and_torn_tail(tmp_path):
    path = str(tmp_path / "inbox.jsonl")
    feed = InboxFeed(path, poll_s=0.0)
    assert feed.poll() == []                # absent file = quiet
    append_line(path, {"rid": 7, "prompt": [1, 2], "max_new": 3,
                       "slo": "high"})
    append_line(path, {"cmd": "drain"})
    # A torn tail (writer mid-append) stays unconsumed...
    with open(path, "a") as f:
        f.write('{"rid": 8, "prompt": [3')
    items = feed.poll()
    # ORDERED: the request line precedes the drain command.
    assert [getattr(i, "rid", None) for i in items] == [7, None]
    assert items[0].slo == "high" and items[0].max_new_tokens == 3
    assert items[1] == {"cmd": "drain"}
    # ...and is delivered once completed.
    with open(path, "a") as f:
        f.write(', 4], "max_new": 2}\n')
    items = feed.poll()
    assert [i.rid for i in items] == [8]
    assert list(items[0].prompt) == [3, 4]
    # Unknown SLO coerces; missing rid raises.
    append_line(path, {"rid": 9, "prompt": [1], "slo": "platinum"})
    assert feed.poll()[0].slo == "standard"
    append_line(path, {"prompt": [1]})
    with pytest.raises(ValueError, match="rid"):
        feed.poll()
    append_line(path, {"cmd": "explode"})
    with pytest.raises(ValueError, match="unknown command"):
        feed.poll()


def test_replica_handle_incremental_journal_tail(tmp_path):
    h = ReplicaHandle("r0", str(tmp_path / "r0"))
    h.begin_epoch(0)
    with open(h.journal, "w") as f:
        f.write(json.dumps({"e": "admit", "rid": 1, "prompt": [1],
                            "max_new": 4, "eos": -1}) + "\n")
        f.write(json.dumps({"e": "tok", "rid": 1, "t": 5,
                            "s": 0.1}) + "\n")
    assert h.read_journal()[1]["tokens"] == [5]
    # New lines accumulate; a torn tail waits for completion.
    with open(h.journal, "a") as f:
        f.write(json.dumps({"e": "tok", "rid": 1, "t": 6,
                            "s": 0.2}) + "\n")
        f.write('{"e": "tok", "rid": 1, "t":')
    assert h.read_journal()[1]["tokens"] == [5, 6]
    with open(h.journal, "a") as f:
        f.write(' 7, "s": 0.3}\n')
        f.write(json.dumps({"e": "done", "rid": 1}) + "\n")
    ent = h.read_journal()[1]
    assert ent["tokens"] == [5, 6, 7] and ent["done"]
    # The incremental accumulator matches a full replay, and an epoch
    # rollover resets it.
    from tensorflow_distributed_tpu.serve import journal as jmod
    assert h.read_journal()[1]["tokens"] == \
        jmod.replay(h.journal)[1]["tokens"]
    h.begin_epoch(1)
    assert h.read_journal() == {}


def test_replica_handle_epochs_and_tolerant_readers(tmp_path):
    h = ReplicaHandle("r0", str(tmp_path / "r0"))
    h.begin_epoch(0)
    assert "/e0/" in h.inbox
    assert h.read_snapshot() is None        # absent
    with open(h.snapshot, "w") as f:
        f.write("{torn")
    assert h.read_snapshot() is None        # torn
    with open(h.snapshot, "w") as f:
        json.dump({"seq": 3}, f)
    assert h.read_snapshot() == {"seq": 3}
    h.send({"rid": 1, "prompt": [1], "max_new": 1})
    assert os.path.exists(h.inbox)
    old_journal = h.journal
    with open(old_journal, "w") as f:
        f.write(json.dumps({"e": "admit", "rid": 1, "prompt": [1],
                            "max_new": 4, "eos": -1}) + "\n")
        f.write(json.dumps({"e": "tok", "rid": 1, "t": 5,
                            "s": 0.1}) + "\n")
    assert h.read_journal()[1]["tokens"] == [5]
    h.begin_epoch(1)
    assert "/e1/" in h.inbox
    assert h.read_journal() == {}           # fresh epoch, fresh files
    assert h.read_journal(epoch=0)[1]["tokens"] == [5]


# --- controller ----------------------------------------------------------

class FakeProc:
    def __init__(self):
        self.rc = None
        self.signals = []

    def poll(self):
        return self.rc

    def send_signal(self, sig):
        self.signals.append(sig)

    def kill(self):
        self.signals.append(9)
        self.rc = -9


def _controller(tmp_path, n=2, ckpt_dir="", **cfg):
    handles = [ReplicaHandle(f"r{i}", str(tmp_path / f"r{i}"))
               for i in range(n)]
    procs = []

    def spawn(cmd):
        p = FakeProc()
        procs.append(p)
        return p

    deaths, restarts = [], []
    ctl = FleetController(
        handles, ["--mode", "serve"], ckpt_dir=ckpt_dir,
        cfg=ControllerConfig(backoff_base_s=0.5, backoff_max_s=2.0,
                             max_restarts=2, **cfg),
        spawn=spawn,
        on_death=lambda n_, t: deaths.append(n_),
        on_restart=lambda n_, t: restarts.append(n_))
    ctl.start(0.0)
    return ctl, handles, procs, deaths, restarts


def test_controller_restart_backoff_and_epoch_rotation(tmp_path):
    ctl, handles, procs, deaths, restarts = _controller(tmp_path)
    assert len(procs) == 2 and handles[0].epoch == 0
    procs[0].rc = -9                        # SIGKILL'd replica
    ctl.poll(1.0)
    assert deaths == ["r0"]
    ctl.poll(1.2)                           # inside backoff: no spawn
    assert len(procs) == 2
    ctl.poll(1.6)                           # past 0.5s backoff
    assert len(procs) == 3
    assert restarts == ["r0"]
    assert handles[0].epoch == 1            # fresh epoch directory
    assert os.path.isdir(handles[0].epoch_dir())
    # Second death: backoff doubles.
    procs[2].rc = 1
    ctl.poll(2.0)
    ctl.poll(2.5)
    assert len(procs) == 3
    ctl.poll(3.1)
    assert len(procs) == 4
    # Third death: budget (2) exhausted — stays down.
    procs[3].rc = 1
    ctl.poll(4.0)
    ctl.poll(99.0)
    assert len(procs) == 4
    assert ctl.members["r0"].gone


def test_controller_diverged_not_restarted(tmp_path):
    ctl, handles, procs, deaths, restarts = _controller(tmp_path)
    procs[1].rc = 2                         # SlotRetryExhausted
    ctl.poll(1.0)
    ctl.poll(50.0)
    assert len(procs) == 2 and ctl.members["r1"].gone
    assert deaths == ["r1"] and restarts == []


def test_controller_drain_before_stop(tmp_path):
    ctl, handles, procs, deaths, restarts = _controller(tmp_path)
    ctl.request_stop(5.0)
    for h in handles:
        with open(h.inbox) as f:
            lines = [json.loads(ln) for ln in f]
        assert {"cmd": "drain"} in lines
    # Replicas finish in-flight work and exit 0 by themselves: no
    # signal is ever sent.
    t = {"v": 0.0}

    def clock():
        t["v"] += 0.05
        if t["v"] > 1.0:
            for p in procs:
                p.rc = 0
        return t["v"]

    assert ctl.wait_stopped(clock=clock, sleep=lambda s: None)
    assert all(p.signals == [] for p in procs)
    # A drain exit during draining is not a death.
    ctl.poll(t["v"])
    assert deaths == []


def test_controller_drain_escalates_on_deadline(tmp_path):
    ctl, handles, procs, *_ = _controller(tmp_path,
                                          drain_timeout_s=1.0)
    ctl.request_stop(0.0)
    t = {"v": 0.0}

    def clock():
        t["v"] += 0.3
        return t["v"]

    assert not ctl.wait_stopped(clock=clock, sleep=lambda s: None)
    assert any(p.signals for p in procs)    # TERM (then KILL) sent


def _mk_step(ckpt_dir, step, marker="state.msgpack"):
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, marker), "w") as f:
        f.write("x")


def test_latest_ckpt_step_scanner_matches_checkpoint_layer(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt)
    assert latest_ckpt_step(ckpt) is None
    _mk_step(ckpt, 2)
    _mk_step(ckpt, 6, marker="ORBAX_COMMITTED")
    _mk_step(ckpt, 8, marker="unrelated.file")   # incomplete: no marker
    os.makedirs(os.path.join(ckpt, "step_00000010.tmp"))
    os.makedirs(os.path.join(ckpt, "quarantined_step_00000004"))
    with open(os.path.join(ckpt, "step_00000012"), "w") as f:
        f.write("a stray file")
    assert latest_ckpt_step(ckpt) == 6
    # Contract parity with the checkpoint layer's own scan.
    from tensorflow_distributed_tpu.train.checkpoint import (
        available_steps)
    assert available_steps(ckpt) == [2, 6]


def test_controller_rolling_swap_one_replica_at_a_time(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt)
    _mk_step(ckpt, 2)
    ctl, handles, procs, *_ = _controller(tmp_path, n=3,
                                          ckpt_dir=ckpt)
    # start() pinned the pre-existing step as already rolled.
    assert ctl.rolled_step == 2

    def snap(h, step):
        os.makedirs(h.epoch_dir(), exist_ok=True)
        with open(h.snapshot, "w") as f:
            json.dump({"seq": 1, "ckpt_step": step}, f)

    def swap_cmds(h):
        if not os.path.exists(h.inbox):
            return 0
        with open(h.inbox) as f:
            return sum(1 for ln in f
                       if json.loads(ln).get("cmd") == "swap")

    for h in handles:
        snap(h, 2)
    ctl.poll(1.0)
    assert all(swap_cmds(h) == 0 for h in handles)   # nothing new
    _mk_step(ckpt, 4)                               # trainer emitted
    ctl.poll(2.0)
    # ONE replica told to swap; the rest untouched (capacity >= N-1).
    assert [swap_cmds(h) for h in handles] == [1, 0, 0]
    ctl.poll(2.5)                                   # r0 not acked yet
    assert [swap_cmds(h) for h in handles] == [1, 0, 0]
    assert ctl.staleness_max == 2
    snap(handles[0], 4)                             # r0 acks
    ctl.poll(3.0)
    assert [swap_cmds(h) for h in handles] == [1, 1, 0]
    snap(handles[1], 4)
    ctl.poll(3.5)
    assert [swap_cmds(h) for h in handles] == [1, 1, 1]
    snap(handles[2], 4)
    ctl.poll(4.0)
    assert ctl.rolling_swaps == 1 and not ctl.swap_in_progress
    assert ctl.summary()["replica_swaps"] == {"r0": 1, "r1": 1,
                                              "r2": 1}
    # A dead replica is skipped (its restart restores the newest
    # checkpoint anyway) — the roll never stalls on it.
    procs[1].rc = -9
    ctl.poll(5.0)
    _mk_step(ckpt, 6)
    ctl.poll(5.1)
    snap(handles[0], 6)
    ctl.poll(5.2)
    ctl.poll(5.3)
    snap(handles[2], 6)
    ctl.poll(5.4)
    assert ctl.rolling_swaps == 2
    assert swap_cmds(handles[1]) == 1               # never re-told


def test_controller_swap_timeout_is_a_partial_roll(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt)
    _mk_step(ckpt, 2)
    ctl, handles, procs, *_ = _controller(
        tmp_path, n=2, ckpt_dir=ckpt, swap_timeout_s=1.0)
    for h in handles:
        os.makedirs(h.epoch_dir(), exist_ok=True)
        with open(h.snapshot, "w") as f:
            json.dump({"seq": 1, "ckpt_step": 2}, f)
    _mk_step(ckpt, 4)
    ctl.poll(1.0)          # swap sent to r0
    ctl.poll(2.5)          # past the 1s ack timeout: r0 skipped
    with open(handles[1].snapshot, "w") as f:
        json.dump({"seq": 2, "ckpt_step": 4}, f)
    ctl.poll(3.0)          # r1 acks; the roll completes
    # A rollout with a timed-out replica is NOT a completed rolling
    # swap (the swaps_ok gate must not pass on a fleet that never
    # converged) — it is counted separately.
    assert ctl.rolling_swaps == 0
    assert ctl.partial_rolls == 1
    assert ctl.swap_timeouts == 1
    s = ctl.summary()
    assert s["rolling_swaps"] == 0 and s["partial_rolls"] == 1


# --- scheduler feed integration (fake engine, jax-free) ------------------

class _ScriptedFeed:
    """poll() pops scripted ORDERED item batches (Request objects
    interleaved with command dicts — the InboxFeed contract)."""

    def __init__(self, batches):
        self.batches = list(batches)

    def poll(self):
        return self.batches.pop(0) if self.batches else []


def _sched_requests(rids, max_new=4):
    from tensorflow_distributed_tpu.serve.scheduler import Request
    return [Request(rid=r, prompt=np.asarray([r], np.int32),
                    max_new_tokens=max_new) for r in rids]


class _Recorder:
    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append((event, fields))


def _fake_engine():
    import tests.test_serve as ts
    return ts._FakeEngine(num_slots=2)


def test_scheduler_feed_drain_and_snapshot_liveness():
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler
    reg = _Recorder()
    feed = _ScriptedFeed([
        _sched_requests([1, 2]),
        [],
        _sched_requests([3]),
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(_fake_engine(), registry=reg, feed=feed)
    done = sched.run([])
    assert sorted(c.rid for c in done) == [1, 2, 3]
    assert sched.draining
    snap = sched.metrics_snapshot()
    # The liveness triplet + capacity facts (satellite: a poller can
    # tell a frozen file from a healthy idle replica).
    assert snap["seq"] >= 1 and snap["pid"] == os.getpid()
    assert snap["wall_ts"] > 0
    assert snap["num_slots"] == 2 and snap["max_len"] == 256
    assert "ckpt_step" not in snap          # no checkpoint armed
    snap2 = sched.metrics_snapshot()
    assert snap2["seq"] == snap["seq"] + 1  # monotonic


def test_scheduler_feed_rejects_unservable_into_journal(tmp_path):
    from tensorflow_distributed_tpu.serve import journal as jmod
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler
    reg = _Recorder()
    jpath = str(tmp_path / "j.jsonl")
    too_big = _sched_requests([9], max_new=500)     # cannot fit
    feed = _ScriptedFeed([
        too_big + _sched_requests([1]),
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(_fake_engine(), registry=reg, feed=feed,
                      journal=jmod.RequestJournal(jpath))
    done = sched.run([])
    assert [c.rid for c in done] == [1]
    assert jmod.replay(jpath)[9]["reject"]
    assert any(e == "serve_reject" and f["rid"] == 9
               for e, f in reg.events)


def test_scheduler_feed_redispatch_supersedes_stale_copy():
    # A stalled replica can read the original dispatch, its cancel,
    # AND the router's re-dispatched continuation in ONE poll batch —
    # the continuation must supersede the original (one admission,
    # one journal stream), never serve the rid twice.
    from tensorflow_distributed_tpu.serve.scheduler import (
        Request, Scheduler)
    reg = _Recorder()
    orig = _sched_requests([7], max_new=6)[0]
    cont = Request(rid=7, prompt=np.asarray([7, 700], np.int32),
                   max_new_tokens=5)
    feed = _ScriptedFeed([
        list(_sched_requests([1]))
        + [orig, {"cmd": "cancel", "rid": 7}, cont],
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(_fake_engine(), registry=reg, feed=feed)
    done = sched.run([])
    by_rid = {}
    for c in done:
        assert c.rid not in by_rid, "rid served twice"
        by_rid[c.rid] = c
    assert sorted(by_rid) == [1, 7]
    # The served copy is the CONTINUATION (its tighter budget).
    assert len(by_rid[7].tokens) == 5
    assert len([e for e, f in reg.events
                if e == "serve_request" and f["rid"] == 7]) == 1


def test_scheduler_feed_rejects_impossible_page_reservation():
    # A paged engine must journal-reject a dispatch whose reservation
    # can NEVER fit the pool (idle-engine admission would raise and
    # kill the replica — a replica never crashes on a bad dispatch).
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler

    class _PagedFake:
        # the paged engine's own rule, over this pool
        reservation_fits = PagedSlotEngine.reservation_fits

        def __init__(self, inner, capacity):
            self._inner = inner
            self.pool = type("P", (), {"capacity": capacity})()
            self.radix = None

        def pages_for(self, plen, max_new):
            return -(-(plen + max_new) // 4)       # page_size 4

        def __getattr__(self, name):
            return getattr(self._inner, name)

    reg = _Recorder()
    eng = _PagedFake(_fake_engine(), capacity=4)   # 3 usable pages
    feed = _ScriptedFeed([
        # 1 + 40 tokens -> 11 pages > 3 usable: impossible; rid 1
        # fits (3 usable pages hold its 2-page reservation).
        _sched_requests([9], max_new=40) + _sched_requests([1]),
        [{"cmd": "drain"}],
    ])
    done = Scheduler(eng, registry=reg, feed=feed).run([])
    assert [c.rid for c in done] == [1]
    assert any(e == "serve_reject" and f["rid"] == 9
               for e, f in reg.events)


def test_scheduler_feed_cancel_drops_live_without_completion():
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler
    reg = _Recorder()
    feed = _ScriptedFeed([
        _sched_requests([1, 2], max_new=50),
        [],
        [{"cmd": "cancel", "rid": 1}],
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(_fake_engine(), registry=reg, feed=feed)
    done = sched.run([])
    assert [c.rid for c in done] == [2]
    assert any(e == "serve_cancel" and f["rid"] == 1
               and f["where"] == "live" for e, f in reg.events)


def test_scheduler_feed_swap_updates_served_ckpt_step():
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler
    eng = _fake_engine()
    eng.swaps = 0

    def swap_params(p):
        eng.swaps += 1
    eng.swap_params = swap_params
    feed = _ScriptedFeed([
        _sched_requests([1]),
        [{"cmd": "swap"}],
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(eng, feed=feed, served_ckpt_step=2,
                      reload_fn=lambda: ({"w": 1}, 6))
    sched.run([])
    assert eng.swaps == 1
    assert sched.served_ckpt_step == 6
    assert sched.metrics_snapshot()["ckpt_step"] == 6


def test_scheduler_hold_export_freezes_snapshot_file(tmp_path):
    from tensorflow_distributed_tpu.serve.scheduler import Scheduler
    path = str(tmp_path / "snap.json")
    feed = _ScriptedFeed([
        _sched_requests([1], max_new=10),
        [{"cmd": "hold_export", "secs": 3600.0}],
        [{"cmd": "drain"}],
    ])
    sched = Scheduler(_fake_engine(), feed=feed,
                      export_every=1e-9, export_path=path)
    sched.run([])
    # The command armed the hold...
    assert sched._export_hold_until > sched.clock()
    # ...which gates the cadence export (the snapshot file freezes —
    # the router's stale-snapshot drill) but NOT a forced one (the
    # run-end final still lands).
    seq0 = sched._snap_seq
    sched._maybe_export()
    assert sched._snap_seq == seq0            # held: no new snapshot
    sched._maybe_export(force=True)
    assert sched._snap_seq == seq0 + 1
    with open(path) as f:
        assert json.load(f)["seq"] == seq0 + 1


# --- paged auto-sizing (satellite: hbm_budget + slot_pages_peak) ---------

def test_auto_num_pages_arithmetic():
    from tensorflow_distributed_tpu.serve.paging.engine import (
        auto_num_pages)
    # No budget, no observation: serving + equal headroom.
    pool, lines = auto_num_pages(num_slots=2, need_pages=4,
                                 page_bytes=1000)
    assert pool == 1 + 8 + 8
    assert any("worst case" in ln for ln in lines)
    # An observed working set replaces the blind headroom.
    pool, lines = auto_num_pages(num_slots=2, need_pages=4,
                                 page_bytes=1000, observed_peak=3)
    assert pool == 1 + 8 + 3
    assert any("slot_pages_peak 3" in ln for ln in lines)
    # A budget caps the pool...
    pool, lines = auto_num_pages(num_slots=2, need_pages=4,
                                 page_bytes=1000,
                                 budget_bytes=12_000,
                                 reserved_bytes=2_000)
    assert pool == 10
    # ...but never below the floor (reservation + COW page).
    pool, _ = auto_num_pages(num_slots=2, need_pages=4,
                             page_bytes=1000, budget_bytes=3_000)
    assert pool == 2 + 8


def test_fleet_config_validation_matrix():
    from tensorflow_distributed_tpu.config import (
        ServeConfig, TrainConfig)

    def serve_cfg(**kw):
        return TrainConfig(mode="serve", model="gpt_lm", seq_len=64,
                           serve=ServeConfig(**kw))

    serve_cfg(inbox="/t/i", journal="/t/j").validate()
    with pytest.raises(ValueError, match="journal"):
        serve_cfg(inbox="/t/i").validate()
    with pytest.raises(ValueError, match="seq-len"):
        TrainConfig(mode="serve", model="gpt_lm",
                    serve=ServeConfig(inbox="/t/i",
                                      journal="/t/j")).validate()
    with pytest.raises(ValueError, match="mode"):
        TrainConfig(serve=ServeConfig(inbox="/t/i",
                                      journal="/t/j")).validate()
    with pytest.raises(ValueError, match="request file"):
        serve_cfg(inbox="/t/i", journal="/t/j",
                  requests="/t/r.jsonl").validate()
    with pytest.raises(ValueError, match="router owns"):
        serve_cfg(inbox="/t/i", journal="/t/j", trace="poisson",
                  arrival_rate=1.0).validate()
    with pytest.raises(ValueError, match="paged"):
        serve_cfg(hbm_budget_gb=1.0).validate()
    with pytest.raises(ValueError, match="drop one"):
        serve_cfg(paged=True, hbm_budget_gb=1.0,
                  num_pages=64).validate()
    serve_cfg(paged=True, hbm_budget_gb=1.0).validate()
    with pytest.raises(ValueError, match="stale_s"):
        RouterConfig(stale_s=0).validate()
    with pytest.raises(ValueError, match="max_restarts"):
        ControllerConfig(max_restarts=-1).validate()


# --- report folding ------------------------------------------------------

def test_report_folds_fleet_records():
    from tensorflow_distributed_tpu.observe.report import (
        render, summarize)
    records = [
        {"event": "fleet_dispatch", "rid": 0, "replica": "r0",
         "kind": "fresh", "retry": 0, "slo": "high", "t_s": 0.1},
        {"event": "fleet_dispatch", "rid": 0, "replica": "r1",
         "kind": "redispatch", "retry": 1, "slo": "high", "t_s": 0.5},
        {"event": "fleet_dispatch", "rid": 1, "replica": "r1",
         "kind": "fresh", "retry": 0, "slo": "batch", "t_s": 0.2},
        {"event": "fleet_replica", "replica": "r0",
         "state": "quarantined", "reason": "stale_snapshot",
         "t_s": 0.4},
        {"event": "fleet_replica", "replica": "r0",
         "state": "rejoined", "t_s": 1.0},
        {"event": "fleet_shed", "rid": 2, "slo": "batch",
         "reason": "saturated", "retries": 0, "t_s": 0.9},
        {"event": "fleet_swap", "replica": "r1", "ckpt_step": 4,
         "t_s": 0.8},
        {"event": "fleet_summary", "requests": 3, "requests_done": 2,
         "requests_shed": 1, "requests_lost": 0, "dispatches": 3,
         "redispatches": 1,
         "dispatch_retry_hist": {"0": 2, "1": 1},
         "quarantines": 1, "rejoins": 1, "deaths": 0, "restarts": 0,
         "rolling_swaps": 1, "staleness_max_steps": 2,
         "tokens_per_sec": 50.0, "wall_s": 2.0,
         "ttft_ms_p99_recovery": 120.0, "recovery_requests": 1,
         "shed_by_class": {"batch": 1}},
    ]
    out = summarize(records)
    fleet = out["fleet"]
    assert fleet["requests"] == 3 and fleet["requests_lost"] == 0
    assert fleet["dispatch_retry_hist"] == {"0": 2, "1": 1}
    assert fleet["staleness_max_steps"] == 2
    assert fleet["shed_events"] == 1
    assert fleet["replicas"]["r0"]["quarantined"] == 1
    assert fleet["replicas"]["r0"]["rejoined"] == 1
    assert fleet["replicas"]["r1"]["dispatches"] == 2
    assert fleet["replicas"]["r1"]["swaps"] == 1
    text = render(out)
    assert "Fleet" in text and "retry_hist" in text
    # Crashed-front-end path: no fleet_summary record — the histogram
    # re-derives from the dispatch stream.
    out2 = summarize([r for r in records
                      if r["event"] != "fleet_summary"])
    assert out2["fleet"]["dispatch_retry_hist"] == {"0": 1, "1": 1}
    # Plain reports stay shape-stable.
    assert "fleet" not in summarize([{"event": "step", "step": 1}])


# --- the real thing (slow) -----------------------------------------------

@pytest.mark.slow
def test_fleet_e2e_sigkill_zero_lost(tmp_path):
    """2-replica REAL fleet, SIGKILL one mid-stream: every request
    completes (re-dispatched as continuations), the dead replica
    restarts on a fresh epoch, and the streams match the fake-free
    greedy reference (the killed work re-derives identically)."""
    import subprocess
    import sys as _sys

    from tensorflow_distributed_tpu.fleet.controller import (
        ControllerConfig as CC)
    from tensorflow_distributed_tpu.fleet.router import (
        RouterConfig as RC)
    from tensorflow_distributed_tpu.fleet.run import (
        load_workload, run_fleet)

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONUNBUFFERED"] = "1"
    ckpt = str(tmp_path / "ckpt")
    common = ["--model", "gpt_lm", "--model-size", "tiny",
              "--seq-len", "48", "--seed", "0",
              "--compute-dtype", "float32"]
    subprocess.run(
        [_sys.executable, "-m", "tensorflow_distributed_tpu.cli",
         *common, "--dataset", "synthetic", "--train-steps", "2",
         "--batch-size", "8", "--eval-every", "0", "--log-every",
         "0", "--checkpoint-dir", ckpt, "--checkpoint-every", "2"],
        env=env, check=True, capture_output=True, timeout=300)
    wl = str(tmp_path / "wl.jsonl")
    rng = np.random.default_rng(0)
    with open(wl, "w") as f:
        for i in range(10):
            plen = int(rng.integers(4, 12))
            f.write(json.dumps({
                "prompt": [int(t) for t in rng.integers(0, 64, plen)],
                "max_new_tokens": 32,
                "arrival_s": round(0.15 * i, 3)}) + "\n")

    def arm_kill(ctl, router):
        import threading
        import time as time_mod

        def hunt():
            # Journal-armed (fresh to one decode step): kill while a
            # request is mid-decode with budget left, so the death
            # reliably leaves in-flight work to re-dispatch.
            t_end = time_mod.monotonic() + 30
            while time_mod.monotonic() < t_end:
                h = ctl.members["r1"].handle
                jr = h.read_journal(epoch=h.epoch)  # stateless: the
                #   router owns the incremental tail cache
                if any(not e.get("done")
                       and 1 <= len(e.get("tokens", ())) <= 16
                       for e in jr.values()):
                    break
                time_mod.sleep(0.01)
            ctl.kill("r1")
        threading.Thread(target=hunt, daemon=True).start()

    summary = run_fleet(
        fleet_dir=str(tmp_path / "fleet"), replicas=2,
        base_args=["--mode", "serve", *common,
                   "--checkpoint-dir", ckpt,
                   "--serve.num-slots", "2",
                   "--serve.buckets", "48"],
        workload=load_workload(wl), ckpt_dir=ckpt, env=env,
        actions=[(0.2, arm_kill)],
        router_cfg=RC(dispatch_timeout_s=60.0),
        controller_cfg=CC(backoff_base_s=0.25),
        timeout_s=300.0,
        jsonl=str(tmp_path / "fleet.jsonl"))
    assert summary["requests_lost"] == 0
    assert summary["requests_done"] == 10
    assert summary["requests_shed"] == 0
    assert summary["deaths"] == 1 and summary["restarts"] == 1
    assert summary["redispatches"] >= 1
    # Every stream ran to its full budget (greedy, no EOS).
    assert all(len(t) == 32 for t in summary["tokens"].values())
    # The fleet JSONL folds into the report's Fleet section.
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)
    rep = summarize(load_records(str(tmp_path / "fleet.jsonl")))
    assert rep["fleet"]["requests_lost"] == 0
    assert rep["fleet"]["replicas"]["r1"]["exited"] >= 1
