"""Request journal: crash-durable serving progress, token granularity.

The scheduler appends three record kinds as it works — ``admit`` (the
full request: id, prompt, budget, eos), ``tok`` (one retired token for
one request), ``done`` (the request finished) — flushed to the OS once
per scheduler iteration, so a SIGKILL'd serving process leaves a
journal complete up to its last decode step. A restarted leg (the
supervisor re-runs ``--mode serve`` with the same args) replays the
journal and re-admits every unfinished request as a CONTINUATION:
prompt extended by the tokens already journaled, budget reduced by the
same count — greedy decode is deterministic, so the continuation
produces exactly the tokens the dead leg would have, and a kill costs
re-decoding at most the tokens that were in flight past the last
flush, never a request.

Semantics of an existing file: non-empty means RESUME (replay, then
append) — that is what makes the supervisor's identical restart
command re-admit instead of restart from scratch. A fresh run wants a
fresh path (benches and tests use per-run temp dirs). Truncated final
lines (the kill can land mid-write) are skipped, mirroring
observe.report.load_records.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import numpy as np


class RequestJournal:
    """Append-side handle. Opens lazily on first append; ``flush()``
    pushes buffered lines to the OS (enough for process-kill
    durability; fsync would only add OS-crash coverage serving does
    not promise)."""

    def __init__(self, path: str):
        self.path = path
        self._f = None

    def _line(self, rec: Dict[str, Any]) -> None:
        if self._f is None:
            self._f = open(self.path, "a")
        self._f.write(json.dumps(rec) + "\n")

    def admit(self, rid: int, prompt, max_new_tokens: int,
              eos_id: int, slo: str = "standard",
              tenant: str = "", session: str = "") -> None:
        """``slo``/``tenant``/``session`` make the journal
        self-describing: replay re-derives requests from the run seed,
        so they are informational for the resume path — but a journal
        read standalone (workload re-derivation, debugging)
        keeps the class/tenant/conversation story, and the session tag
        is how a resumed leg's multi-turn linkage survives a SIGKILL
        (the re-derived workload carries the same ids; pinned in
        tests/test_paging.py)."""
        rec = {"e": "admit", "rid": int(rid),
               "prompt": [int(t) for t in np.asarray(prompt)],
               "max_new": int(max_new_tokens),
               "eos": int(eos_id)}
        if slo != "standard":
            rec["slo"] = slo
        if tenant:
            rec["tenant"] = tenant
        if session:
            rec["sess"] = session
        self._line(rec)

    def token(self, rid: int, tok: int, t_s: float) -> None:
        """One retired token (``t_s`` = run-relative seconds, so a
        killed leg's serving wall time can be reconstructed from its
        last journaled token)."""
        self._line({"e": "tok", "rid": int(rid), "t": int(tok),
                    "s": round(t_s, 4)})

    def done(self, rid: int) -> None:
        self._line({"e": "done", "rid": int(rid)})

    def reject(self, rid: int) -> None:
        """The request cannot be served here (does not fit the cache,
        or arrived while draining). A fleet router reading the journal
        sheds it instead of waiting forever — the replica must never
        crash over a bad dispatch."""
        self._line({"e": "reject", "rid": int(rid)})

    def flush(self) -> None:
        if self._f is not None:
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def fold_record(out: Dict[int, Dict[str, Any]],
                rec: Dict[str, Any]) -> None:
    """Fold ONE parsed journal record into a replay accumulator — the
    single definition of journal semantics, shared by :func:`replay`
    and the fleet router's incremental tail
    (fleet.replica.ReplicaHandle.read_journal)."""
    rid = rec.get("rid")
    if rid is None:
        return
    ent = out.setdefault(int(rid), {"req": None, "tokens": [],
                                    "done": False,
                                    "reject": False,
                                    "last_s": 0.0})
    kind = rec.get("e")
    if kind == "admit":
        ent["req"] = {"prompt": rec.get("prompt", []),
                      "max_new": rec.get("max_new", 0),
                      "eos": rec.get("eos", -1)}
    elif kind == "tok":
        ent["tokens"].append(int(rec["t"]))
        ent["last_s"] = max(ent["last_s"],
                            float(rec.get("s", 0.0)))
    elif kind == "done":
        ent["done"] = True
    elif kind == "reject":
        ent["reject"] = True


def replay(path: str) -> Dict[int, Dict[str, Any]]:
    """Read a journal back into ``{rid: {"req": {...} | None,
    "tokens": [...], "done": bool, "last_s": float}}``. Missing file =
    empty dict (a fresh run). Malformed lines (the truncated tail of a
    kill) are skipped."""
    out: Dict[int, Dict[str, Any]] = {}
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # the kill's mid-write tail
            fold_record(out, rec)
    return out


def apply_replay(requests: List[Any],
                 journal: Dict[int, Dict[str, Any]]) -> List[Any]:
    """Fold a replayed journal into a fresh workload (the restarted
    leg regenerates its requests deterministically — same seed, same
    trace — and this narrows them to the unfinished work):

    - ``done`` requests drop (already served and streamed);
    - partially-served requests become CONTINUATIONS: prompt extended
      by the journaled tokens, budget cut by the same count, arrival 0
      (they were in flight — they re-enter immediately), tagged with
      ``_base_tokens`` so the completion reports the FULL token list;
    - untouched requests keep their arrival offsets SHIFTED by the
      dead leg's elapsed serving time (the open-loop clients kept
      sending while the process was down — a request whose arrival
      already passed is due immediately, not re-waited).

    Pure function over Request-shaped objects (works on the fake
    engine's requests too — jax-free by design)."""
    out: List[Any] = []
    import dataclasses

    elapsed = max((e["last_s"] for e in journal.values()),
                  default=0.0)
    for req in requests:
        ent = journal.get(req.rid)
        if ent is None:
            out.append(dataclasses.replace(
                req, arrival_s=max(0.0, req.arrival_s - elapsed)))
            continue
        if ent["done"]:
            continue
        toks = list(ent["tokens"])
        if not toks:
            # Admitted but no token journaled (killed inside its first
            # prefill): re-serve from scratch, due immediately.
            out.append(dataclasses.replace(req, arrival_s=0.0))
            continue
        if len(toks) >= req.max_new_tokens or (
                req.eos_id >= 0 and toks[-1] == req.eos_id):
            # Every budgeted token (or the EOS) was journaled but the
            # done record didn't land — the request IS finished; don't
            # re-admit a zero-budget or past-EOS continuation.
            continue
        cont = dataclasses.replace(
            req,
            prompt=np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(toks, np.int32)]),
            max_new_tokens=req.max_new_tokens - len(toks),
            arrival_s=0.0)
        cont._base_tokens = toks
        out.append(cont)
    return out
