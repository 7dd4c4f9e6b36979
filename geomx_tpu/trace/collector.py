"""Scheduler-side trace collector: merge + each round's blocking chain.

Runs on the global scheduler (the one node every party can reach over
the WAN domain).  Nodes batch-ship completed spans as
``Ctrl.TRACE_REPORT`` data-channel requests (fire-and-forget — no
response slot, so a dead collector never blocks training); the collector
owns the PS app id on the scheduler's postoffice, which otherwise serves
no data traffic.

Clock correction: each report carries the sender's heartbeat-RTT clock
offsets to its scheduler(s) (``Postoffice.clock_offsets``).  Offsets are
"scheduler clock minus my clock"; a worker only knows its party
scheduler, so its offset to the global clock is chained through its
party's local server, which heartbeats both tiers:

    off(worker -> global) = off(worker -> psched) + off(psched -> global)
    off(psched -> global) = off(server -> global) - off(server -> psched)

On one host all offsets are ~0; on real deployments this is the same
RTT/2 estimate NTP starts from — good to a few ms, enough to order
LAN-push vs WAN vs optimizer stages that differ by tens of ms.
"""

from __future__ import annotations

import collections
import json
import logging
import threading
from typing import Deque, Dict, List, Optional

_log = logging.getLogger(__name__)

# span-name prefix -> stage of the round trip (the push→merge→WAN→
# optimize→pull chain of PAPER.md, plus the worker's own work and the
# control stages).  A span whose name has none (``handle``, ``be.*``,
# ``opt.step``) takes the stage of the nearest span around it that has.
_STAGES = (
    ("worker.grad", "compute"),
    ("edge.", "edge"),
    ("worker.push", "lan_push"),
    ("local.push", "local_merge"),
    ("local.close", "local_merge"),
    ("local.land", "local_merge"),
    ("local.init", "local_merge"),
    ("codec.", "codec"),
    ("wan.", "wan"),
    ("global.push", "global_merge"),
    ("global.close", "global_merge"),
    ("global.opt", "global_merge"),
    ("global.swap", "global_merge"),
    ("global.acks", "global_merge"),
    ("global.replay", "global_merge"),
    ("global.init", "global_merge"),
    ("global.pull", "pull_fanout"),
    ("global.serve", "pull_fanout"),
    ("local.pull", "pull_fanout"),
    ("worker.pull", "pull_fanout"),
    ("worker.wait", "pull_fanout"),
    ("barrier", "barrier"),
)
# what the collector holds at once (``TraceCollector``): the newest
# events of the merged timeline where a dump was asked for, the rounds
# not whole yet, the finished rounds' reports, and the events of no round
MAX_EVENTS = 100_000
MAX_HELD_ROUNDS = 4
MAX_REPORTS = 512
MAX_LOOSE = 4096
# the chain's own labels, and the stage each is counted under
ROOT_SPAN = "round"
PATH_EVENT = "round.path"
UNEXPLAINED = "unexplained"
WAN = "wan"
WAIT_QUEUE = "wait:queue"
WAIT_DEVICE = "wait:device"
WAIT_LOCK = "wait:lock"
OTHER_STAGE = "other"
_LABEL_STAGE = {UNEXPLAINED: UNEXPLAINED, WAN: "wan", WAIT_QUEUE: "queue"}
# a span in which a thread waits for the node's other threads (the
# worker's ``wait_all``): the chain goes on with what they were doing
_JOIN_SPANS = frozenset(("worker.wait",))
# node role -> the field of ``round.path`` its working time goes to
_ROLE_FIELD = {"worker": "worker_us", "server": "local_server_us",
               "global_server": "global_server_us",
               "standby_global": "global_server_us"}
_WAIT_FIELD = {WAIT_DEVICE: "device_wait_us", WAIT_LOCK: "lock_wait_us",
               WAIT_QUEUE: "queue_wait_us", WAN: "wan_us",
               UNEXPLAINED: "unexplained_us"}
PATH_FIELDS = ("worker_us", "local_server_us", "global_server_us",
               "device_wait_us", "lock_wait_us", "queue_wait_us", "wan_us",
               "unexplained_us")


def _stage_of(name: str) -> Optional[str]:
    for prefix, stage in _STAGES:
        if name.startswith(prefix):
            return stage
    return None


# ---- the blocking chain -----------------------------------------------------
#
# Pure functions over one round's event dicts (``ts`` / ``dur`` in
# microseconds on one clock, ``pid`` the node, ``tid`` the thread,
# ``args`` with ``span`` / ``parent`` and what the site carries).  Times
# are rounded to whole microseconds once, so that the labels sum to the
# round's wall time exactly.

class _S:
    """One timed span of the round."""

    __slots__ = ("node", "tid", "name", "a", "b", "id", "parent", "args",
                 "up", "lane")

    def __init__(self, ev: dict):
        args = ev.get("args") or {}
        self.node = str(ev.get("pid", "?"))
        self.tid = str(ev.get("tid", ""))
        self.name = ev.get("name", "")
        self.a = int(round(ev["ts"]))
        self.b = max(self.a, int(round(ev["ts"] + (ev.get("dur") or 0.0))))
        self.id = args.get("span", 0)
        self.parent = args.get("parent", 0)
        self.args = args
        self.up = None      # the span around it on its thread
        self.lane = None    # (node, serial channel or thread)

    @property
    def label(self) -> str:
        return f"{self.node.split(':')[0]}:{self.name}"

    @property
    def top(self) -> "_S":
        s = self
        while s.up is not None:
            s = s.up
        return s

    @property
    def stage(self) -> str:
        s = self
        while s is not None:
            st = _stage_of(s.name)
            if st is not None:
                return st
            s = s.up
        return OTHER_STAGE


def _innermost(spans: list) -> list:
    """One thread's spans flattened into ``(a, b, span)`` pieces that do
    not overlap, each instant given to the innermost span open (a
    span's pieces sum to its self time); sets each span's ``up``.  The
    definition ``benchmark/lib/spans.py`` ``innermost`` uses, kept here
    because the program imports nothing of the benchmark."""
    out, stack, at = [], [], 0

    def emit(a, b, s):
        if b > a:
            out.append((a, b, s))

    for s in sorted(spans, key=lambda s: (s.a, s.a - s.b)):
        while stack and stack[-1].b <= s.a:
            top = stack.pop()
            emit(at, top.b, top)
            at = max(at, top.b)
        if stack:
            emit(at, s.a, stack[-1])
            s.up = stack[-1]
        stack.append(s)
        at = s.a
    while stack:
        top = stack.pop()
        emit(at, top.b, top)
        at = max(at, top.b)
    return out


class _Round:
    """One round's spans indexed for the walk."""

    def __init__(self, events: list):
        self.spans = [_S(e) for e in events if (e.get("dur") or 0.0) > 0.0]
        self.by_id = {s.id: s for s in self.spans if s.id}
        # a message's own instant, by the message's span id; its arrival
        # on the far side of a wire, by the same id
        self.sends, self.recvs = {}, {}
        for e in events:
            name, a = e.get("name", ""), e.get("args") or {}
            if name.endswith(".send") and a.get("span"):
                self.sends[a["span"]] = e
            elif name == "wan.recv" and a.get("parent"):
                self.recvs[a["parent"]] = e
        by_thread: Dict[tuple, list] = {}
        for s in self.spans:
            by_thread.setdefault((s.node, s.tid), []).append(s)
        # a serial channel of the reactor hops between the pool's
        # threads: its pieces are gathered under the channel's name
        # (``handle``'s ``lane``), every other thread's under its own
        self.lanes: Dict[tuple, list] = {}
        for (node, tid), spans in by_thread.items():
            for a, b, s in _innermost(spans):
                if s.lane is None:
                    s.lane = (node, s.top.args.get("lane") or tid)
                self.lanes.setdefault(s.lane, []).append((a, b, s))
        for s in self.spans:
            if s.lane is None:    # no self time at all
                s.lane = (s.node, s.top.args.get("lane") or s.tid)
        for pieces in self.lanes.values():
            pieces.sort(key=lambda p: p[0])
        self.tops: Dict[str, list] = {}
        for s in self.spans:
            if s.up is None and s.name != ROOT_SPAN:
                self.tops.setdefault(s.node, []).append(s)


def blocking_chain(events: list, detail: bool = False) -> Optional[dict]:
    """The round's blocking chain: every microsecond between the opening
    of the first worker's ``round`` root and the close of the last one's
    given to exactly one label (docs/tracing.md "The blocking chain").

    Walks back from the last close: along the thread (or serial channel)
    the chain is on, each instant to the innermost open span
    (``<role>:<span>``), less the waits the span recorded
    (``wait:device``; ``wait:lock``, or what the lock's holder was doing
    where it is known); where the thread waited for the node's other
    threads (``worker.wait``), on to the one that worked last; at the
    start of a ``handle``, the time its message waited (``queued_us``)
    to what the receiving channel was doing meanwhile (``wait:queue``
    where nothing), a wire's part of it to ``wan``, and on to the span
    that sent it (the ``.send`` instant's ``by``); ``unexplained`` where
    no span is open and no wait recorded.

    Returns ``{"t0", "t1", "wall_us", "path": {label: us}, "stages":
    {stage: us}, "nodes": {stage: {node: us}}, "lost": node or None}``
    (with ``detail`` also ``"segments"``: ``[label, node, us]`` in the
    order the walk gave them, latest first), or None for a round with
    no root."""
    rnd = _Round(events)
    roots = [s for s in rnd.spans if s.name == ROOT_SPAN]
    if not roots:
        return None
    t0 = min(s.a for s in roots)
    last = max(roots, key=lambda s: s.b)
    path: Dict[str, int] = {}
    stages: Dict[str, int] = {}
    nodes: Dict[str, Dict[str, int]] = {}
    lost = [None]
    segments: list = []

    def give(label: str, stage: str, node: str, us: int) -> None:
        if us > 0:
            if detail:
                segments.append([label, node, us])
            path[label] = path.get(label, 0) + us
            stages[stage] = stages.get(stage, 0) + us
            by = nodes.setdefault(stage, {})
            by[node] = by.get(node, 0) + us

    def give_piece(s: _S, lo: int, hi: int, depth: int = 0) -> None:
        """[lo, hi] of span ``s``'s self time: its recorded waits apart,
        the rest to the span."""
        at = lo
        for kind, off, us, holder in sorted(s.args.get("waits") or (),
                                            key=lambda w: w[1]):
            wa = max(at, s.a + int(round(off)))
            wb = min(hi, s.a + int(round(off + us)))
            if wb <= wa:
                continue
            give(s.label, s.stage, s.node, wa - at)
            held = rnd.by_id.get(holder) if kind == "lock" else None
            if held is not None and held.lane != s.lane and depth < 3:
                # the holder's thread meanwhile, its own waits included
                give_lane(held.lane, wa, wb, WAIT_LOCK, s, depth + 1)
            else:
                give(WAIT_DEVICE if kind == "device" else WAIT_LOCK,
                     s.stage, s.node, wb - wa)
            at = wb
        give(s.label, s.stage, s.node, hi - at)

    def give_lane(lane: tuple, lo: int, hi: int, idle: str, of: _S = None,
                  depth: int = 0) -> None:
        """[lo, hi] to whatever ``lane`` was doing; to ``idle`` where
        it had no span open (but a round's root)."""
        at = lo
        for a, b, s in rnd.lanes.get(lane, ()):
            if b <= at or s.name == ROOT_SPAN:
                continue
            if a >= hi:
                break
            if a > at:
                _idle(idle, of, lane, a - at)
            give_piece(s, max(a, at), min(b, hi), depth)
            at = min(b, hi)
        if hi > at:
            _idle(idle, of, lane, hi - at)

    def _idle(label: str, of: _S, lane: tuple, us: int) -> None:
        if of is not None and label != UNEXPLAINED:
            give(label, _LABEL_STAGE.get(label, of.stage), of.node, us)
        else:
            give(label, _LABEL_STAGE.get(label, OTHER_STAGE), lane[0], us)

    def latest_before(node: str, lane: tuple, t: int, lo: int):
        """The top-level span of ``node``'s other lanes that began last
        before ``t`` (and was still open after ``lo``)."""
        best = None
        for x in rnd.tops.get(node, ()):
            if x.lane != lane and x.a < t and x.b > lo and (
                    best is None or x.a > best.a):
                best = x
        return best

    def sender_of(h: _S):
        """(the span that sent ``h``'s message, the message's ``.send``
        instant), either None where the events do not say."""
        send = rnd.sends.get(h.parent)
        if send is not None:
            by = rnd.by_id.get((send.get("args") or {}).get("by", 0))
            if by is None:
                by = rnd.by_id.get(send["args"].get("parent", 0))
            return by, send
        return rnd.by_id.get(h.parent), None

    t, cur = last.b, last
    for _ in range(4 * len(rnd.spans) + 16):
        if t <= t0 or cur is None:
            break
        top = cur.top
        # ---- back along the lane to the top-level span's start --------
        jump = None
        for a, b, s in reversed(rnd.lanes.get(top.lane, ())):
            if a >= t or s.top is not top:
                if b <= top.a:
                    break
                continue
            hi = min(b, t)
            if s.name in _JOIN_SPANS:
                other = latest_before(s.node, s.lane, hi, a)
                if other is not None:
                    woke = min(other.b, hi)
                    give(UNEXPLAINED, UNEXPLAINED, s.node, hi - woke)
                    jump, t = other, woke
                    break
            if s.name == ROOT_SPAN:
                give(UNEXPLAINED, UNEXPLAINED, s.node, hi - a)
            else:
                give_piece(s, a, hi)
            t = a
        if jump is not None:
            cur = jump
            continue
        t = min(t, top.a)
        if top.name == ROOT_SPAN:
            break
        # ---- at a top-level span's start: how it came to run ----------
        sender, send = sender_of(top)
        q = top.args.get("queued_us")
        arrived = top.a
        if q is not None:
            sent = top.a - int(round(q))
        elif send is not None:
            sent = min(top.a, int(round(send["ts"])))
        else:
            sent = top.a
        sent = max(sent, t0)
        recv = rnd.recvs.get(top.parent)
        if recv is not None and send is not None:
            # a wire (or the modeled one): the sender's instant to the
            # receiving van's
            arrived = max(sent, min(top.a, int(round(recv["ts"]))))
            left = max(sent, min(arrived, int(round(send["ts"]))))
            give(WAN, "wan", str(send.get("pid", top.node)), arrived - left)
            give(WAIT_QUEUE, "queue", str(send.get("pid", top.node)),
                 left - sent)
        elif q is None:
            # a wire with no far-side instant (a LAN hop over TCP)
            give(WAIT_QUEUE, "queue", top.node, top.a - sent)
            arrived = top.a
        else:
            arrived = sent
        give_lane(top.lane, arrived, top.a, WAIT_QUEUE, top)
        t = sent
        if sender is None:
            lost[0] = top.node
            break
        cur = sender
        if t > sender.b:
            # the sender's span as the events have it closed before the
            # send (a clock off by more than the hop): on from its end
            give(UNEXPLAINED, UNEXPLAINED, sender.node, t - sender.b)
            t = sender.b
        elif t < sender.a:
            lost[0] = sender.node
            break
    give(UNEXPLAINED, UNEXPLAINED, lost[0] or (cur.node if cur else "?"),
         max(0, t - t0))
    out = {"t0": t0, "t1": last.b, "wall_us": last.b - t0, "path": path,
           "stages": stages, "nodes": nodes, "lost": lost[0]}
    if detail:
        out["segments"] = segments
    return out


def path_fields(chain: dict) -> dict:
    """The fields of a ``round.path`` instant from a round's chain: the
    chain's time on working threads by the node's role, and the waits.
    They sum to ``wall_us``."""
    out = dict.fromkeys(PATH_FIELDS, 0)
    for label, us in chain["path"].items():
        field = _WAIT_FIELD.get(label) or _ROLE_FIELD.get(
            label.split(":", 1)[0], "unexplained_us")
        out[field] += us
    wall = chain["wall_us"]
    out.update(wall_us=wall,
               unexplained_pct=100.0 * out["unexplained_us"] / wall
               if wall else 0.0)
    return out


def _new_stage() -> dict:
    return {"busy_us": 0.0, "path_us": 0, "worst_node": None,
            "worst_us": 0.0, "by_party": {}}


def _party_of(node: str) -> str:
    return node.rsplit("@", 1)[1] if "@" in node else "central"


def _shard_of(node: str):
    """Global-tier shard rank of a node, or None.  The shard identity
    survives failover: ``standby_global:k`` serves exactly shard k's
    key range once promoted, so its spans bill to the same shard as the
    primary it replaced."""
    for role in ("global_server:", "standby_global:"):
        if node.startswith(role):
            try:
                return int(node[len(role):].split("@", 1)[0])
            except ValueError:
                return None
    return None


def resolve_clock_offsets(offs: Dict[str, Dict[str, float]],
                          gname: str) -> Dict[str, float]:
    """Per-node offset to the global scheduler's clock (seconds), from
    each node's heartbeat-echo offsets to its scheduler target(s) —
    the chaining documented in the module docstring.  Shared by the
    trace collector and the flight-recorder postmortem assembler
    (obs/postmortem.py), which rebases per-node dumps the same way."""
    out: Dict[str, float] = {gname: 0.0}
    # party-scheduler offsets chained through the party's server
    psched_to_g: Dict[str, float] = {}
    for n, o in offs.items():
        if gname in o:
            out[n] = o[gname]
            for sched, v in o.items():
                if sched != gname:
                    psched_to_g[sched] = o[gname] - v
                    out.setdefault(sched, o[gname] - v)
    for n, o in offs.items():
        if n in out:
            continue
        for sched, v in o.items():
            if sched in psched_to_g:
                out[n] = v + psched_to_g[sched]
                break
    return out


def _round_report(tid: int, events: List[dict]) -> tuple:
    """``(report, chain)`` of one round from its events (``ts`` on one
    clock): :meth:`TraceCollector.critical_path`'s entry for the round,
    and the :func:`blocking_chain` it was made from (None for a round
    with no root).

    ``wall_us`` from the first worker's ``round`` root opening to the
    last one's close; ``path`` ``{label: us}``, the round's blocking
    chain (the labels sum to ``wall_us``); per stage ``path_us``, its
    share of the chain, beside ``busy_us``, the summed durations of its
    spans over every thread (thread time: it grows with keys, parties
    and threads, and says nothing of what the round waited for), the
    stage's worst node and straggler party; and ``dominant_stage``, the
    stage with the largest share of the CHAIN: the most a faster stage
    can take off the round, i.e. the first place a perf PR should look.
    WAN time is recovered from matched wan.send → wan.recv instants.
    """
    # wan.send instants by span-id, for pairing with their wan.recv
    sends = {ev["args"]["span"]: ev for ev in events
             if ev.get("name") == "wan.send"
             and ev.get("args", {}).get("span")}
    r = {"trace_id": tid, "round": tid - 1, "num_spans": len(events),
         "stages": {}, "path": {}}
    t0 = t1 = events[0]["ts"] if events else 0.0
    for ev in events:
        a = ev.get("args", {})
        dur = float(ev.get("dur") or 0.0)
        t0 = min(t0, ev["ts"])
        t1 = max(t1, ev["ts"] + dur)
        name = ev.get("name", "")
        stage = _stage_of(name)
        node = ev.get("pid", "?")
        if name == "wan.recv":
            send = sends.get(a.get("parent", -1))
            if send is None:
                continue
            dur = max(0.0, ev["ts"] - send["ts"])
            node = send.get("pid", node)  # bill the sender's link
        elif name == "wan.send" or dur <= 0.0:
            continue  # instants: wan time comes from the recv pair
        if stage is None:
            continue
        st = r["stages"].setdefault(stage, _new_stage())
        st["busy_us"] += dur
        party = _party_of(node)
        st["by_party"][party] = st["by_party"].get(party, 0.0) + dur
        if dur > st["worst_us"]:
            st["worst_us"] = dur
            st["worst_node"] = node
        # sharded global tier: bill global-server work (and WAN
        # transit INTO a shard — the recv side of the matched pair)
        # to its shard, so the report names the slowest shard the
        # way it names the straggler party
        shard = _shard_of(str(ev.get("pid", node))
                          if name == "wan.recv" else node)
        if shard is not None:
            bs = r.setdefault("by_shard", {})
            bs[shard] = bs.get(shard, 0.0) + dur
    r.update(t0=t0, t1=t1, wall_us=t1 - t0)
    chain = blocking_chain(events)
    if chain is not None:
        r.update(t0=chain["t0"], t1=chain["t1"], wall_us=chain["wall_us"],
                 path=dict(sorted(chain["path"].items(),
                                  key=lambda kv: -kv[1])))
        if chain["lost"]:
            r["chain_lost_at"] = chain["lost"]
        for stage, us in chain["stages"].items():
            st = r["stages"].setdefault(stage, _new_stage())
            st["path_us"] = us
            on = chain["nodes"][stage]
            st["path_node"] = max(on, key=on.get)
            if st["worst_node"] is None:
                # a stage of the chain alone (a wait, the unexplained
                # rest): the node it was on
                st["worst_node"] = st["path_node"]
    if r["stages"]:
        # by the chain where the round has one (a round whose roots
        # never arrived: by thread time, as before)
        key = "path_us" if r["path"] else "busy_us"
        r["dominant_stage"] = max(
            r["stages"], key=lambda s: r["stages"][s][key])
        for st in r["stages"].values():
            if st["by_party"]:
                st["straggler_party"] = max(
                    st["by_party"], key=st["by_party"].get)
    else:
        r["dominant_stage"] = None
    if r.get("by_shard"):
        # the first place to look when shard-count scaling is
        # sublinear: which key range's server bounded the round
        r["slowest_shard"] = max(r["by_shard"], key=r["by_shard"].get)
    return r, chain


class TraceCollector:
    """One per deployment, on the global scheduler's postoffice.

    Retention is bounded by construction.  A round's events are held
    until the round is whole (every worker has closed a later one, and
    the chain is not lost for want of a server's batch: a worker ships
    when its root closes, a server by ``trace_batch_events``); its chain
    then goes out as one ``round.path`` instant, its report
    (:meth:`critical_path`'s entry for the round) is kept, and its
    events are DROPPED: two rounds' events at most where every server
    fills a batch a round, ``MAX_HELD_ROUNDS`` (and the one arriving)
    whatever the nodes do.
    The merged timeline (``_events``, for :meth:`dump`) is kept only
    where a dump was asked for (``Config.trace_dir`` set), and there
    under ``MAX_EVENTS``, oldest dropped first."""

    def __init__(self, postoffice):
        from geomx_tpu.kvstore.common import Ctrl
        from geomx_tpu.obs.endpoint import get_endpoint

        self.po = postoffice
        self.node = str(postoffice.node)
        self._mu = threading.Lock()
        self._keep = bool(getattr(postoffice.config, "trace_dir", ""))
        self._events: Deque[dict] = collections.deque(maxlen=MAX_EVENTS)
        self._offsets: Dict[str, Dict[str, float]] = {}
        self.reports_received = 0
        self.events_received = 0
        # what the bounds cost: events of a round that was already given
        # out or pushed out, and chains that could not be computed
        self.late_events = 0
        self.path_errors = 0
        # the rounds not whole yet, by trace id; each worker's newest
        # ``round`` root; the newest round given out
        self._by_round: Dict[int, List[dict]] = {}
        self._newest_root: Dict[str, int] = {}
        self._done = 0
        # a held round whose chain was found lost -> how far the workers
        # were then and how many of its events had come; and whether a
        # thread is giving rounds out right now
        self._lost_at: Dict[int, tuple] = {}
        self._finishing = False
        # the finished rounds' reports, the ``round.path`` instants as
        # they come back, and the events of no round (failover and
        # eviction marks): small, and bounded each
        self._reports: Deque[dict] = collections.deque(maxlen=MAX_REPORTS)
        self._loose: Deque[dict] = collections.deque(maxlen=MAX_LOOSE)
        self._tracer = None
        # sibling collectors (the metrics collector's perfetto counter
        # tracks) contribute events to the merged timeline through here
        self.extra_event_sources: List = []
        # the scheduler's PS app is shared with the other telemetry
        # collectors — one endpoint routes frames by Ctrl head
        self._endpoint = get_endpoint(postoffice).acquire()
        self._endpoint.route(Ctrl.TRACE_REPORT, self._on_msg)

    def _on_msg(self, msg):
        body = msg.body if isinstance(msg.body, dict) else {}
        self.ingest(body)

    def ingest(self, body: dict) -> None:
        node = str(body.get("node", "?"))
        spans = body.get("spans") or ()
        with self._mu:
            self.events_received += len(spans)
            if self._keep:
                self._events.extend(spans)
            for ev in spans:
                tid = ev.get("args", {}).get("trace_id", 0)
                if tid <= 0 or ev.get("name") == PATH_EVENT:
                    self._loose.append(ev)
                elif tid <= self._done:
                    self.late_events += 1
                else:
                    self._by_round.setdefault(tid, []).append(ev)
                    if (ev.get("name") == ROOT_SPAN
                            and ev.get("cat") == "round"):
                        pid = ev.get("pid", "?")
                        self._newest_root[pid] = max(
                            self._newest_root.get(pid, 0), tid)
            offs = body.get("offsets")
            if offs:
                self._offsets[node] = {str(k): float(v)
                                       for k, v in offs.items()}
            self.reports_received += 1
            if self._finishing:
                return      # the thread that is at it looks again
            self._finishing = True
        try:
            while True:
                with self._mu:
                    ready = self._next_ready_locked()
                    if ready is None:
                        # cleared under the lock that found nothing
                        # ready: a report that lands now looks itself
                        self._finishing = False
                        return
                self._finish_round(*ready)
        except BaseException:
            # whatever escapes (``_finish_round`` lets nothing out, so an
            # interrupt of the delivering thread): rounds go on being
            # given out by the next report
            with self._mu:
                self._finishing = False
            raise

    def _next_ready_locked(self) -> Optional[tuple]:
        """``(trace id, its events, behind, forced)`` of the oldest held
        round if it can be given out: every worker has closed a later
        root (``behind`` is the oldest of the workers' newest roots),
        and, where the round's chain was found lost, more of its events
        have come or a worker has closed another root since (a server
        ships by the batch, and a node that sees only some rounds, like
        the global server under HFA with ``hfa_k2 > 1``, late); or,
        forced, ``MAX_HELD_ROUNDS`` newer ones are held already (a
        worker that never closes a later round must not make the others'
        pile up).  In order: a round that waits holds the later ones."""
        if not self._by_round:
            return None
        tid = min(self._by_round)
        behind = min(self._newest_root.values(), default=0)
        forced = len(self._by_round) > MAX_HELD_ROUNDS
        events = self._by_round[tid]
        if not forced and (tid >= behind or self._lost_at.get(tid)
                           == (behind, len(events))):
            return None
        return tid, list(events), behind, forced

    def _finish_round(self, tid: int, events: List[dict], behind: int,
                      forced: bool) -> None:
        """Round ``tid`` is whole: its report is kept, its chain goes
        out as one ``round.path`` instant of this node's tracer (so also
        ``geomx:<global scheduler>:round.path`` under a live profiler
        session), fields in microseconds (``path_fields``), and its
        events go.  A chain that is lost (the events do not say what
        sent a message) waits for the rest of the round's events: it is
        tried again when more of them have come or the workers have
        closed another round, until it is forced out.  Never raises: it runs on the thread that
        delivered a node's report, during shutdown too."""
        report = chain = None
        try:
            report, chain = _round_report(tid, self._rebased(events))
        except Exception:
            self.path_errors += 1
            _log.warning("round %d: no chain", tid - 1, exc_info=True)
        with self._mu:
            if chain is not None and chain["lost"] and not forced:
                self._lost_at[tid] = (behind, len(events))
                return
            now = self._by_round.pop(tid, ())
            self.late_events += max(0, len(now) - len(events))
            self._lost_at.pop(tid, None)
            self._done = max(self._done, tid)
            if report is not None:
                self._reports.append(report)
        if chain is None:
            return
        try:
            if self._tracer is None:
                from geomx_tpu.trace.recorder import get_tracer

                self._tracer = get_tracer(self.node)
            self._tracer.instant(PATH_EVENT, trace_id=tid,
                                 **path_fields(chain))
        except Exception:
            self.path_errors += 1
            _log.warning("round %d: no round.path", tid - 1, exc_info=True)

    def held_events(self) -> int:
        """Events the collector holds right now, whatever for."""
        with self._mu:
            return (len(self._events) + len(self._loose)
                    + sum(len(v) for v in self._by_round.values()))

    # ---- clock-offset resolution -------------------------------------------
    def _resolve_offsets(self) -> Dict[str, float]:
        """Per-node offset to the global scheduler's clock (seconds)."""
        with self._mu:
            offs = {n: dict(o) for n, o in self._offsets.items()}
        out = resolve_clock_offsets(
            offs, str(self.po.topology.global_scheduler()))
        out.setdefault(self.node, 0.0)
        return out

    # ---- merge --------------------------------------------------------------
    def merged_events(self) -> List[dict]:
        """The collected events, timestamps rebased onto the global
        scheduler's clock (``ts`` in µs from the earliest event): every
        one where a dump was asked for (``Config.trace_dir``; the newest
        ``MAX_EVENTS``), else those of the rounds not given out yet, the
        ``round.path`` instants and the events of no round."""
        with self._mu:
            if self._keep:
                events = list(self._events)
            else:
                events = list(self._loose)
                for evs in self._by_round.values():
                    events.extend(evs)
        for src in list(self.extra_event_sources):
            try:
                events.extend(src())
            except Exception:  # a sibling mid-stop must not break dumps
                pass
        out = self._rebased(events)
        if out:
            t_min = out[0]["ts"]
            for e in out:
                e["ts"] -= t_min
        return out

    def _rebased(self, events: List[dict]) -> List[dict]:
        """Copies of ``events`` on the global scheduler's clock (``ts``
        in µs of its monotonic clock), in order."""
        offsets = self._resolve_offsets()
        out = []
        for ev in events:
            off_us = offsets.get(ev.get("pid", "?"), 0.0) * 1e6
            e = dict(ev)
            e["ts"] = ev.get("args", {}).get(
                "t_mono_us", ev.get("ts", 0.0)) + off_us
            out.append(e)
        out.sort(key=lambda e: e["ts"])
        return out

    def merged_trace(self) -> dict:
        """Chrome-trace/perfetto JSON of the whole deployment: one
        ``pid`` per node, spans linked by args.span/args.parent."""
        return {"traceEvents": self.merged_events(),
                "displayTimeUnit": "ms",
                "otherData": {"clock_offsets_s": self._resolve_offsets()}}

    def dump(self, path: str) -> dict:
        trace = self.merged_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return trace

    # ---- critical path ------------------------------------------------------
    def critical_path(self) -> dict:
        """Per-round blocking chain + straggler attribution
        (:func:`_round_report`): the finished rounds' reports as they
        were kept (the newest ``MAX_REPORTS``), then one for each round
        still held, marked ``held`` (some node's part of it may not have
        come: ``control/signals.py`` passes such a round over).  Where a dump was asked for, every round of the kept
        timeline is reported afresh, late events included."""
        with self._mu:
            if self._keep:
                done: List[dict] = []
                by_round: Dict[int, List[dict]] = {}
                for ev in self._events:
                    tid = ev.get("args", {}).get("trace_id", 0)
                    if tid > 0:
                        by_round.setdefault(tid, []).append(ev)
            else:
                done = list(self._reports)
                by_round = {t: list(v) for t, v in self._by_round.items()}
            received = self.events_received
            given_out = self._done
        rounds = done
        for t in sorted(by_round):
            r = _round_report(t, self._rebased(by_round[t]))[0]
            if t > given_out:
                r["held"] = True    # not whole yet: may lack a node's part
            rounds.append(r)
        return {"rounds": rounds, "num_events": received,
                "clock_offsets_s": self._resolve_offsets()}

    def report_text(self) -> str:
        """Human-readable summary, two lines a round: the blocking chain
        by label, then the stages by their share of it."""
        cp = self.critical_path()
        lines = []
        for r in cp["rounds"]:
            chain = ", ".join(f"{label}={us / 1e3:.1f}ms"
                              for label, us in list(r["path"].items())[:8])
            stages = ", ".join(
                f"{s}={st['path_us'] / 1e3:.1f}ms"
                + (f"(on {st['path_node']})" if st.get("path_node") else "")
                for s, st in sorted(r["stages"].items(),
                                    key=lambda kv: -kv[1]["path_us"])
                if st["path_us"])
            shard = (f" slowest_shard={r['slowest_shard']}"
                     if "slowest_shard" in r else "")
            lines.append(
                f"round {r['round']}: wall={r['wall_us'] / 1e3:.1f}ms "
                f"dominant={r['dominant_stage']}{shard} chain [{chain}]")
            lines.append(f"  stages on the chain [{stages}]")
        return "\n".join(lines)

    def stop(self):
        self._endpoint.release()
