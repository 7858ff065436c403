"""Plain reference for what a replay job computes.

An independent, straightforward implementation of the semantics of the
program's `MultiQuerySimulator.run` for the strategy the cells use (the
adaptive ``dyskew`` link), written from
the paper's description and the program's documented event order.  It
imports nothing of the program and takes nothing the program made: it
reads the generated queries (`bench.gen.Query`) and the strategy dicts
of the traffic file.

Semantics, in brief.  One heap of events ordered by (virtual time,
push order).  Each query's producers emit their batches one after the
other; a batch is routed when it arrives, and the producer's next batch
arrives one scan gap later, plus a credit-based backpressure delay.
Rows travel to a worker (in-process, over the node's IPC, or over the
source node's serialised NIC uplink) and queue in the worker's FIFO; a
worker serves 16 rows at a time.  A query with an adaptive link ticks
its link state machines every ``tick_interval`` from its arrival while
it has work (plus one last tick), and routes a batch remotely only
while the producer's link says so, by a waterfill over its observed
backlog behind the Row Size Model's density guard and the cost gate.
The heap runs until it is empty, with no shortcut: the closed-form
drain, the coalesced arrival runs and the batched tick of the program
are optimisations that must not change any of this.

The link state machine (paper Fig. 2) is kept in numpy float32, as the
program keeps it on the device.  Times and costs are float64; with
``precision="float32"`` they are computed in float32 instead, which is
the benchmark's control: a reference one precision below the stated
one, which the comparison must refuse.

Link state is snapshotted at the instant the last batch of the whole
run has been routed: after it no routing happens, so no later tick can
change a result, and that is the state the program hands back.
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

TICK, ARRIVAL, ENQUEUE, DONE = 0, 1, 2, 3
SERVICE_CHUNK = 16

INIT, DECIDING, DRAINING, DISTRIBUTING, LOCAL_TERMINAL, DISTRIBUTED_TERMINAL = (
    range(6)
)
NEVER, LATE, EARLY, EAGER_SNOWPARK = 0, 1, 2, 3
POLICIES = {"NEVER": NEVER, "LATE": LATE, "EARLY": EARLY,
            "EAGER_SNOWPARK": EAGER_SNOWPARK}

F32 = np.float32


def _remote(state: np.ndarray) -> np.ndarray:
    return (state == DISTRIBUTING) | (state == DISTRIBUTED_TERMINAL)


class Links:
    """Link state machines of T queries x n sibling producers, one shared
    configuration, float32 metrics."""

    def __init__(self, cfg: Dict, num: int, n: int):
        self.cfg = cfg
        self.n = n
        w = int(cfg["slope_window"])
        self.state = np.zeros((num, n), np.int32)
        self.strikes = np.zeros((num, n), np.int32)
        self.rows = np.zeros((num, n), F32)
        self.idle = np.zeros((num, n), F32)
        self.window = np.zeros((num, n, w), F32)
        self.density = np.zeros((num, n), F32)
        self.bpr = np.zeros((num, n), F32)
        self.transitions = np.zeros((num, n), np.int32)
        self.ticks = np.zeros(num, np.int32)

    def snapshot(self, r: int) -> Dict[str, np.ndarray]:
        return {
            "state": self.state[r].copy(),
            "strikes": self.strikes[r].copy(),
            "metrics.rows": self.rows[r].copy(),
            "metrics.idle_ticks": self.idle[r].copy(),
            "metrics.sync_window": self.window[r].copy(),
            "metrics.batch_density": self.density[r].copy(),
            "metrics.bytes_per_row": self.bpr[r].copy(),
            "transitions": self.transitions[r].copy(),
            "tick": self.ticks[r].copy(),
        }

    def _idle_skew(self, idle: np.ndarray) -> np.ndarray:
        cfg, n = self.cfg, self.n
        is_idle = idle >= cfg["idle_grace"]
        if n <= 1:
            return np.zeros_like(is_idle)
        idle_f = is_idle.astype(F32)
        siblings = idle_f.sum(axis=1, keepdims=True, dtype=F32) - idle_f
        return ~is_idle & (siblings >= F32(cfg["idle_sibling_frac"] * (n - 1)))

    def _others_mean(self, x: np.ndarray) -> np.ndarray:
        n = self.n
        if n <= 1:
            return np.full_like(x, np.inf)
        return (x.sum(axis=1, keepdims=True, dtype=F32) - x) / F32(n - 1)

    def _skewed(self, rows, idle, window) -> np.ndarray:
        cfg = self.cfg
        model = cfg["skew_model"]
        theta = F32(cfg["theta"])
        if model == "IDLE_TIME":
            return self._idle_skew(idle)
        if model == "ROW_PERCENTAGE":
            return rows * theta > self._others_mean(rows)
        if model == "SYNC_TIME_SLOPE":
            w = window.shape[-1]
            t = np.arange(w, dtype=F32)
            t = t - t.mean(dtype=F32)
            denom = max((t * t).sum(dtype=F32), F32(1e-9))
            centered = window - window.mean(axis=-1, keepdims=True, dtype=F32)
            slopes = (centered * t).sum(axis=-1, dtype=F32) / denom
            return (slopes * theta >= self._others_mean(slopes)) & (
                slopes > F32(1e-9))
        raise ValueError(f"unknown skew model {model!r}")

    def tick(self, r: np.ndarray, rows_in, sync_in, density, bpr,
             signal) -> np.ndarray:
        """Advance rows ``r`` by one tick; returns their distribute masks."""
        cfg = self.cfg
        rows_in = rows_in.astype(F32)
        rows = self.rows[r] + rows_in
        received = (rows_in > 0) | signal
        idle = np.where(received, F32(0.0), self.idle[r] + F32(1.0))
        old_window = self.window[r]
        new_cum = old_window[:, :, -1] + sync_in.astype(F32)
        window = np.concatenate([old_window[:, :, 1:], new_cum[:, :, None]],
                                axis=-1)
        density = density.astype(F32)
        bpr = bpr.astype(F32)

        state = self.state[r]
        strikes = self.strikes[r]
        skewed = self._skewed(rows, idle, window)
        skew_strikes = np.where(skewed, strikes + 1, 0).astype(np.int32)
        deciding = state == DECIDING
        fire = (skew_strikes >= cfg["n_strikes"]) & deciding
        distributing = state == DISTRIBUTING
        clean_strikes = np.where(~skewed, strikes + 1, 0).astype(np.int32)
        clean_fire = clean_strikes >= cfg["n_strikes"]
        new_strikes = np.where(
            deciding, skew_strikes,
            np.where(distributing, clean_strikes, 0)).astype(np.int32)

        policy = POLICIES[cfg["policy"]]
        new = state.copy()
        if policy == NEVER:
            new[state == INIT] = LOCAL_TERMINAL
        elif policy == LATE:
            new[state == INIT] = DECIDING
            new[deciding & fire] = DRAINING
            new[state == DRAINING] = DISTRIBUTING
            if cfg["looping"]:
                new[distributing & clean_fire] = DECIDING
            else:
                new[distributing] = DISTRIBUTED_TERMINAL
        elif policy == EARLY:
            new[state == INIT] = DISTRIBUTING
            new[distributing] = DISTRIBUTED_TERMINAL
        elif policy == EAGER_SNOWPARK:
            min_density = F32(
                cfg["target_batch_density"] * cfg["min_batch_density_frac"])
            heavy = (
                ~self._idle_skew(idle)
                & (density > 0) & (density < min_density)
                & (bpr >= F32(cfg["heavy_row_bytes"]))
            )
            new[state == INIT] = DISTRIBUTING
            new[distributing & heavy] = LOCAL_TERMINAL
        else:
            raise ValueError(f"unknown policy {cfg['policy']!r}")

        became = ~_remote(state) & _remote(new)
        self.state[r] = new
        self.strikes[r] = new_strikes
        self.rows[r] = rows
        self.idle[r] = idle
        self.window[r] = window
        self.density[r] = density
        self.bpr[r] = bpr
        self.transitions[r] += became.astype(np.int32)
        self.ticks[r] += 1
        return _remote(new)


def waterfill(backlog: np.ndarray, k: int, unit: float) -> np.ndarray:
    """Spread ``k`` rows of ``unit`` seconds over bins so the loads end
    as level as possible: the closed-form water level, floored, then
    trimmed from the most loaded bin or topped up on the least loaded."""
    n = len(backlog)
    finite = np.isfinite(backlog)
    counts = np.zeros(n, np.int64)
    if k == 0:
        return counts
    if not finite.any():
        counts[0] = k
        return counts
    low = np.sort(backlog[finite])
    levels = (k * unit + np.cumsum(low)) / np.arange(1, len(low) + 1)
    j = int(np.nonzero(levels >= low)[0][-1])
    counts = np.floor(np.maximum(levels[j] - backlog, 0.0) / unit)
    counts[~finite] = 0
    counts = counts.astype(np.int64)
    extra = int(counts.sum()) - k
    while extra > 0:
        loads = np.where(counts > 0, backlog + counts * unit, -np.inf)
        counts[int(np.argmax(loads))] -= 1
        extra -= 1
    if extra < 0:
        order = np.argsort(np.where(finite, backlog + counts * unit, np.inf))
        ne = int(finite.sum())
        i = 0
        while extra < 0:
            counts[order[i % ne]] += 1
            extra += 1
            i += 1
    return counts


def run(warehouse: Dict, queries: Sequence, strategies: Sequence[Dict],
        precision: str = "float64") -> Dict[str, list]:
    """Replay ``queries`` (each with its strategy dict) on one warehouse.

    Returns ``{"results": [...], "links": [...]}``: per query a dict of
    its results, and its link state at the end of routing."""
    if precision not in ("float64", "float32"):
        raise ValueError(f"unknown precision {precision!r}")
    R = F32 if precision == "float32" else float
    acc_dtype = F32 if precision == "float32" else np.float64

    nodes = int(warehouse["num_nodes"])
    per_node = int(warehouse["interpreters_per_node"])
    n = nodes * per_node
    node = [w // per_node for w in range(n)]
    net_bw = R(warehouse["network_bandwidth"])
    net_lat = R(warehouse["network_latency"])
    ipc_bw = R(warehouse["ipc_bandwidth"])
    ipc_lat = R(warehouse["ipc_latency"])
    ser = R(warehouse["per_row_serialize"])
    contention = bool(warehouse["model_contention"])
    window_rows = int(warehouse["flow_window_rows"])

    nq = len(queries)
    if precision == "float32":
        streams = [[[(list(c.astype(F32)), s.astype(F32)) for c, s in st]
                    for st in qy.streams] for qy in queries]
    else:
        streams = [[[(c.tolist(), s) for c, s in st] for st in qy.streams]
                   for qy in queries]
    batch_bytes = [[[R(s.sum()) for _, s in st] for st in qs]
                   for qs in streams]
    gap = [R(qy.gap) for qy in queries]
    arrival = [R(qy.arrival) for qy in queries]
    ema = [R(st["cost_ema"]) for st in strategies]
    overhead = [R(st["decision_overhead"]) for st in strategies]
    interval = [R(st["tick_interval"]) for st in strategies]
    for st in strategies:
        if st["kind"] != "dyskew":
            raise ValueError(f"no reference for strategy {st['kind']!r}")

    # Link groups: one Links per distinct configuration.
    link_of: List[tuple] = [()] * nq
    groups: Dict[tuple, List[int]] = {}
    for q, st in enumerate(strategies):
        groups.setdefault(tuple(sorted(st["dyskew"].items())), []).append(q)
    for key, members in groups.items():
        lk = Links(dict(key), len(members), n)
        for r, q in enumerate(members):
            link_of[q] = (lk, r)
    mask = [[False] * n for _ in range(nq)]

    rings = [deque() for _ in range(n)]
    running = [False] * n
    nic_free = [R(0.0)] * nodes
    est = [R(1e-3)] * nq
    outstanding = [[R(0.0)] * n for _ in range(nq)]
    recv = np.zeros((nq, n), acc_dtype)
    sync = np.zeros((nq, n), acc_dtype)
    rows_arr = np.zeros((nq, n), acc_dtype)
    batches_arr = np.zeros((nq, n), acc_dtype)
    bytes_arr = np.zeros((nq, n), acc_dtype)
    busy = [[R(0.0)] * n for _ in range(nq)]
    bytes_moved = [R(0.0)] * nq
    rows_redist = [0] * nq
    dec_overhead = [R(0.0)] * nq
    num_ticks = [0] * nq
    remaining = [sum(len(s) for s in qs) for qs in streams]
    total_remaining = sum(remaining)
    rows_total = [int(qy.rows) for qy in queries]
    rows_completed = [0] * nq
    last_done = list(arrival)
    snapshot: Optional[List] = None

    events: list = []
    seq = 0

    def push(t, kd, q, who, payload):
        nonlocal seq
        heapq.heappush(events, (t, seq, kd, q, who, payload))
        seq += 1

    for q in range(nq):
        push(arrival[q], TICK, q, 0, None)
        for p, stream in enumerate(streams[q]):
            if stream:
                push(arrival[q], ARRIVAL, q, p, 0)

    def active(q: int) -> bool:
        return remaining[q] > 0 or rows_completed[q] < rows_total[q]

    def start_worker(w: int, now):
        if running[w] or not rings[w]:
            return
        ring = rings[w]
        chunk = [ring.popleft() for _ in range(min(SERVICE_CHUNK, len(ring)))]
        total = R(0.0)
        for _, c in chunk:
            total = total + c
        running[w] = True
        push(now + total, DONE, 0, w, chunk)

    def idle_sibling_frac(p: int) -> float:
        idle = sum(1 for w in range(n)
                   if w != p and not running[w] and not rings[w])
        return idle / max(n - 1, 1)

    def plan(q: int, p: int, costs, sizes, bbytes) -> Optional[np.ndarray]:
        """Per-row destinations of one batch, or None to keep it local."""
        st = strategies[q]
        k = len(costs)
        cfg = st["dyskew"]
        if not mask[q][p]:
            return None
        bpr = bbytes / max(k, 1)
        if (
            st["enable_density_guard"]
            and k < cfg["target_batch_density"] * cfg["min_batch_density_frac"]
            and bpr >= cfg["heavy_row_bytes"]
            and idle_sibling_frac(p) < cfg["idle_sibling_frac"]
        ):
            return None
        backlog = np.asarray(outstanding[q], acc_dtype) * est[q]
        if cfg["self_skip"]:
            backlog = np.where(np.asarray(node) == node[p], np.inf, backlog)
        counts = waterfill(backlog, k, max(est[q], R(1e-9)))
        dests = np.repeat(np.arange(n), counts)
        if st["enable_cost_gate"]:
            moving = dests != p
            n_moving = int(moving.sum())
            t_move = R(sizes[moving].sum()) / net_bw + n_moving * ser
            saved = est[q] * n_moving * (1.0 - 1.0 / n)
            if not saved > cfg["cost_gate"] * t_move:
                return None
        return dests

    def route(q: int, p: int, b: int, now) -> None:
        nonlocal total_remaining
        costs, sizes = streams[q][p][b]
        k = len(costs)
        dests = plan(q, p, costs, sizes, batch_bytes[q][p][b])
        out = outstanding[q]
        if dests is None:
            push(now + k * ser, ENQUEUE, q, p, costs)
            out[p] += k
            return
        dests_np = np.asarray(dests)
        for d in np.unique(dests_np):
            d = int(d)
            sel = dests_np == d
            nrows = int(sel.sum())
            nbytes = R(sizes[sel].sum())
            seg = [c for c, s in zip(costs, sel) if s]
            if node[d] != node[p]:
                rows_redist[q] += nrows
                bytes_moved[q] += nbytes
                if contention:
                    src = node[p]
                    start = now if now > nic_free[src] else nic_free[src]
                    occupy = nbytes / net_bw
                    nic_free[src] = start + occupy
                    arrive = start + occupy + net_lat + nrows * ser
                else:
                    arrive = now + net_lat + nbytes / net_bw + nrows * ser
            elif d == p:
                arrive = now + nrows * ser
            else:
                rows_redist[q] += nrows
                arrive = now + ipc_lat + nbytes / ipc_bw + nrows * ser
            push(arrive, ENQUEUE, q, d, seg)
            out[d] += nrows

    def tick(now, qs: List[int]) -> None:
        by_links: Dict[int, List[int]] = {}
        for q in qs:
            by_links.setdefault(id(link_of[q][0]), []).append(q)
        signal = np.asarray(running, bool)[None, :]
        for members in by_links.values():
            lk = link_of[members[0]][0]
            r = np.asarray([link_of[q][1] for q in members])
            idx = np.asarray(members)
            ra, ba = rows_arr[idx], batches_arr[idx]
            density = np.where(ba > 0, ra / np.maximum(ba, 1), 0.0)
            bpr = np.where(ra > 0, bytes_arr[idx] / np.maximum(ra, 1), 0.0)
            dist = lk.tick(r, recv[idx], sync[idx], density, bpr, signal)
            for row, q in enumerate(members):
                mask[q] = dist[row].tolist()
            for acc in (recv, sync, rows_arr, batches_arr, bytes_arr):
                acc[idx] = 0.0
        for q in qs:
            num_ticks[q] += 1
            if active(q):
                push(now + interval[q], TICK, q, 0, None)

    while events:
        now, _, kd, q, who, payload = heapq.heappop(events)
        if kd == ENQUEUE:
            w = who
            ring = rings[w]
            for c in payload:
                ring.append((q, c))
            recv[q, w] += len(payload)
            start_worker(w, now)
        elif kd == DONE:
            w = who
            per_query: Dict[int, list] = {}
            for qq, c in payload:
                per_query.setdefault(qq, []).append(c)
            for qq in sorted(per_query):
                cs = per_query[qq]
                tot = R(0.0)
                for c in cs:
                    tot = tot + c
                cnt = len(cs)
                busy[qq][w] += tot
                sync[qq, w] += tot
                est[qq] = (1 - ema[qq]) * est[qq] + ema[qq] * (tot / cnt)
                left = outstanding[qq][w] - cnt
                outstanding[qq][w] = left if left > 0.0 else R(0.0)
                rows_completed[qq] += cnt
                last_done[qq] = now
            running[w] = False
            start_worker(w, now)
        elif kd == ARRIVAL:
            p, b = who, payload
            costs, _ = streams[q][p][b]
            remaining[q] -= 1
            total_remaining -= 1
            rows_arr[q, p] += len(costs)
            batches_arr[q, p] += 1
            bytes_arr[q, p] += batch_bytes[q][p][b]
            dec_overhead[q] += overhead[q]
            now = now + overhead[q]
            route(q, p, b, now)
            if b + 1 < len(streams[q][p]):
                bl = min(outstanding[q]) if mask[q][p] else outstanding[q][p]
                backpressure = max(0.0, bl - window_rows) * est[q]
                push(now + gap[q] + backpressure, ARRIVAL, q, p, b + 1)
            if total_remaining == 0 and snapshot is None:
                snapshot = [lk.snapshot(r) for lk, r in link_of]
        else:  # TICK: every tick due at this instant, in one step
            qs = [q]
            while events and events[0][0] == now and events[0][2] == TICK:
                qs.append(heapq.heappop(events)[3])
            tick(now, qs)

    if snapshot is None:
        snapshot = [lk.snapshot(r) for lk, r in link_of]
    results = []
    for q in range(nq):
        latency = max(float(last_done[q] - arrival[q]), 1e-12)
        b = np.asarray(busy[q], np.float64)
        results.append({
            "latency": latency,
            "utilization": float(b.sum() / (latency * n)),
            "bytes_moved_remote": float(bytes_moved[q]),
            "rows_redistributed": int(rows_redist[q]),
            "per_worker_busy": b,
            "decision_overhead": float(dec_overhead[q]),
            "num_ticks": int(num_ticks[q]),
        })
    return {"results": results, "links": snapshot}
