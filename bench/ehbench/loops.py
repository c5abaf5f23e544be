"""The two ways a traffic mix drives the engine, and the checks each
makes of what it produced.

``repeat``: one client runs a whole-graph program back to back through
``Engine.query``.  Each query gets an empty result cache, so every one
executes; a program whose rules share a bag still hits that cache
within one query, as often as the warm-up query does.  The window
starts after one warm-up query and ends when the last query started
within it completes.

``open``: an open loop of requests due at Poisson arrival times, each a
prepared query bound to a vertex drawn from a Zipfian popularity.  The
loop admits every request that has come due with ``QueryServer.submit``,
then calls ``QueryServer.drain()``, and repeats.  A request's latency
runs from its due time to the end of the drain that answered it.
"""
from __future__ import annotations

import time

import numpy as np

from ehbench import traffic as traffic_mod
from ehbench.common import (Check, NoChip, RunRecord, counters_delta, log,
                            now, span)


def _load(engine_load, alias, rel: dict, graph,
          stream: np.random.Generator) -> None:
    """Hand the loader the edge list in an order drawn from the run's
    seed."""
    order = stream.permutation(graph.m)
    engine_load(rel["name"], graph.sources()[order], graph.neighbors[order])
    for a in rel.get("aliases", ()):
        alias(a, rel["name"])


def _check_backend(backend, require_tpu: bool) -> None:
    if require_tpu and getattr(backend, "_interpret", True):
        raise NoChip("the engine's Pallas kernels would run in interpret "
                     "mode")


# ------------------------------------------------------------------ repeat
def repeat(*, cfg, traffic, graph, seed, seconds, t_start, compiles,
           tracer, require_tpu, fault, rng, answer) -> dict:
    from repro.core.engine import Engine
    from repro.core.executor import BagResultCache

    eng = Engine(backend="device")
    _check_backend(eng.backend, require_tpu)
    _load(eng.load_edges, eng.alias, cfg["relation"], graph, rng(seed, 1))
    if fault is not None:
        fault(eng)
    text = traffic["query"]

    def one():
        eng.bag_cache = BagResultCache()
        launches = eng.backend.stats["pipeline.launches"]
        t0 = now()
        with span("bench.query"):
            res = eng.query(text)
            got = answer(res)
        dt = now() - t0
        ran = eng.backend.stats["pipeline.launches"] > launches
        return got, dt, eng.bag_cache.hits, ran

    with span("bench.warmup"):
        warm = one()
    setup_s = now() - t_start
    log(f"[setup] setup_s={setup_s:.6f} warmup_query_s={warm[1]:.6f}")

    stats0, comp0 = dict(eng.backend.stats), compiles.n
    answers, durs, hits, carried = [], [], 0, 0
    start = now()
    while now() - start < seconds:
        got, dt, h, ran = one()
        answers.append((got, ran))
        durs.append(dt)
        hits += h
        # hits beyond the program's own sharing within one query
        carried += max(0, h - warm[2])
    window_s = now() - start
    counters = counters_delta(stats0, eng.backend.stats)
    counters["bag_cache.hits"] = hits
    counters["bag_cache.carried_hits"] = carried
    counters["compiles"] = compiles.n - comp0
    log(f"[window] queries={len(durs)} window_s={window_s:.6f} "
        f"query_s min/median/max={min(durs):.6f}/"
        f"{float(np.median(durs)):.6f}/{max(durs):.6f} counters={counters}")

    traced = 0
    if tracer is not None:
        with tracer:
            got, dt, h, ran = one()
        answers.append((got, ran))
        traced = 1
        log(f"[trace] traced_query_s={dt:.6f}")

    record = RunRecord(loop="repeat", setup_s=setup_s, window_s=window_s,
                       completed=len(durs), latencies_s=durs, waits_s=[],
                       counters=counters, traced_units=traced)

    def release():
        nonlocal eng
        eng = None

    return {"record": record, "answers": answers, "release": release,
            "attempted": len(answers), "failed": 0}


def check_repeat(out: dict, graph, ref) -> list[Check]:
    """Every answer of the run against the reference's.  A count is
    compared exactly (``count_gap``); a keyed answer, where the
    reference defines ``compare``, entry by entry under the reference's
    own tolerance (``entry_gap``: the worst query's keys missing or
    extra plus values outside it)."""
    t0 = time.perf_counter()
    want = ref.reference(graph)
    compare = getattr(ref, "compare", None)
    answers = out["answers"]
    if compare is None:
        gap_name = "count_gap"
        gaps = [abs(got - want) for got, _ in answers]
        summary = (f"reference={want} "
                   f"answers={sorted(set(got for got, _ in answers))}")
    else:
        gap_name = "entry_gap"
        # a control hands every query the same arrays: compare them once
        # (``answers`` keeps each alive, so no id is reused)
        distinct = {tuple(map(id, got)): got for got, _ in answers}
        gap_of = {k: compare(got, want) for k, got in distinct.items()}
        gaps = [gap_of[tuple(map(id, got))] for got, _ in answers]
        summary = (f"reference_keys={len(want[0])} answers={len(answers)} "
                   f"entry_gaps={sorted(set(gaps))} "
                   f"max_rel_err={max_rel_err(distinct.values(), want)}")
    wrong = sum(g != 0 for g in gaps)
    unlaunched = sum(not ran for _, ran in answers)
    out["failed"] = sum(g != 0 or not ran
                        for g, (_, ran) in zip(gaps, answers))
    c = out["record"].counters
    log(f"[check] {summary} reference_s={time.perf_counter() - t0:.6f}")
    return [Check(gap_name, max(gaps), 0),
            Check("wrong_queries", wrong, 0),
            Check("unlaunched_queries", unlaunched, 0),
            Check("cache_hits", c.get("bag_cache.carried_hits", 0), 0),
            Check("host_syncs", c.get("extend.host_syncs", 0), 0),
            Check("host_rounds", c.get("recursion.host_rounds", 0), 0)]


def max_rel_err(answers, want) -> float | None:
    """Largest relative gap of a value over the keyed answers whose keys
    are the reference's, for the log; None where no answer's keys are."""
    keys, vals = want
    with np.errstate(divide="ignore", invalid="ignore"):
        errs = [np.max(np.abs(got[1] - vals) / np.abs(vals), initial=0.0)
                for got in answers if np.array_equal(got[0], keys)]
    return float(max(errs)) if errs else None


# -------------------------------------------------------------------- open
class Server:
    """A loaded ``QueryServer`` with the traffic mix's prepared query
    and its popularity order, warmed to steady state."""

    def __init__(self, *, cfg, traffic, graph, seed, require_tpu, fault,
                 rng):
        from repro.serve import QueryServer

        self.srv = QueryServer(backend="device")
        _check_backend(self.srv.backend, require_tpu)
        self.tenant = cfg["tenant"]
        self.text = traffic["query"]
        self.traffic = traffic
        srv, tenant = self.srv, self.tenant
        _load(lambda n, s, d: srv.load_graph(tenant, n, s, d),
              lambda a, n: srv.alias(tenant, a, n), cfg["relation"], graph,
              rng(seed, 1))
        if fault is not None:
            fault(srv)
        # popularity: an order over the vertices that have edges, fixed
        # by the mix's schedule seed and independent of degree
        self.fixed = int(traffic["schedule_seed"])
        self.population = rng(self.fixed, 3).permutation(
            np.flatnonzero(graph.degrees > 0))
        self.largest = int(self.population[
            np.argmax(graph.degrees[self.population])])
        self.rng = rng

    def draw(self, size: int, stream: np.random.Generator) -> np.ndarray:
        ranks = traffic_mod.zipf_ranks(len(self.population),
                                       self.traffic["zipf_theta"], size,
                                       stream)
        return self.population[ranks]

    def schedule(self, rate: float, seconds: float,
                 order: np.random.Generator):
        """Due times and bindings: the mix's fixed set of requests for
        ``rate`` and ``seconds``, gaps and bindings each in an order
        drawn from ``order``."""
        template = self.rng(self.fixed, 4)
        due = traffic_mod.arrival_times(rate, seconds, template, order)
        binds = self.draw(len(due), template)
        return due, binds[order.permutation(len(binds))]

    def warm_up(self, requests: int, bindings, compiles) -> int:
        """Bring the server to steady state: its capacity feedback has
        seen the largest binding of the population (a long-running
        server has), every batch size up to ``warm_batch`` has run once,
        and each distinct binding of ``requests`` draws from the mix's
        own distribution (a fixed draw, independent of the schedule) has
        run once.  Then each distinct binding of ``bindings`` (the run's
        schedule) not met so far runs once, so that nothing compiles in
        the window: the engine compiles small programs for each result
        length it has not met (``GenericJoin._project``).  Returns the
        compilations that last step made: those the window's requests
        would have made in a server that had met only the mix's
        distribution."""
        stream = self.rng(self.fixed, 5)
        with span("bench.warmup"):
            self.batch([self.largest])
            for b in range(1, int(self.traffic["warm_batch"]) + 1):
                self.batch(self.draw(b, stream))
            met = np.unique(self.draw(int(requests), stream))
            for v in met:
                self.batch([v])
            c0 = compiles.n
            for v in np.setdiff1d(np.asarray(bindings, np.int64), met):
                self.batch([v])
        return compiles.n - c0

    def batch(self, bindings) -> None:
        for v in bindings:
            self.srv.submit(self.tenant, self.text, int(v))
        self.srv.drain()

    def window(self, due: np.ndarray, binds: np.ndarray) -> dict:
        """Drive the open loop over one schedule; returns per-request
        latency and wait (seconds from due time), tickets and batch
        sizes.  The loop ends when every request is answered."""
        n = len(due)
        lat = np.zeros(n)
        wait = np.zeros(n)
        late = np.zeros(n)
        tickets = [None] * n
        sizes = []
        srv, tenant, text = self.srv, self.tenant, self.text
        i = 0
        start = now()
        while i < n:
            t = now() - start
            if due[i] > t:
                time.sleep(due[i] - t)
                continue
            first = i
            with span("bench.admit"):
                while i < n and due[i] <= now() - start:
                    late[i] = now() - start - due[i]
                    tickets[i] = srv.submit(tenant, text, int(binds[i]))
                    i += 1
            d0 = now() - start
            with span("bench.drain"):
                srv.drain()
            d1 = now() - start
            wait[first:i] = d0 - due[first:i]
            lat[first:i] = d1 - due[first:i]
            sizes.append(i - first)
        return {"latency": lat, "wait": wait, "late": late,
                "tickets": tickets, "sizes": sizes, "binds": binds,
                "end": now() - start}


def open_loop(*, cfg, traffic, graph, seed, seconds, t_start, compiles,
              tracer, require_tpu, fault, rng, answer) -> dict:
    server = Server(cfg=cfg, traffic=traffic, graph=graph, seed=seed,
                    require_tpu=require_tpu, fault=fault, rng=rng)
    rate = float(traffic["rate"])
    due, binds = server.schedule(rate, seconds, rng(seed, 4))
    if tracer is not None:
        tdue, tbinds = server.schedule(
            rate, float(traffic["trace_seconds"]), rng(seed, 6))
    else:
        tbinds = ()
    first = server.warm_up(len(due), np.concatenate([binds, tbinds]),
                           compiles)
    setup_s = now() - t_start
    log(f"[setup] setup_s={setup_s:.6f} requests={len(due)} rate={rate}")

    backend = server.srv.backend
    stats0, comp0 = dict(backend.stats), compiles.n
    w = server.window(due, binds)
    counters = counters_delta(stats0, backend.stats)
    counters["compiles"] = compiles.n - comp0
    counters["compiles.first_serve"] = first
    eng = server.srv.engine(server.tenant)
    counters["bag_cache.hits"] = eng.bag_cache.hits
    counters["bag_cache.misses"] = eng.bag_cache.misses
    sizes = w["sizes"]
    log(f"[window] requests={len(due)} drains={len(sizes)} "
        f"max_batch={max(sizes, default=0)} end_s={w['end']:.6f} "
        f"late_p99_ms={np.percentile(w['late'], 99) * 1e3 if len(due) else 0:.3f} "
        f"counters={counters}")
    runs = [w]
    traced = 0
    if tracer is not None:
        with tracer:
            runs.append(server.window(tdue, tbinds))
        traced = len(tdue)

    record = RunRecord(loop="open", setup_s=setup_s,
                       window_s=max(seconds, w["end"]),
                       completed=sum(t is not None and t.done
                                     for t in w["tickets"]),
                       latencies_s=list(w["latency"]),
                       waits_s=list(w["wait"]), counters=counters,
                       traced_units=traced)
    answers = []
    for r in runs:
        for t, v in zip(r["tickets"], r["binds"]):
            got = answer(t.result) if t is not None and t.done else None
            answers.append((int(v), got))

    def release():
        nonlocal server
        server = None

    return {"record": record, "answers": answers, "release": release,
            "attempted": len(answers), "failed": 0}


def check_open(out: dict, graph, ref) -> list[Check]:
    t0 = time.perf_counter()
    want = ref.per_vertex(graph, [v for v, _ in out["answers"]])
    missing = sum(got is None for _, got in out["answers"])
    gaps = [abs(got - want[v]) for v, got in out["answers"]
            if got is not None]
    wrong = sum(g != 0 for g in gaps)
    out["failed"] = wrong + missing
    log(f"[check] answers={len(out['answers'])} distinct={len(want)} "
        f"reference_s={time.perf_counter() - t0:.6f}")
    return [Check("answer_gap", max(gaps, default=0), 0),
            Check("wrong_answers", wrong, 0),
            Check("unanswered", missing, 0)]


LOOPS = {"repeat": repeat, "open": open_loop}
CHECKS = {"repeat": check_repeat, "open": check_open}
