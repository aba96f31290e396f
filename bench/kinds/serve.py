"""Serving cells: the program's ``ServeEngine`` (continuous batching over a
fixed slot pool, bucketed batched prefill, active-slot decode) on seeded
weights, offered an open-loop schedule in wall seconds.

Set-up builds the weights on the device in one call, builds the engine, and
runs every prefill (bucket x batch) and decode shape this cell's traffic can
reach, so nothing compiles in the window. The window submits each request
when it is due and ticks the engine whenever it holds work; the tick ends in
the engine's host read of the sampled tokens, so a token is stamped when the
tick that produced it returns. After the window closes the engine keeps
ticking until every request due in the window is answered (at most a minute
more); a request that never answers counts as failed. Then, with the engine
freed, the plain reference checks a seeded sample of the answers.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from kinds.program import model_config
from repro.serving.engine import Request, ServeEngine
from yardstick import loadgen, serve_ref, stats, weights
from yardstick import trace as tr
from yardstick.device import memory_peak_bytes

GRACE_S = 60.0  # how long past the window's close an answer may still come
TRACE_S = 8.0  # a traced run measures a short window of its own


class Run:
    """What one serving run measured, for the metric readers."""

    kind = "serve"


def _buckets(traffic: dict, eng: dict) -> list[int]:
    b = eng["prompt_bucket"]
    lo = -(-traffic["prompt_min"] // b) * b
    hi = min(-(-traffic["prompt_max"] // b) * b, eng["cache_len"])
    return list(range(lo, hi + 1, b))


def warm_up(engine, traffic: dict, eng: dict, vocab: int) -> None:
    """Run every program the traffic can reach: a prefill for each bucket at
    each power-of-two batch up to the slot count, then decodes at every
    occupancy from all slots down to one."""
    rng = np.random.default_rng(0)
    slots = eng["max_slots"]
    bpads = [1 << i for i in range(slots.bit_length()) if (1 << i) <= slots]
    for bucket in _buckets(traffic, eng):
        for bpad in bpads:
            for _ in range(bpad):
                engine.submit(Request(prompt=rng.integers(0, vocab, bucket).tolist(),
                                      max_new_tokens=1))
            engine.step()
    for k in range(slots):
        engine.submit(Request(prompt=rng.integers(0, vocab, traffic["prompt_min"]).tolist(),
                              max_new_tokens=2 + k))
    while engine.pending or engine.active:
        engine.step()


def run(cell, seed: int, seconds: float, trace_dir, devices, t0: float) -> dict:
    conf, traffic = cell.config, cell.traffic
    model, eng = conf["model"], conf["engine"]
    cfg = model_config(cell.workload["config"], model)
    wkey = jax.random.fold_in(weights.seed_key(seed), 0)
    make = jax.jit(lambda k: weights.make(model, k))
    params = make(jax.device_put(wkey, devices[0]))
    engine = ServeEngine(cfg, params, max_slots=eng["max_slots"],
                         cache_len=eng["cache_len"], prompt_bucket=eng["prompt_bucket"],
                         prefix_cache=eng["prefix_cache"])
    warm_up(engine, traffic, eng, model["vocab_size"])
    rate = cell.workload["rate_rps"]
    if trace_dir is not None:  # a traced run measures a short window of its own
        seconds = min(seconds, TRACE_S)
    sched = loadgen.schedule(traffic, rate, seconds, seed, model["vocab_size"])

    reqs = [Request(prompt=a.prompt.tolist(), max_new_tokens=a.max_new) for a in sched]
    stamps = [[] for _ in sched]  # host time each token reached the host
    submit_t = [None] * len(sched)
    ticks = []  # (start, end, admitted, contexts before the tick)
    live = {}  # index -> tokens seen
    nxt = 0
    traced = trace_dir is not None
    if traced:
        tr.start(trace_dir)
    setup_s = time.perf_counter() - t0
    w0 = time.perf_counter()
    win = TraceAnnotation(tr.WINDOW)
    win.__enter__()
    while True:
        now = time.perf_counter() - w0
        if traced and now >= seconds:
            win.__exit__(None, None, None)
            tr.stop()
            traced = False
        with TraceAnnotation("bench.loadgen"):
            due = []
            while nxt < len(sched) and sched[nxt].t <= now:
                due.append(nxt)
                nxt += 1
        for i in due:
            with TraceAnnotation("bench.submit"):
                engine.submit(reqs[i])
            submit_t[i] = time.perf_counter() - w0
            live[i] = 0
        if engine.pending or engine.active:
            ctx = [int(engine.pos[s]) for s in engine.active]
            t_a = time.perf_counter() - w0
            with TraceAnnotation("bench.engine_step", tick=len(ticks)):
                engine.step()
            t_b = time.perf_counter() - w0
            plens, new = [], 0
            for i in list(live):
                n = len(reqs[i].output)
                if n > live[i]:
                    if live[i] == 0:
                        plens.append(len(reqs[i].prompt))
                    stamps[i].extend([t_b] * (n - live[i]))
                    new += n - live[i]
                    live[i] = n
                if reqs[i].done:
                    del live[i]
            ticks.append({"t0": t_a, "t1": t_b, "plens": plens, "ctx": ctx,
                          "decoded": new - len(plens)})
        elif nxt < len(sched):
            time.sleep(max(0.0, min(sched[nxt].t - (time.perf_counter() - w0), 0.05)))
        elif not live:
            break
        if now > seconds + GRACE_S:
            break
    if traced:
        win.__exit__(None, None, None)
        tr.stop()

    r = Run()
    r.model, r.traffic, r.engine, r.devices, r.seconds = model, traffic, eng, devices, seconds
    r.ticks, r.window_s = ticks, seconds
    r.lateness = [submit_t[i] - sched[i].t for i in range(len(sched)) if submit_t[i] is not None]
    r.memory_peak_bytes = memory_peak_bytes(devices)
    ok = [i for i in range(len(sched)) if reqs[i].done and len(stamps[i]) == sched[i].max_new]
    # a request never answered waited at least until the run ended
    end = time.perf_counter() - w0
    ttft = [(stamps[i][0] if stamps[i] else end) - sched[i].t for i in range(len(sched))]
    gaps = [b - a for st in stamps for a, b in zip(st, st[1:]) if b <= seconds]
    admit_ends = {t["t1"] for t in ticks if t["plens"]}
    n_admit = sum(b in admit_ends for st in stamps for b in st[1:] if b <= seconds)
    answers = [(reqs[i].prompt, list(reqs[i].output)) for i in ok]
    del engine, params, reqs
    gc.collect()

    sample = serve_ref.sample(answers, seed, traffic["check_tokens"])
    numbers = {"token_gap": serve_ref.token_gap(model, wkey, sample, devices[0])}
    e2e = {
        "setup_s": setup_s,
        "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
        "itl_p50_ms": 1e3 * stats.percentile(gaps or [end], 50),
        "itl_p99_ms": 1e3 * stats.percentile(gaps or [end], 99),
        "serve_tokens_per_s": sum(t <= seconds for st in stamps for t in st) / seconds,
    }
    # where the gap percentiles fall: the share of gaps closed by a tick that admitted
    diag = {"gaps": len(gaps), "admit_gap_share": n_admit / max(len(gaps), 1),
            **{f"itl_p{q}_ms": 1e3 * stats.percentile(gaps or [end], q) for q in (90, 95)}}
    return {"setup_s": setup_s, "attempted": len(sched), "failed": len(sched) - len(ok),
            "e2e": e2e, "numbers": numbers, "run": r, "sample": sample, "diag": diag}
