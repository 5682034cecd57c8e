"""Serving cells: the engine driven by an open-loop schedule.

The window drives ``ServeEngine.step`` exactly as a server loop would: due
requests are submitted between steps (the engine admits them at the top of
the next step, prefills each into a free slot, then runs one fused decode
chunk), and a client holds a token once the step that produced it has
synced it to the host. After the window, a sample of the finished requests
is compared with the plain reference over each prompt and its served tokens.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from benchmarks.chip import check, traffic, weights
from benchmarks.chip.record import RequestRecord, RunRecord
from benchmarks.chip.runtime import Runtime, model_config, program_model

from repro.configs.base import ShapeConfig
from repro.core.mimdram import plan_sharding
from repro.launch import mesh as mesh_lib
from repro.launch.engine import Request, ServeEngine
from repro.launch.steps import named
from repro.models.module import param_pspecs

# a request still unfinished this long after the window closed has failed
DRAIN_S = 60.0


class Server:
    """The engine and its weights for one cell, made from the seed."""

    def __init__(self, cell, seed: int, devices, rt: Runtime,
                 fault: Optional[Any] = None):
        m, e = cell.config["model"], cell.traffic["engine"]
        self.m, self.e = m, e
        self.family = cell.family()
        # the KV layout is the traffic file's: the program reads its page
        # size when it builds its programs
        page = int(e.get("kv_page_size", 0))
        os.environ["REPRO_KV_PAGES"] = str(page)
        cfg = model_config(cell.config, self.family)
        mesh = mesh_lib.make_local_mesh(("data",), devices)
        self.plan = plan_sharding(
            cfg, ShapeConfig("serve", e["prompt_bucket"] + e["max_new"],
                             e["slots"], "decode"), mesh)
        self.model = program_model(cfg)
        specs = self.model.param_specs()
        weights.check_layout(self.family, m, specs)
        self.init = weights.make_init(
            self.family, m, cell.config["init"],
            named(self.plan, param_pspecs(specs, self.plan)))
        with rt.phase("weights"):
            params = self.init(weights.seed_key(seed))
            jax.block_until_ready(params)
        self.engine = ServeEngine(
            self.model, params, self.plan, slots=e["slots"],
            prompt_len=e["prompt_bucket"], max_new=e["max_new"],
            chunk=e["chunk"], seed=seed % (2 ** 31))
        if self.engine.paged != (page > 0):
            raise ValueError(f"the engine built the wrong KV layout for "
                             f"kv_page_size {page}")
        if fault is not None:
            fault(self.engine)
        with rt.phase("warmup"):
            self.warm_up()

    def warm_up(self) -> None:
        """Compile and run every program the window uses, at its shapes: one
        full-bucket request through prefill, insert and a decode chunk."""
        eng = self.engine
        eng.submit(Request(uid=-1, tokens=np.ones(self.e["prompt_bucket"],
                                                  np.int32),
                           max_new_tokens=1))
        while eng.step():
            pass
        bad = [c for c in eng.completions if c.finish_reason == "error"]
        if bad or eng._dead:
            raise RuntimeError(f"the engine failed its warm-up: "
                               f"{bad[0].error if bad else 'engine dead'}")
        eng.completions.clear()

    def release(self) -> None:
        self.engine = None
        gc.collect()


def _stats_diff(after: Dict[str, Any], before: Dict[str, Any]) -> Dict:
    keys = ("decode_dispatches", "prefills", "tokens_out", "straggler_events",
            "shed_events", "error_completions", "retries", "queue_peak")
    return {k: after.get(k, 0) - (0 if k == "queue_peak" else
                                  before.get(k, 0)) for k in keys}


def drive(server: Server, sched: List[traffic.Arrival], seconds: float,
          rec: RunRecord, *, stop_at_close: bool, rt: Runtime) -> None:
    """The window: submit each request when due, step the engine, and note
    when each client holds its tokens. Work counts are accumulated as
    tokens arrive, over valid positions only (a prompt's own tokens, not its
    padding to the bucket), as the model family counts them: each request's
    prefill, and for each decode row the matrix products and the attention
    over the positions it may attend to, with the attention's least
    bytes."""
    eng, m, e, fam = server.engine, server.m, server.e, server.family
    recs = {a.uid: RequestRecord(uid=a.uid, due=a.due_s, max_new=a.max_new,
                                 prompt_len=len(a.tokens))
            for a in sched}
    rec.requests = [recs[a.uid] for a in sched]
    work = rec.work
    work["chunk"] = e["chunk"]
    for k in ("decode_tokens", "decode_attn_flops", "decode_attn_bytes",
              "decode_model_flops", "prefill_model_flops", "steps", "late_s_sum", "late_s_max",
              "late_n"):
        work.setdefault(k, 0.0)
    stats0 = dict(eng.stats)
    n_done = len(eng.completions)
    clock = time.perf_counter
    t0 = clock()
    i, n = 0, len(sched)
    last_end = 0.0
    pending = set()

    def deliver(uid: int, count: int, ts: float, te: float,
                final: Optional[Any]) -> None:
        """Tokens the host now holds for ``uid`` after the step [ts, te]."""
        r = recs[uid]
        n = r.prompt_len
        if r.admitted is None:
            r.admitted = ts
            work["prefill_model_flops"] += fam.prefill_flops(m, n)
        if count > r.n_tokens:
            t_a, t_b = r.n_tokens, count
            if r.first is None:
                r.first = te
            r.last = te
            r.n_chunks += 1
            # token 0 comes from the prefill
            a = max(t_a, 1)
            for k, v in fam.decode_work(m, n, a, t_b).items():
                work[k] += v
            work["decode_tokens"] += t_b - a
            if ts < seconds:
                rec.tokens_in_window += t_b - t_a
            r.n_tokens = count
        if final is not None:
            r.done = True
            r.failed = final.finish_reason == "error"
            r.tokens = np.asarray(final.tokens, np.int32)
            pending.discard(uid)

    while True:
        now = clock() - t0
        while i < n and sched[i].due_s <= now:
            a = sched[i]
            eng.submit(Request(uid=a.uid, tokens=a.tokens,
                               max_new_tokens=a.max_new))
            recs[a.uid].submitted = now
            pending.add(a.uid)
            i += 1
        closed = now >= seconds
        if closed and (stop_at_close or not pending
                       or now >= seconds + DRAIN_S):
            break
        if not (eng._active or eng._queue):
            if i >= n:
                if closed:
                    break
                wake = seconds
            else:
                wake = sched[i].due_s
            with rt.annotate("bench.wait"):
                time.sleep(max(wake - (clock() - t0), 0.0))
            late = clock() - t0 - wake
            if i < n and wake == sched[i].due_s:
                work["late_s_sum"] += late
                work["late_s_max"] = max(work["late_s_max"], late)
                work["late_n"] += 1
            continue
        ts = clock() - t0
        with rt.annotate("bench.step"):
            eng.step()
        te = clock() - t0
        rec.step_times.append((ts, te))
        work["steps"] += 1
        if ts < seconds:
            last_end = te
        for st in eng._active.values():
            deliver(st.request.uid, len(st.produced), ts, te, None)
        for c in eng.completions[n_done:]:
            deliver(c.uid, len(c.tokens), ts, te, c)
        n_done = len(eng.completions)
        rt.step_done(work)
    for uid in pending:
        recs[uid].failed = not stop_at_close
    rec.window_s = last_end if stop_at_close else max(last_end, seconds)
    rec.counters = _stats_diff(eng.stats, stats0)


def served_sample(rec: RunRecord, k: int, seed: int) -> List[RequestRecord]:
    fin = {r.uid: len(r.tokens) for r in rec.requests
           if r.done and not r.failed and r.tokens is not None
           and len(r.tokens) > 0}
    by_uid = {r.uid: r for r in rec.requests}
    return [by_uid[u] for u in traffic.check_sample(fin, k, seed)]


def check_rows(sample: List[RequestRecord], prompts: Dict[int, np.ndarray]
               ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(tokens, rows, served) per request: the prompt alone, at positions
    0..n-1, then the served tokens; row j's logits pick served[j]."""
    out = []
    for r in sample:
        p = prompts[r.uid]
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([p, served[:-1]])
        rows = len(p) - 1 + np.arange(len(served))
        out.append((seq, rows, served))
    return out


def reference_gaps(row_logits: Callable, params, m: Dict[str, Any], cases,
                   mode: str, seq_len: int, rows: int,
                   per_call: int = 4) -> Dict[str, List[np.ndarray]]:
    """Per request: ``ref`` gaps of the served tokens under the float32
    reference (the family's ``row_logits``), and ``ctl`` gaps of the tokens
    ``mode`` would put first (``mode="f32"``: none). Every call has the same shape (``per_call``
    requests of ``seq_len`` tokens, ``rows`` compared rows; the last group
    padded with its own last request), so the reference compiles once per
    cell and the compile cache serves every later run."""
    out: Dict[str, List[np.ndarray]] = {"ref": [], "ctl": []}
    for s in range(0, len(cases), per_call):
        group = cases[s:s + per_call]
        toks = np.zeros((per_call, seq_len), np.int32)
        rws = np.zeros((per_call, rows), np.int32)
        for j in range(per_call):
            seq, rw, _ = group[min(j, len(group) - 1)]
            toks[j, :len(seq)] = seq
            rws[j, :len(rw)] = rw
            rws[j, len(rw):] = rw[-1]
        ref = np.asarray(row_logits(params, toks, rws, m, "f32"))
        ctl = (np.asarray(row_logits(params, toks, rws, m, mode))
               if mode != "f32" else None)
        for j, (_, rw, served) in enumerate(group):
            rr = ref[j, :len(rw)]
            out["ref"].append(check.logit_gaps(rr, served))
            if ctl is not None:
                out["ctl"].append(check.logit_gaps(rr, ctl[j, :len(rw)]
                                                   .argmax(-1)))
    return out


def run(cell, *, seed: int, seconds: float, rt: Runtime, devices,
        fault: Optional[Any] = None, control: Optional[str] = None
        ) -> Tuple[RunRecord, Dict[str, Any]]:
    """One run of a serving cell; returns the record and the compared
    numbers (``max_logit_gap``, and with ``control`` the control's)."""
    rec = RunRecord(kind="serve", seconds=seconds, chips=len(devices))
    server = Server(cell, seed, devices, rt, fault=fault)
    m, e = server.m, server.e
    sched = traffic.schedule(cell.traffic, seed=seed, seconds=seconds,
                             vocab=m["vocab_size"])
    prompts = {a.uid: a.tokens for a in sched}
    batch = cell.traffic["arrivals"]["process"] == "batch"
    rec.setup_s = rt.setup_done()
    with rt.window() as win:
        drive(server, sched, seconds, rec, stop_at_close=batch, rt=rt)
    rec.trace, rec.traced_work = win.summary, rt.traced_work
    rec.traced_until = rt.traced_until
    rt.read_memory()
    # the program's state goes before the reference runs, so the reference
    # never sets the process's memory peak
    init = server.init
    sample = served_sample(rec, cell.traffic["check"]["requests"], seed)
    server.release()
    numbers: Dict[str, Any] = {"compared_requests": len(sample),
                               "compared_tokens": int(sum(
                                   len(r.tokens) for r in sample))}
    if sample:
        with rt.phase("check"):
            params = init(weights.seed_key(seed))
            cases = check_rows(sample, prompts)
            gaps = reference_gaps(server.family.row_logits, params, m,
                                  cases, control or "f32",
                                  e["prompt_bucket"] + e["max_new"],
                                  e["max_new"])
            del params
        numbers["max_logit_gap"] = float(max(g.max() for g in gaps["ref"]))
        if control:
            numbers["control_max_logit_gap"] = float(
                max(g.max() for g in gaps["ctl"]))
    return rec, numbers
